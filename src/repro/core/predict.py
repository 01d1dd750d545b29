"""Fully device-resident tiled GP prediction pipeline (paper Section 4).

Pipeline (all stages jit-compiled, data stays on device end-to-end):

  1. assemble packed training covariance  K = K_XX + sigma^2 I   (tiled)
  2. tiled Cholesky                       K = L L^T
  3. forward / backward substitution      L beta = y;  L^T alpha = beta
  4. cross covariance                     K_* = K_{X̂,X}          (tiled)
  5. predictive mean                      ŷ = K_* alpha
  6. (uncertainty) solve L V = K_{X,X̂}, then one head (scheduler.Head):
     covariance  Σ = K_{X̂,X̂} - V^T V
     variance    diag(Σ) = diag(K_{X̂,X̂}) - colsum(V ∘ V)

Two execution strategies (DESIGN.md §7):

* :func:`predict` (the default path) — the whole pipeline is ONE
  multi-stage program: :func:`repro.core.scheduler.build_program_schedule`
  emits a single DAG with cross-stage edges and
  :func:`repro.core.executor.run_program` walks it over a named buffer
  environment, under one ``jax.jit``.  Substitution rows and
  cross-covariance tiles fire the moment their factor tiles resolve — the
  paper's headline cross-stage overlap.
* :func:`predict_staged` — the staged baseline: the six stages run as
  separate executor invocations with a barrier between each (kept for
  equivalence testing and as the paper's per-stage reference).

Padding: inputs of arbitrary n / n̂ are padded to tile multiples; the padded
covariance region is identity/zero which leaves all results for the first n
(resp. n̂) entries exactly unchanged (see kernels_math docstring).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import cholesky as chol
from repro.core import executor
from repro.core import kernels_math as km
from repro.core import precision, tiling, triangular
from repro.core.scheduler import Head
from repro.dist import sharding as dist_sharding

# Dispatch-boundary trace spans (DESIGN.md §15): ``pad`` (inputs into tile
# chunks), the program's launch (``fused``, ``fused_batched``,
# ``nlml_program``) and ``untile`` (outputs back to rows).  The jnp fast
# paths run the program under jit, so executor.run_program only executes at
# trace time there — the per-dispatch record must happen HERE, at the host
# call into the cached jitted fn, where operands are concrete.
_tracer = obs.Tracer("repro.predict")


def _record_program(kind, xc, q_tiles, head, n_streams, backend):
    """Record one jitted fused-program dispatch (no-op unless obs is on).

    Skipped at trace time (``xc`` a tracer — when this caller is itself
    under an outer jit/grad the dispatch belongs to whoever runs that
    trace) and for the Pallas backend, whose unjitted eager path records
    inside executor.run_program — so no dispatch is ever counted twice.
    """
    if obs.enabled() and backend == "jnp" and not isinstance(xc, jax.core.Tracer):
        executor.record_dispatch(
            kind,
            executor.program_plan(xc.shape[-3], q_tiles, head, n_streams),
            backend=backend,
            batched=xc.ndim == 4,
        )


def _count_head(head: Head, operand) -> None:
    """Count one host dispatch of an uncertainty head, fused or warm:
    ``predict.head.variance`` / ``predict.head.covariance`` (no-op unless
    obs is on; skipped at trace time, like ``predict.warm_tail``)."""
    if head != Head.NONE and obs.enabled() and not isinstance(operand, jax.core.Tracer):
        obs.inc(f"predict.head.{head.name.lower()}")


# ---------------------------------------------------------------------------
# Tiled covariance assembly (jnp path; Pallas path lives in repro.kernels).
# ---------------------------------------------------------------------------


def _tile_kernel(
    xa, xb, row0, col0, params, n_valid_r, n_valid_c, symmetric, kernel=None
):
    """One covariance tile with global index masking (see kernels_math.cov_tile)."""
    return km.cov_tile(
        xa, xb, row0, col0, params, n_valid_r, n_valid_c, symmetric, kernel=kernel
    )


def assemble_packed_covariance(
    x_chunks: jax.Array,
    params,
    n_valid: int,
    *,
    backend: str = "jnp",
    kernel: Optional[km.Kernel] = None,
) -> jax.Array:
    """x_chunks: (M, m, D) padded feature chunks -> packed lower tiles (T, m, m).

    Only the M(M+1)/2 lower tiles are evaluated — the paper's observation that
    the tiled structure reduces assembly work (Fig. 4 discussion).
    ``kernel`` picks the registered covariance family (None -> SE).
    """
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.assemble_packed_covariance(x_chunks, params, n_valid, kernel)
    m_tiles, m, _ = x_chunks.shape
    rows, cols = tiling._packed_coords(m_tiles)
    row0 = jnp.asarray(rows * m)
    col0 = jnp.asarray(cols * m)
    fn = jax.vmap(
        functools.partial(
            _tile_kernel, params=params, n_valid_r=n_valid, n_valid_c=n_valid,
            symmetric=True, kernel=kernel,
        )
    )
    return fn(x_chunks[rows], x_chunks[cols], row0, col0)


def assemble_cross_tiles(
    xt_chunks: jax.Array,
    x_chunks: jax.Array,
    params,
    nt_valid: int,
    n_valid: int,
    *,
    backend: str = "jnp",
    kernel: Optional[km.Kernel] = None,
) -> jax.Array:
    """K_{X̂,X} tile grid: (Mhat, M, m, m) from (Mhat, m, D) × (M, m, D)."""
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.assemble_cross_tiles(
            xt_chunks, x_chunks, params, nt_valid, n_valid, kernel
        )
    mh, m, _ = xt_chunks.shape
    mt = x_chunks.shape[0]

    def one(xa, row0):
        return jax.vmap(
            lambda xb, col0: _tile_kernel(
                xa, xb, row0, col0, params, nt_valid, n_valid, symmetric=False,
                kernel=kernel,
            )
        )(x_chunks, jnp.arange(mt) * m)

    return jax.vmap(one)(xt_chunks, jnp.arange(mh) * m)


def assemble_prior_tiles(
    xt_chunks: jax.Array,
    params,
    nt_valid: int,
    *,
    backend: str = "jnp",
    kernel: Optional[km.Kernel] = None,
) -> jax.Array:
    """Prior K_{X̂,X̂} tile grid (Mhat, Mhat, m, m), no noise, padded region 0."""
    del backend  # cheap relative to cross/solves; jnp path always used
    mh, m, _ = xt_chunks.shape

    def one(xa, row0):
        return jax.vmap(
            lambda xb, col0: _tile_kernel(
                xa, xb, row0, col0, params, nt_valid, nt_valid, symmetric=False,
                kernel=kernel,
            )
        )(xt_chunks, jnp.arange(mh) * m)

    return jax.vmap(one)(xt_chunks, jnp.arange(mh) * m)


def assemble_prior_diagonal(
    xt_chunks: jax.Array, params, nt_valid, *, kernel: Optional[km.Kernel] = None
) -> jax.Array:
    """Diagonal of the prior K_{X̂,X̂} as chunks (Mhat, m), padded rows 0.

    Only the Mhat diagonal tiles are assembled — as the full grid assembles
    them, so every kernel, stationary or not, keeps its own k(x, x).
    """
    mh, m, _ = xt_chunks.shape
    row0 = jnp.arange(mh) * m
    tiles = jax.vmap(
        lambda xa, r0: _tile_kernel(
            xa, xa, r0, r0, params, nt_valid, nt_valid, symmetric=False,
            kernel=kernel,
        )
    )(xt_chunks, row0)
    return jnp.diagonal(tiles, axis1=-2, axis2=-1)


# per-problem (B,) leaf normalization — canonical impl in kernels_math
_broadcast_params = km.broadcast_params


def assemble_cross_tiles_batched(
    xt_chunks: jax.Array,
    x_chunks: jax.Array,
    params,
    nt_valid,
    n_valid,
    kernel: Optional[km.Kernel] = None,
) -> jax.Array:
    """Problem-batched K_{X̂,X} grid: (B, Mhat, M, m, m) with per-problem params.

    Always the jnp tile kernel: the Pallas assembly kernel bakes
    hyperparameters in as compile-time constants and cannot vary them across
    the problem axis (see executor._cov_batch_fn_batched).

    ``nt_valid``/``n_valid`` may be shared scalars or (B,) per-problem
    validity frontiers (the ragged-fleet path, DESIGN.md §11) — either way
    they join the problem-axis vmap.
    """
    b = xt_chunks.shape[0]
    params = _broadcast_params(params, b, kernel)
    ntb = jnp.broadcast_to(jnp.asarray(nt_valid), (b,))
    nb = jnp.broadcast_to(jnp.asarray(n_valid), (b,))
    return jax.vmap(
        lambda xt1, x1, p, nt1, n1: assemble_cross_tiles(
            xt1, x1, p, nt1, n1, kernel=kernel
        )
    )(xt_chunks, x_chunks, params, ntb, nb)


def assemble_prior_tiles_batched(
    xt_chunks: jax.Array, params, nt_valid, kernel: Optional[km.Kernel] = None
) -> jax.Array:
    """Problem-batched prior K_{X̂,X̂} grid (B, Mhat, Mhat, m, m)."""
    b = xt_chunks.shape[0]
    params = _broadcast_params(params, b, kernel)
    ntb = jnp.broadcast_to(jnp.asarray(nt_valid), (b,))
    return jax.vmap(
        lambda xt1, p, nt1: assemble_prior_tiles(xt1, p, nt1, kernel=kernel)
    )(xt_chunks, params, ntb)


def assemble_prior_diagonal_batched(
    xt_chunks: jax.Array, params, nt_valid, kernel: Optional[km.Kernel] = None
) -> jax.Array:
    """Problem-batched prior diagonal chunks (B, Mhat, m)."""
    b = xt_chunks.shape[0]
    params = _broadcast_params(params, b, kernel)
    ntb = jnp.broadcast_to(jnp.asarray(nt_valid), (b,))
    return jax.vmap(
        lambda xt1, p, nt1: assemble_prior_diagonal(xt1, p, nt1, kernel=kernel)
    )(xt_chunks, params, ntb)


def _resolve_dtype(dtype, *arrays):
    """``dtype=None`` means "preserve the (canonicalized) input dtype" —
    the explicit alternative to the old implicit float32 default."""
    if dtype is not None:
        return jnp.dtype(dtype)
    return jnp.result_type(*(jnp.asarray(a).dtype for a in arrays))


# ---------------------------------------------------------------------------
# End-to-end tiled prediction.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PosteriorState:
    """Cached per-training-set state: the packed factor and the weight vector.

    Everything a repeated ``predict`` needs that does not depend on x_test:
    re-using this skips covariance assembly, the factorization, and both
    substitutions — the O(n^3) part of the pipeline.

    This is a *live* state (DESIGN.md §10): :meth:`extend` absorbs new
    observations in O(n^2 b) via a block Cholesky append and :meth:`shrink`
    evicts the oldest ones via tiled rank updates — no re-factorization.
    The optional ``beta``/``y_chunks`` fields carry the forward-solve chunks
    and padded targets the incremental maintenance needs; states built
    before §10 (``None``) are reconstructed from the factor on demand
    (two O(n^2) packed matvecs).
    """

    lpacked: jax.Array     # (T, m, m) packed Cholesky factor of K
    alpha: jax.Array       # (M, m) chunks of K^{-1} y
    x_chunks: jax.Array    # (M, m, D) padded training features
    n: int                 # valid training rows (bucket capacity when ragged)
    m: int                 # tile size
    params: object         # hyperparameter pytree the factor was built with
    beta: Optional[jax.Array] = None      # (M, m) forward-solve chunks L^{-1} y
    y_chunks: Optional[jax.Array] = None  # (M, m) padded training targets
    # ragged stacked states only (DESIGN.md §11): per-problem validity
    # frontiers (B,) — each problem's factor is identity past its frontier
    # and the prediction/NLML heads mask with these instead of ``n``.
    n_valid: Optional[jax.Array] = None
    # the covariance family the factor was assembled with (DESIGN.md §13);
    # like ``params``, it travels with the state so warm predictions and
    # streaming updates can never silently mix kernels.
    kernel: km.Kernel = km.SQUARED_EXPONENTIAL

    def extend(self, x_new: jax.Array, y_new: jax.Array, **kwargs) -> "PosteriorState":
        """Absorb new observations in O(n^2 b) (block Cholesky append).

        Keyword arguments are forwarded to
        :func:`repro.core.update.extend_state` (``n_streams``, ``backend``,
        ``update_dtype``, ``check_finite``).  Raises
        :class:`repro.core.update.CholeskyUpdateError` on numerical failure
        — callers fall back to a fresh :func:`posterior_state`.
        """
        from repro.core import update as upd

        return upd.extend_state(self, x_new, y_new, **kwargs)

    def shrink(self, k: int, **kwargs) -> "PosteriorState":
        """Evict the k oldest observations in O(n^2 k) (tiled rank update).

        ``k`` must be a multiple of the tile size (whole leading
        tile-columns); see :func:`repro.core.update.shrink_state`.
        """
        from repro.core import update as upd

        return upd.shrink_state(self, k, **kwargs)


@precision.f32_matmuls
def posterior_state(
    x_train: jax.Array,
    y_train: jax.Array,
    params,
    m: int,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=None,
    kernel: Optional[km.Kernel] = None,
) -> PosteriorState:
    """Assemble + factor K and solve for alpha = K^{-1} y (the cacheable part)."""
    kernel = km.resolve_kernel(kernel)
    n = x_train.shape[0]
    dtype = _resolve_dtype(dtype, x_train)
    xc = tiling.pad_features(x_train, m, dtype=dtype)
    yc = tiling.pad_vector(y_train, m, dtype=dtype)
    packed = assemble_packed_covariance(xc, params, n, backend=backend, kernel=kernel)
    lpacked = chol.tiled_cholesky(
        packed, n_streams=n_streams, backend=backend, update_dtype=update_dtype
    )
    beta = triangular.forward_substitution(lpacked, yc, n_streams=n_streams)
    alpha = triangular.backward_substitution(lpacked, beta, n_streams=n_streams)
    return PosteriorState(
        lpacked=lpacked, alpha=alpha, x_chunks=xc, n=n, m=m, params=params,
        beta=beta, y_chunks=yc, kernel=kernel,
    )


@precision.f32_matmuls
def predict_from_state(
    state: PosteriorState,
    x_test: jax.Array,
    *,
    head: Head = Head.NONE,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    dtype=None,
):
    """Prediction given a (possibly cached) :class:`PosteriorState`.

    The kernel hyperparameters come from the state itself — alpha and the
    factor are only valid for the params K was assembled with, so accepting
    them separately would invite a silent mismatch.  ``dtype=None`` follows
    the state's storage dtype.  Returns the mean, ``(mean, var)`` for
    ``Head.VARIANCE`` or ``(mean, sigma)`` for ``Head.COVARIANCE``.
    """
    params = state.params
    kernel = state.kernel
    nh = x_test.shape[0]
    head = Head(head)
    if obs.enabled() and not isinstance(x_test, jax.core.Tracer):
        obs.inc("predict.warm_tail")
    _count_head(head, x_test)
    dtype = state.x_chunks.dtype if dtype is None else jnp.dtype(dtype)
    with _tracer.span("pad"):
        xtc = tiling.pad_features(x_test, state.m, dtype=dtype)
    kstar = assemble_cross_tiles(
        xtc, state.x_chunks, params, nh, state.n, backend=backend, kernel=kernel
    )
    mean = triangular.tiled_matvec(kstar, state.alpha).reshape(-1)[:nh]
    if head == Head.NONE:
        return mean

    # L V = K_{X,X̂}:  B tiles are the transpose grid of K_* tiles.
    b_tiles = jnp.einsum("qiab->iqba", kstar)
    v = triangular.forward_substitution_matrix(state.lpacked, b_tiles, n_streams=n_streams)
    if head == Head.VARIANCE:
        prior = assemble_prior_diagonal(xtc, params, nh, kernel=kernel)
        var = prior - triangular.tiled_gram_diag(v)            # (Q, mq)
        with _tracer.span("untile"):
            return mean, var.reshape(-1)[:nh]
    w = triangular.tiled_gram(v)                               # (Q, Q, mq, mq)
    prior = assemble_prior_tiles(xtc, params, nh, backend=backend, kernel=kernel)
    sigma_tiles = prior - w
    with _tracer.span("untile"):
        sigma = tiling.untile_dense(sigma_tiles)[:nh, :nh]
    return mean, sigma


# ---------------------------------------------------------------------------
# Fused whole-pipeline prediction (one program, one jit — DESIGN.md §7).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _fused_program_fn(
    head: Head,
    n_streams: Optional[int],
    backend: str,
    update_dtype,
    n_valid: Optional[int],
    nt_valid: Optional[int],
    batch_dispatch: str = "flat",
    mesh=None,
    kernel: Optional[km.Kernel] = None,
):
    """The ONE jit of the fused pipeline, cached per static configuration.

    Shapes are implied by the traced operands; the program plan itself is
    lru-cached inside :func:`repro.core.executor.program_plan`.  The cache
    is shared by the single-problem and problem-batched paths — B enters
    only through the traced operand shapes (jit re-specializes per B), never
    through the plan.  The Pallas backend bakes hyperparameters into its
    assembly kernels as compile-time constants, so it runs unjitted at this
    level (each Pallas call is its own compiled kernel).

    **Ragged variant:** keyed with ``n_valid=None`` the returned function
    takes the validity frontiers as two extra *traced* operands
    ``fn(xc, yc, xtc, params, n_valid, nt_valid)`` — (B,) arrays or
    scalars.  One jit trace (and one executor Plan) then serves every
    per-problem size mix of a bucket geometry: frontier values never force
    a retrace (DESIGN.md §11).

    **Sharded variant (DESIGN.md §12):** ``mesh`` pins every B-leading
    buffer to the fleet layout inside the jit.  The mesh changes the traced
    jaxpr (sharding constraints are ops), so it joins the lru key — but it
    never reaches the executor's Plan caches, which stay shard-invariant.

    **Kernel zoo (DESIGN.md §13):** the (hashable) ``kernel`` instance joins
    the lru key too — each covariance family gets its own jit — while the
    executor's Plan caches stay kernel-invariant (only ASSEMBLE/CROSS/PRIOR
    payloads differ).

    ``head`` (:class:`repro.core.scheduler.Head`) is the program's
    uncertainty head: each head is its own program and its own jit.
    """
    if n_valid is None:

        def ragged_fn(xc, yc, xtc, params, nv, ntv):
            return executor.run_program(
                xc,
                yc,
                xtc,
                params,
                nv,
                ntv,
                head=head,
                n_streams=n_streams,
                backend=backend,
                update_dtype=update_dtype,
                batch_dispatch=batch_dispatch,
                mesh=mesh,
                kernel=kernel,
            )

        ragged_fn = precision.f32_matmuls(ragged_fn)
        return jax.jit(ragged_fn) if backend == "jnp" else ragged_fn

    def fn(xc, yc, xtc, params):
        return executor.run_program(
            xc,
            yc,
            xtc,
            params,
            n_valid,
            nt_valid,
            head=head,
            n_streams=n_streams,
            backend=backend,
            update_dtype=update_dtype,
            batch_dispatch=batch_dispatch,
            mesh=mesh,
            kernel=kernel,
        )

    fn = precision.f32_matmuls(fn)
    return jax.jit(fn) if backend == "jnp" else fn


def predict_fused(
    x_train: jax.Array,
    y_train: jax.Array,
    x_test: jax.Array,
    params,
    m: int,
    *,
    head: Head = Head.NONE,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=None,
    with_state: bool = False,
    kernel: Optional[km.Kernel] = None,
):
    """Whole-pipeline fused prediction: one program, one jit, one plan cache.

    Runs assembly, factorization, both substitutions, cross covariance and
    the prediction heads as a single multi-stage program with cross-stage
    wavefronts (executor.run_program).  Returns the mean, ``(mean, var)``
    for ``head=Head.VARIANCE`` or ``(mean, sigma)`` for
    ``Head.COVARIANCE``; with ``with_state=True`` also the
    :class:`PosteriorState` sliced out of the program's buffer environment,
    so callers can reuse the factor for later staged predictions.
    """
    kernel = km.resolve_kernel(kernel)
    head = Head(head)
    n = x_train.shape[0]
    nh = x_test.shape[0]
    dtype = _resolve_dtype(dtype, x_train)
    with _tracer.span("pad"):
        xc = tiling.pad_features(x_train, m, dtype=dtype)
        yc = tiling.pad_vector(y_train, m, dtype=dtype)
        xtc = tiling.pad_features(x_test, m, dtype=dtype)
    fn = _fused_program_fn(
        head, n_streams, backend, update_dtype, n, nh, kernel=kernel
    )
    _record_program("run_program", xc, xtc.shape[-3], head, n_streams, backend)
    _count_head(head, xc)
    with _tracer.span("fused"):
        env = fn(xc, yc, xtc, params)
    with _tracer.span("untile"):
        mean = env["mean"].reshape(-1)[:nh]
        if head == Head.COVARIANCE:
            q_tiles = xtc.shape[0]
            sigma_tiles = env["prior"].reshape(q_tiles, q_tiles, m, m)
            result = (mean, tiling.untile_dense(sigma_tiles)[:nh, :nh])
        elif head == Head.VARIANCE:
            result = (mean, env["prior"].reshape(-1)[:nh])
        else:
            result = mean
    if not with_state:
        return result
    # env["y"] holds beta after the in-place forward substitution (§7)
    state = PosteriorState(
        lpacked=env["packed"], alpha=env["alpha"], x_chunks=xc, n=n, m=m,
        params=params, beta=env["y"], y_chunks=yc, kernel=kernel,
    )
    return result, state


def predict_fused_batched(
    x_train: jax.Array,
    y_train: jax.Array,
    x_test: jax.Array,
    params,
    m: int,
    *,
    head: Head = Head.NONE,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=None,
    with_state: bool = False,
    batch_dispatch: str = "flat",
    n_valid=None,
    nt_valid=None,
    mesh=None,
    kernel: Optional[km.Kernel] = None,
):
    """Fused prediction for B independent GPs in ONE batched program.

    x_train (B, n, D) / y_train (B, n) / x_test (B, n̂, D) stacked problems
    of identical shape; ``params`` leaves may be scalars (shared) or (B,)
    (per-problem).  The same lru-cached Plan as the single-problem program
    drives all B problems — identical launch count, every launch B times
    wider (DESIGN.md §9).  Shares :func:`_fused_program_fn`'s jit cache with
    the unbatched path (jit re-specializes on the leading B axis).

    **Ragged batches (DESIGN.md §11):** pass ``n_valid`` — a (B,) vector of
    per-problem valid training counts — when the stacked problems are
    zero-padded to a shared bucket capacity; rows past each frontier must
    be zero.  ``nt_valid`` optionally masks per-problem test counts the
    same way (mean/var/sigma rows past a problem's own count come back
    zero).
    The frontiers are traced operands: every size mix of the same stacked
    shape shares one jit trace and one executor Plan.

    **Sharded fleets (DESIGN.md §12):** ``mesh`` commits the stacked inputs
    to the fleet layout (B over the mesh's DP axes) and pins every env
    buffer to it inside the program — pure data parallelism, zero
    collectives, one Plan regardless of device count.

    Returns mean (B, n̂), ``(mean, var)`` with var (B, n̂) for
    ``Head.VARIANCE``, or ``(mean, sigma)`` with sigma (B, n̂, n̂) for
    ``Head.COVARIANCE``; with ``with_state=True`` also the stacked
    :class:`PosteriorState` (leading B axis on lpacked/alpha/x_chunks).
    """
    kernel = km.resolve_kernel(kernel)
    head = Head(head)
    b, n = x_train.shape[0], x_train.shape[1]
    nh = x_test.shape[1]
    dtype = _resolve_dtype(dtype, x_train)
    with _tracer.span("pad"):
        xc = tiling.pad_features(x_train, m, dtype=dtype)    # (B, M, m, D)
        yc = tiling.pad_vector(y_train, m, dtype=dtype)      # (B, M, m)
        xtc = tiling.pad_features(x_test, m, dtype=dtype)    # (B, Q, m, D)
        if mesh is not None:
            xc = dist_sharding.device_put_fleet(xc, mesh)
            yc = dist_sharding.device_put_fleet(yc, mesh)
            xtc = dist_sharding.device_put_fleet(xtc, mesh)
    ragged = n_valid is not None
    if ragged:
        nv = jnp.asarray(n_valid, jnp.int32)
        ntv = jnp.asarray(nh if nt_valid is None else nt_valid, jnp.int32)
        fn = _fused_program_fn(
            head, n_streams, backend, update_dtype, None, None,
            batch_dispatch, mesh, kernel,
        )
        _record_program(
            "run_program", xc, xtc.shape[-3], head, n_streams, backend
        )
        _count_head(head, xc)
        with _tracer.span("fused_batched"):
            env = fn(xc, yc, xtc, params, nv, ntv)
    else:
        fn = _fused_program_fn(
            head, n_streams, backend, update_dtype, n, nh, batch_dispatch,
            mesh, kernel,
        )
        _record_program(
            "run_program", xc, xtc.shape[-3], head, n_streams, backend
        )
        _count_head(head, xc)
        with _tracer.span("fused_batched"):
            env = fn(xc, yc, xtc, params)
    with _tracer.span("untile"):
        mean = env["mean"].reshape(b, -1)[:, :nh]
        if head == Head.COVARIANCE:
            q_tiles = xtc.shape[1]
            sigma_tiles = env["prior"].reshape(b, q_tiles, q_tiles, m, m)
            result = (mean, tiling.untile_dense(sigma_tiles)[:, :nh, :nh])
        elif head == Head.VARIANCE:
            result = (mean, env["prior"].reshape(b, -1)[:, :nh])
        else:
            result = mean
    if not with_state:
        return result
    state = PosteriorState(
        lpacked=env["packed"], alpha=env["alpha"], x_chunks=xc, n=n, m=m,
        params=params, beta=env["y"], y_chunks=yc,
        n_valid=nv if ragged else None, kernel=kernel,
    )
    return result, state


@precision.f32_matmuls
def predict_from_state_batched(
    state: PosteriorState,
    x_test: jax.Array,
    *,
    head: Head = Head.NONE,
    n_streams: Optional[int] = None,
    dtype=None,
    nt_valid=None,
    mesh=None,
):
    """Warm batched prediction from a stacked :class:`PosteriorState`.

    The state holds B factors/weights (leading B axis); x_test (B, n̂, D).
    Reuses the cached O(n^3) work and runs only the cross-covariance / mean
    (and, for an uncertainty ``head``, the matrix-solve tail) — all through
    the batched executor plans.  Assembly uses the jnp tile kernel (per-problem params).

    Ragged states (``state.n_valid`` set) mask the cross covariance at each
    problem's own frontier — required for correctness, not just economy:
    the padded feature rows are zeros, so an unmasked K_* column against
    them would be k(x̂, 0) ≠ 0 and corrupt the solve tail (the masked
    factor is identity there).  ``nt_valid`` (scalar or (B,)) optionally
    masks per-problem test counts; rows past a problem's count come back 0.
    """
    params = state.params
    kernel = state.kernel
    b, nh = x_test.shape[0], x_test.shape[1]
    head = Head(head)
    if obs.enabled() and not isinstance(x_test, jax.core.Tracer):
        obs.inc("predict.warm_tail_batched")
    _count_head(head, x_test)
    dtype = state.x_chunks.dtype if dtype is None else jnp.dtype(dtype)
    # the warm tail runs op-by-op (no enclosing jit): committing the test
    # block to the fleet layout is enough — the cached state buffers carry
    # their sharding out of the fused program and propagate it through the
    # assembly/matvec ops.
    with _tracer.span("pad"):
        xtc = tiling.pad_features(x_test, state.m, dtype=dtype)
        xtc = dist_sharding.device_put_fleet(xtc, mesh)
    nv = state.n if state.n_valid is None else state.n_valid
    ntv = nh if nt_valid is None else nt_valid
    kstar = assemble_cross_tiles_batched(
        xtc, state.x_chunks, params, ntv, nv, kernel
    )
    mean = triangular.tiled_matvec(kstar, state.alpha).reshape(b, -1)[:, :nh]
    if head == Head.NONE:
        return mean

    # L V = K_{X,X̂}:  B tiles are the per-problem transpose grids of K_*.
    b_tiles = jnp.einsum("zqiab->ziqba", kstar)
    v = triangular.forward_substitution_matrix(
        state.lpacked, b_tiles, n_streams=n_streams
    )
    if head == Head.VARIANCE:
        prior = assemble_prior_diagonal_batched(xtc, params, ntv, kernel)
        var = prior - triangular.tiled_gram_diag(v)      # (B, Q, mq)
        with _tracer.span("untile"):
            return mean, var.reshape(b, -1)[:, :nh]
    w = triangular.tiled_gram(v)                         # (B, Q, Q, mq, mq)
    prior = assemble_prior_tiles_batched(xtc, params, ntv, kernel)
    sigma_tiles = prior - w
    with _tracer.span("untile"):
        sigma = tiling.untile_dense(sigma_tiles)[:, :nh, :nh]
    return mean, sigma


def nlml_program_env(
    x_train: jax.Array,
    y_train: jax.Array,
    params,
    m: int,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=None,
    batch_dispatch: str = "flat",
    n_valid=None,
    mesh=None,
    kernel: Optional[km.Kernel] = None,
):
    """Run the NLML prefix of the fused program (DESIGN.md §8).

    ``q_tiles=0`` reduces the whole-pipeline DAG to assembly → factorization
    → both substitutions; the returned buffer environment's ``packed`` slice
    is the factor (log-determinant head) and ``alpha`` the weight chunks
    (quadratic-term head).  Shares the jit/plan caches with
    :func:`predict_fused` — the NLML program *is* the prediction program with
    zero test tiles.  Returns ``(env, yc)`` with ``yc`` the padded target
    chunks (the quadratic term is ``sum(yc * env['alpha'])``).

    Fully traceable under ``jax.grad``: jnp ops differentiate natively and
    the Pallas tile ops carry reference VJPs; assembly falls back to the jnp
    tile kernel when the hyperparameters are traced (executor._cov_batch_fn).

    Problem-batched with x_train (B, n, D) / y_train (B, n): the env buffers
    gain the leading B axis and ``env["alpha"]`` / ``env["packed"]`` hold B
    independent weight chunks / factors (DESIGN.md §9).  Ragged batches
    pass ``n_valid`` (B,) per-problem counts — stacks zero-padded to a
    bucket capacity factor through ONE traced program (DESIGN.md §11).
    """
    kernel = km.resolve_kernel(kernel)
    n = x_train.shape[-2]
    dtype = _resolve_dtype(dtype, x_train)
    with _tracer.span("pad"):
        xc = tiling.pad_features(x_train, m, dtype=dtype)
        yc = tiling.pad_vector(y_train, m, dtype=dtype)
        xtc = jnp.zeros(xc.shape[:-3] + (0, m, xc.shape[-1]), dtype)
        if mesh is not None and xc.ndim == 4:
            xc = dist_sharding.device_put_fleet(xc, mesh)
            yc = dist_sharding.device_put_fleet(yc, mesh)
        else:
            mesh = None  # unbatched programs have no problem axis to shard
    if n_valid is not None:
        fn = _fused_program_fn(
            Head.NONE, n_streams, backend, update_dtype, None, None,
            batch_dispatch, mesh, kernel,
        )
        nv = jnp.asarray(n_valid, jnp.int32)
        _record_program("run_program", xc, 0, Head.NONE, n_streams, backend)
        with _tracer.span("nlml_program"):
            return fn(xc, yc, xtc, params, nv, jnp.asarray(0, jnp.int32)), yc
    fn = _fused_program_fn(
        Head.NONE, n_streams, backend, update_dtype, n, 0, batch_dispatch, mesh,
        kernel,
    )
    _record_program("run_program", xc, 0, Head.NONE, n_streams, backend)
    with _tracer.span("nlml_program"):
        return fn(xc, yc, xtc, params), yc


def predict(
    x_train: jax.Array,
    y_train: jax.Array,
    x_test: jax.Array,
    params,
    m: int,
    *,
    full_cov: bool = False,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=None,
    kernel: Optional[km.Kernel] = None,
):
    """Tiled GP prediction — the fused whole-pipeline program.

    Returns mean (n̂,), or (mean, var) with ``full_cov=False`` semantics of
    the paper's *Predict with Full Covariance* operation when ``full_cov``:
    (mean (n̂,), posterior covariance (n̂, n̂)).

    The old ``fused=False`` wrapper branch is gone: the staged per-stage
    baseline lives behind :func:`predict_staged` (explicitly, for the
    fused-vs-staged benchmarks) and behind the warm
    :func:`posterior_state` / :func:`predict_from_state` pair everywhere
    else.
    """
    return predict_fused(
        x_train,
        y_train,
        x_test,
        params,
        m,
        head=Head.COVARIANCE if full_cov else Head.NONE,
        n_streams=n_streams,
        backend=backend,
        update_dtype=update_dtype,
        dtype=dtype,
        kernel=kernel,
    )


def predict_staged(
    x_train: jax.Array,
    y_train: jax.Array,
    x_test: jax.Array,
    params,
    m: int,
    *,
    full_cov: bool = False,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=None,
    kernel: Optional[km.Kernel] = None,
):
    """The staged per-stage baseline: six executor invocations with a
    barrier between each — the paper's per-stage reference that the fused
    program is benchmarked against (DESIGN.md §7)."""
    state = posterior_state(
        x_train,
        y_train,
        params,
        m,
        n_streams=n_streams,
        backend=backend,
        update_dtype=update_dtype,
        dtype=dtype,
        kernel=kernel,
    )
    return predict_from_state(
        state,
        x_test,
        head=Head.COVARIANCE if full_cov else Head.NONE,
        n_streams=n_streams,
        backend=backend,
        dtype=dtype,
    )


@precision.f32_matmuls
def predict_monolithic(
    x_train: jax.Array,
    y_train: jax.Array,
    x_test: jax.Array,
    params,
    *,
    full_cov: bool = False,
    dtype=None,
    kernel: Optional[km.Kernel] = None,
):
    """Reference (cuSOLVER-analogue) dense pipeline: one-call Cholesky."""
    dtype = _resolve_dtype(dtype, x_train)
    x = x_train.astype(dtype)
    y = y_train.astype(dtype)
    xt = x_test.astype(dtype)
    k = km.assemble_covariance(x, params, kernel=kernel, dtype=dtype)
    l = chol.monolithic_cholesky(k)
    beta = jax.lax.linalg.triangular_solve(
        l, y[:, None], left_side=True, lower=True
    )
    alpha = jax.lax.linalg.triangular_solve(
        l, beta, left_side=True, lower=True, transpose_a=True
    )[:, 0]
    kstar = km.assemble_cross_covariance(xt, x, params, kernel=kernel, dtype=dtype)
    mean = kstar @ alpha
    if not full_cov:
        return mean
    v = jax.lax.linalg.triangular_solve(l, kstar.T, left_side=True, lower=True)
    prior = km.assemble_prior_covariance(xt, params, kernel=kernel, dtype=dtype)
    sigma = prior - v.T @ v
    return mean, sigma


obs.register_cache("predict.fused_program_fn", _fused_program_fn)
