"""Tiled Nyström low-rank tier: O(n m²) approximate GP regression (DESIGN.md §14).

The exact tier factorizes the n×n covariance; this tier factorizes only the
m×m *inner system* of the DTC/Nyström approximation (m = number of inducing
points, m ≪ n):

    A  = K_uu + σ⁻² K_un K_nu                     (m × m)
    μ* = σ⁻² K_*u A⁻¹ K_un y
    Σ* = K_** − K_*u K_uu⁻¹ K_u* + K_*u A⁻¹ K_u*

Everything n-sized goes through the same tiled bulk-op machinery as the
exact tier: K_un is a (MU × M) tile grid assembled by the CROSS family, the
contraction c = K_un y is the LRGEMM bulk-op family
(``executor.run_lowrank_contraction``), and the m×m factorizations reuse
the fused POTRF/TRSM/SYRK pipeline — the Plans are method-invariant, so
the Plan cache is shared with the exact tier.

Numerically the inner system is held in *whitened* (SGPR) form: with
W = L_uu⁻¹ K_un,

    B  = I + σ⁻² W Wᵀ        so that        A = L_uu B L_uuᵀ.

A itself is badly conditioned in float32 (its scale grows like σ⁻² n while
its smallest eigenvalue is the K_uu jitter), but B's eigenvalues are ≥ 1 by
construction, so chol(B) never goes indefinite.  All A⁻¹ applications
become L_uu/L_B triangular-solve sandwiches, and
log det A − log det K_uu = log det B falls out of L_B's diagonal directly.

The NLML uses the Woodbury identity + matrix determinant lemma (see
``mll.nlml_lowrank``), so training is O(n m²) per step too.

Inducing-point selection (``select_inducing``) supports a strided subset of
the training inputs, a few Lloyd iterations of k-means ("kmeans-lite"), or
an explicit user-supplied set.  The selected inducing inputs always pass
through ``jax.lax.stop_gradient`` — hyperparameter gradients treat u as
fixed (standard sparse-GP practice), which also keeps the hand-derived
custom VJP in ``mll`` consistent with autodiff of this builder.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor
from repro.core import kernels_math as km
from repro.core import predict as pred
from repro.core import precision, tiling, triangular

# K_uu is regularized with a small jitter (NOT the noise variance) so the
# approximation converges to the exact GP as m -> n.  1e-4 is the float32
# floor: SE Gram matrices are numerically rank-deficient and chol(K_uu)
# needs the jitter to dominate the ~eps * m roundoff in the factorization;
# pass a smaller value explicitly when building float64 states.
DEFAULT_JITTER = 1e-4


# ---------------------------------------------------------------------------
# Inducing-point selection.
# ---------------------------------------------------------------------------


def _subset_indices(mu: int, nv) -> jax.Array:
    """Strided subset indices, ragged-safe: distinct for the first min(mu, nv)
    rows even when nv < mu (the tail repeats the last valid point)."""
    nv = jnp.asarray(nv, jnp.int32)
    step = jnp.maximum(jnp.minimum(mu, nv), 1)
    idx = (jnp.arange(mu, dtype=jnp.int32) * nv) // step
    return jnp.clip(idx, 0, jnp.maximum(nv - 1, 0))


def _kmeans_lite(x: jax.Array, mu: int, nv, iters: int) -> jax.Array:
    """A few Lloyd iterations, pure jnp; rows >= nv are masked out."""
    n = x.shape[0]
    centers = x[_subset_indices(mu, nv)]
    valid = (jnp.arange(n) < nv)[:, None]  # (n, 1)
    for _ in range(iters):
        d2 = km.sq_dists(x, centers)  # (n, mu)
        assign = jnp.argmin(d2, axis=1)
        onehot = jax.nn.one_hot(assign, mu, dtype=x.dtype) * valid
        counts = jnp.sum(onehot, axis=0)  # (mu,)
        sums = onehot.T @ x  # (mu, D)
        centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
        )
    return centers


def _select_one(x, m_inducing, strategy, nv, kmeans_iters):
    if strategy == "subset":
        return x[_subset_indices(m_inducing, nv)]
    if strategy == "kmeans-lite":
        return _kmeans_lite(x, m_inducing, nv, kmeans_iters)
    raise ValueError(f"unknown inducing strategy: {strategy!r}")


def select_inducing(
    x: jax.Array,
    m_inducing: int,
    *,
    strategy: str = "subset",
    inducing: Optional[jax.Array] = None,
    n_valid=None,
    kmeans_iters: int = 4,
) -> Tuple[jax.Array, object]:
    """Pick inducing inputs u from training inputs x.

    Returns ``(u, mu_valid)`` where u is (m_inducing, D) — or (B, m_inducing,
    D) for batched x — and ``mu_valid`` is the per-problem count of distinct
    inducing points (None when every problem fills all m_inducing slots).
    u is wrapped in ``stop_gradient``: hyperparameter training treats the
    inducing set as fixed.
    """
    if inducing is not None:
        u = jnp.asarray(inducing)
        if u.shape[-2] != m_inducing:
            raise ValueError(
                f"explicit inducing set has {u.shape[-2]} points, expected "
                f"m_inducing={m_inducing}"
            )
        return jax.lax.stop_gradient(u), None
    batched = x.ndim == 3
    if n_valid is None:
        nv = x.shape[-2]
        nv = jnp.full((x.shape[0],), nv, jnp.int32) if batched else nv
    else:
        nv = jnp.asarray(n_valid, jnp.int32)
    if batched:
        u = jax.vmap(
            lambda xi, nvi: _select_one(xi, m_inducing, strategy, nvi, kmeans_iters)
        )(x, nv)
    else:
        u = _select_one(x, m_inducing, strategy, nv, kmeans_iters)
    mu_valid = jnp.minimum(m_inducing, nv)
    if not batched and isinstance(nv, int):
        mu_valid = min(m_inducing, nv)
        if mu_valid == m_inducing:
            mu_valid = None
    return jax.lax.stop_gradient(u), mu_valid


# ---------------------------------------------------------------------------
# Low-rank posterior state.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LowRankState:
    """Cached Nyström pieces — everything needed for O(m²)-per-test-point
    prediction and O(m³) streaming absorption of new data.

    Shapes are written single-problem; every array field grows a leading
    (B,) axis under problem batching.
    """

    u_chunks: jax.Array  # (MU, m, D) padded inducing chunks
    luu_packed: jax.Array  # packed lower tiles of chol(K_uu + jitter I)
    b_packed: jax.Array  # packed lower tiles of B = I + s^-2 W W^T (unfactored)
    lb_packed: jax.Array  # packed lower tiles of chol(B)
    c_chunks: jax.Array  # (MU, m) tiled  c = K_un y
    gamma: jax.Array  # (MU, m) tiled  A^{-1} c  (A = L_uu B L_uu^T)
    yty: jax.Array  # scalar (or (B,))  yᵀy
    n: int  # padded training-point count
    m: int  # tile size
    m_inducing: int
    params: object
    jitter: float
    mu_valid: Optional[jax.Array] = None  # (B,) or None
    n_valid: Optional[jax.Array] = None  # (B,) or None
    kernel: object = km.SQUARED_EXPONENTIAL


# ---------------------------------------------------------------------------
# Assembly helpers.
# ---------------------------------------------------------------------------


def _retune_diag(packed, mu_tiles, m, delta, mu_valid, batched):
    """Shift the *valid* diagonal of packed symmetric tiles by ``delta``.

    Symmetric assembly pins the diagonal to kernel.diag + noise; the inner
    matrices here want jitter instead, so post-correct by
    delta = jitter - noise on rows < mu_valid (padding rows keep their
    identity pinning).  Works uniformly for every kernel family.
    """
    idx = np.array([tiling.packed_index(p, p, mu_tiles) for p in range(mu_tiles)])
    take, put, _ = executor._env_ops(batched)
    diag = take(packed, idx)  # (..., MU, m, m)
    row = jnp.arange(mu_tiles * m).reshape(mu_tiles, m)
    if mu_valid is None:
        mask = jnp.ones((mu_tiles, m), bool)
    elif batched:
        mask = row[None] < jnp.asarray(mu_valid, jnp.int32)[:, None, None]
    else:
        mask = row < jnp.asarray(mu_valid, jnp.int32)
    eye = jnp.eye(m, dtype=packed.dtype)
    # delta may be a scalar or per-problem (B,); align it under the (MU, m) mask
    delta = jnp.asarray(delta)[..., None, None]
    shift = jnp.where(mask, delta, 0.0)[..., :, :, None] * eye
    return put(packed, idx, diag + shift.astype(packed.dtype))


def _assemble_kuu(u_chunks, params, mu_valid, *, backend, kernel, batched):
    """Packed lower tiles of K_uu (diag pinned to k(0,0) + noise; identity
    padding past mu_valid)."""
    if batched:
        b = u_chunks.shape[0]
        mu = u_chunks.shape[1] * u_chunks.shape[2]
        mv = (
            jnp.full((b,), mu, jnp.int32)
            if mu_valid is None
            else jnp.broadcast_to(jnp.asarray(mu_valid, jnp.int32), (b,))
        )
        bp = pred._broadcast_params(params, b, kernel)
        return jax.vmap(
            lambda uc, p, v: pred.assemble_packed_covariance(uc, p, v, kernel=kernel)
        )(u_chunks, bp, mv)
    mv = u_chunks.shape[0] * u_chunks.shape[1] if mu_valid is None else mu_valid
    use_pallas = backend == "pallas" and km.params_concrete(params)
    return pred.assemble_packed_covariance(
        u_chunks, params, mv,
        backend="pallas" if use_pallas else "jnp", kernel=kernel,
    )


def _assemble_cross(u_chunks, x_chunks, params, mu_valid, n_valid, *, backend, kernel, batched):
    """K_un tile grid (MU, M, m, m) — rows = inducing, cols = training."""
    if batched:
        return pred.assemble_cross_tiles_batched(
            u_chunks, x_chunks, params, mu_valid, n_valid, kernel=kernel
        )
    use_pallas = backend == "pallas" and km.params_concrete(params)
    mu = u_chunks.shape[0] * u_chunks.shape[1]
    n = x_chunks.shape[0] * x_chunks.shape[1]
    return pred.assemble_cross_tiles(
        u_chunks,
        x_chunks,
        params,
        mu if mu_valid is None else mu_valid,
        n if n_valid is None else n_valid,
        backend="pallas" if use_pallas else "jnp",
        kernel=kernel,
    )


def _packed_from_grid(grid, mu_tiles, batched):
    """Gather the lower-triangle tiles of a symmetric (MU, MU, m, m) grid
    into packed order."""
    rows, cols = tiling._packed_coords(mu_tiles)
    if batched:
        return grid[:, rows, cols]
    return grid[rows, cols]


def _packed_eye(mu_tiles, m, dtype):
    """Packed lower tiles of the (MU*m × MU*m) identity."""
    rows, cols = tiling._packed_coords(mu_tiles)
    base = np.zeros((len(rows), m, m), np.float64)
    base[rows == cols] = np.eye(m)
    return jnp.asarray(base, dtype)


def _inner_solve(luu, lb, rhs, n_streams):
    """gamma = A^{-1} rhs via the whitened sandwich
    L_uu^-T L_B^-T L_B^-1 L_uu^-1 rhs (four triangular sweeps)."""
    z = executor.run_solve(luu, rhs, lower=True, n_streams=n_streams)
    z = executor.run_solve(lb, z, lower=True, n_streams=n_streams)
    z = executor.run_solve(lb, z, lower=False, n_streams=n_streams)
    return executor.run_solve(luu, z, lower=False, n_streams=n_streams)


# ---------------------------------------------------------------------------
# State construction.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _build_fn(cfg):
    (n_streams, backend, update_dtype, batch_dispatch, kernel, jitter, _dt, batched) = cfg
    z = "z" if batched else ""

    def build(u_chunks, x_chunks, y_chunks, params, mu_valid, n_valid):
        kuu = _assemble_kuu(
            u_chunks, params, mu_valid, backend=backend, kernel=kernel, batched=batched
        )
        mu_tiles, m = (u_chunks.shape[-3], u_chunks.shape[-2])
        noise = jnp.asarray(kernel.noise(params))
        inv_noise = 1.0 / noise
        kuu = _retune_diag(
            kuu, mu_tiles, m, jnp.asarray(jitter) - noise, mu_valid, batched
        )
        kun = _assemble_cross(
            u_chunks, x_chunks, params, mu_valid, n_valid,
            backend=backend, kernel=kernel, batched=batched,
        )
        c = executor.run_lowrank_contraction(
            kun, y_chunks, backend=backend,
            batch_dispatch=batch_dispatch, n_streams=n_streams,
        )
        luu = executor.run_cholesky(
            kuu, backend=backend, n_streams=n_streams,
            update_dtype=update_dtype, batch_dispatch=batch_dispatch,
        )
        # whitened cross grid W = L_uu^-1 K_un, then B = I + s^-2 W W^T
        w = executor.run_solve(luu, kun, lower=True, n_streams=n_streams)
        gram = jnp.einsum(f"{z}pjac,{z}qjbc->{z}pqab", w, w)
        b_packed = _packed_eye(mu_tiles, m, kuu.dtype) + inv_noise[
            ..., None, None, None
        ] * _packed_from_grid(gram, mu_tiles, batched)
        lb = executor.run_cholesky(
            b_packed, backend=backend, n_streams=n_streams,
            update_dtype=update_dtype, batch_dispatch=batch_dispatch,
        )
        gamma = _inner_solve(luu, lb, c, n_streams)
        # rows past the validity frontier may hold caller padding, not zeros
        row = jnp.arange(y_chunks.shape[-2] * y_chunks.shape[-1]).reshape(
            y_chunks.shape[-2:]
        )
        ymask = row[None] < n_valid[:, None, None] if batched else row < n_valid
        yty = jnp.sum(jnp.where(ymask, y_chunks * y_chunks, 0.0), axis=(-2, -1))
        return dict(
            luu_packed=luu, b_packed=b_packed, lb_packed=lb,
            c_chunks=c, gamma=gamma, yty=yty,
        )

    build = precision.f32_matmuls(build)
    if backend == "jnp":
        return jax.jit(build)
    return build


def lowrank_state(
    x: jax.Array,
    y: jax.Array,
    params,
    m_inducing: int,
    tile_size: int,
    *,
    strategy: str = "subset",
    inducing: Optional[jax.Array] = None,
    jitter: float = DEFAULT_JITTER,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=jnp.float32,
    batch_dispatch: str = "flat",
    n_valid=None,
    kernel=None,
) -> LowRankState:
    """Build the Nyström low-rank posterior state.

    x: (n, D) or (B, n, D); y: (n,) or (B, n).  ``n_valid`` (None, int, or
    (B,) array) marks ragged problems — rows past it are padding.
    """
    kernel = km.resolve_kernel(kernel)
    x = jnp.asarray(x, dtype)
    y = jnp.asarray(y, dtype)
    batched = x.ndim == 3
    u, mu_valid = select_inducing(
        x, m_inducing, strategy=strategy, inducing=inducing, n_valid=n_valid
    )
    uc = tiling.pad_features(u, tile_size)
    xc = tiling.pad_features(x, tile_size)
    yc = tiling.pad_vector(y, tile_size)
    if batched:
        nv = (
            jnp.full((x.shape[0],), x.shape[1], jnp.int32)
            if n_valid is None
            else jnp.asarray(n_valid, jnp.int32)
        )
        mv = (
            jnp.full((x.shape[0],), m_inducing, jnp.int32)
            if mu_valid is None
            else jnp.asarray(mu_valid, jnp.int32)
        )
    else:
        nv = x.shape[0] if n_valid is None else n_valid
        mv = m_inducing if mu_valid is None else mu_valid
    cfg = (
        n_streams, backend, update_dtype, batch_dispatch, kernel,
        float(jitter), jnp.dtype(dtype).name, batched,
    )
    out = _build_fn(cfg)(uc, xc, yc, params, mv, nv)
    if mu_valid is None:
        keep_mv = None
    elif not batched:
        keep_mv = mu_valid  # ragged single problem: fewer points than slots
    elif n_valid is not None or m_inducing > x.shape[1]:
        keep_mv = mu_valid
    else:
        keep_mv = None
    return LowRankState(
        u_chunks=uc,
        luu_packed=out["luu_packed"],
        b_packed=out["b_packed"],
        lb_packed=out["lb_packed"],
        c_chunks=out["c_chunks"],
        gamma=out["gamma"],
        yty=out["yty"],
        n=x.shape[-2],
        m=tile_size,
        m_inducing=m_inducing,
        params=params,
        jitter=float(jitter),
        mu_valid=None if keep_mv is None else jnp.asarray(keep_mv, jnp.int32),
        n_valid=None if n_valid is None else jnp.asarray(nv, jnp.int32),
        kernel=kernel,
    )


# ---------------------------------------------------------------------------
# Streaming absorption (rank-m update; O(b m² + m³), never O(n³)).
# ---------------------------------------------------------------------------


@precision.f32_matmuls
def absorb(
    state: LowRankState,
    x_new: jax.Array,
    y_new: jax.Array,
    counts=None,
    *,
    sign: int = 1,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    batch_dispatch: str = "flat",
) -> LowRankState:
    """Absorb (sign=+1) or forget (sign=-1) a block of training data.

    The inducing set stays fixed; only the m×m inner system A, the
    projection c = K_un y, and the counters change.  ``counts`` masks a
    ragged batch block (scalar or (B,)); None means every row is valid.
    Raises :class:`repro.core.update.CholeskyUpdateError` when the refreshed
    factor goes non-finite (sign=-1 can remove more information than the
    inner system holds) — callers should cold-rebuild.
    """
    from repro.core import update as upd

    kernel = state.kernel
    dtype = state.c_chunks.dtype
    x_new = jnp.asarray(x_new, dtype)
    y_new = jnp.asarray(y_new, dtype)
    batched = state.c_chunks.ndim == 3
    b = x_new.shape[-2]
    if counts is None:
        cnt = jnp.full((x_new.shape[0],), b, jnp.int32) if batched else b
    else:
        cnt = jnp.asarray(counts, jnp.int32)
    xbc = tiling.pad_features(x_new, state.m)
    ybc = tiling.pad_vector(y_new, state.m)
    mv = state.mu_valid
    if mv is None:
        mv = (
            jnp.full((x_new.shape[0],), state.m_inducing, jnp.int32)
            if batched
            else state.m_inducing
        )
    kub = _assemble_cross(
        state.u_chunks, xbc, state.params, mv, cnt,
        backend=backend, kernel=kernel, batched=batched,
    )
    dc = executor.run_lowrank_contraction(
        kub, ybc, backend=backend,
        batch_dispatch=batch_dispatch, n_streams=n_streams,
    )
    z = "z" if batched else ""
    mu_tiles = state.u_chunks.shape[-3]
    # whitened block W_b = L_uu^-1 K_ub; the inducing factor never changes
    wb = executor.run_solve(state.luu_packed, kub, lower=True, n_streams=n_streams)
    dgram = jnp.einsum(f"{z}pjac,{z}qjbc->{z}pqab", wb, wb)
    dgram_p = _packed_from_grid(dgram, mu_tiles, batched)
    inv_noise = 1.0 / jnp.asarray(kernel.noise(state.params))
    s = jnp.asarray(sign, dtype)
    b_packed = state.b_packed + s * inv_noise[..., None, None, None] * dgram_p
    c = state.c_chunks + s * dc
    lb = executor.run_cholesky(
        b_packed, backend=backend, n_streams=n_streams,
        update_dtype=update_dtype, batch_dispatch=batch_dispatch,
    )
    if bool(jnp.any(~jnp.isfinite(lb))):
        raise upd.CholeskyUpdateError(
            "low-rank inner-system refactorization went non-finite"
        )
    gamma = _inner_solve(state.luu_packed, lb, c, n_streams)
    row = jnp.arange(ybc.shape[-2] * ybc.shape[-1]).reshape(ybc.shape[-2:])
    if batched:
        ymask = row[None] < cnt[:, None, None]
    else:
        ymask = row < cnt
    dyty = jnp.sum(jnp.where(ymask, ybc * ybc, 0.0), axis=(-2, -1))
    nv = state.n_valid
    if nv is not None:
        nv = nv + sign * cnt
    return dataclasses.replace(
        state,
        b_packed=b_packed,
        lb_packed=lb,
        c_chunks=c,
        gamma=gamma,
        yty=state.yty + s * dyty,
        n=state.n + sign * b,
        n_valid=nv,
    )


# ---------------------------------------------------------------------------
# Prediction heads.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _head_fn(cfg):
    (full_cov, n_streams, backend, _dt, kernel, batched, batch_dispatch) = cfg
    z = "z" if batched else ""

    def head(xtc, u_chunks, luu, lb, gamma, params, ntv, mv):
        if batched:
            kstar = pred.assemble_cross_tiles_batched(
                xtc, u_chunks, params, ntv, mv, kernel=kernel
            )
        else:
            use_pallas = backend == "pallas" and km.params_concrete(params)
            kstar = pred.assemble_cross_tiles(
                xtc, u_chunks, params, ntv, mv,
                backend="pallas" if use_pallas else "jnp", kernel=kernel,
            )
        inv_noise = 1.0 / jnp.asarray(kernel.noise(params))
        mean_c = inv_noise[..., None, None] * jnp.einsum(
            f"{z}pqab,{z}qb->{z}pa", kstar, gamma
        )
        mean = mean_c.reshape(mean_c.shape[:-2] + (-1,))
        if not full_cov:
            return mean, None
        # tile rows of K_u* : (..., MU, Q, m, m)
        kut = jnp.swapaxes(jnp.swapaxes(kstar, -4, -3), -2, -1)
        v1 = executor.run_solve(luu, kut, lower=True, n_streams=n_streams)
        v2 = executor.run_solve(lb, v1, lower=True, n_streams=n_streams)
        if batched:
            prior = pred.assemble_prior_tiles_batched(xtc, params, ntv, kernel=kernel)
        else:
            prior = pred.assemble_prior_tiles(xtc, params, ntv, kernel=kernel)
        covt = (
            prior
            - jnp.einsum(f"{z}ipab,{z}iqac->{z}pqbc", v1, v1)
            + jnp.einsum(f"{z}ipab,{z}iqac->{z}pqbc", v2, v2)
        )
        cov = tiling.untile_dense(covt)
        nt_pad = cov.shape[-1]
        eye = jnp.eye(nt_pad, dtype=bool)
        cov = jnp.where(eye, jnp.clip(cov, 0.0, None), cov)
        return mean, cov

    head = precision.f32_matmuls(head)
    if backend == "jnp":
        return jax.jit(head)
    return head


def predict_from_lowrank_state(
    state: LowRankState,
    x_test: jax.Array,
    *,
    full_cov: bool = False,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    dtype=None,
    nt_valid=None,
    batch_dispatch: str = "flat",
):
    """Posterior mean (and optionally covariance) from a cached low-rank
    state.  x_test: (n*, D) or (B, n*, D)."""
    dtype = state.c_chunks.dtype if dtype is None else jnp.dtype(dtype)
    x_test = jnp.asarray(x_test, dtype)
    batched = state.c_chunks.ndim == 3
    nt = x_test.shape[-2]
    xtc = tiling.pad_features(x_test, state.m)
    if batched:
        B = x_test.shape[0]
        ntv = (
            jnp.full((B,), nt, jnp.int32)
            if nt_valid is None
            else jnp.asarray(nt_valid, jnp.int32)
        )
        mv = (
            jnp.full((B,), state.m_inducing, jnp.int32)
            if state.mu_valid is None
            else state.mu_valid
        )
    else:
        ntv = nt if nt_valid is None else nt_valid
        mv = state.m_inducing if state.mu_valid is None else state.mu_valid
    cfg = (
        bool(full_cov), n_streams, backend, jnp.dtype(dtype).name,
        state.kernel, batched, batch_dispatch,
    )
    mean, cov = _head_fn(cfg)(
        xtc, state.u_chunks, state.luu_packed, state.lb_packed,
        state.gamma, state.params, ntv, mv,
    )
    mean = mean[..., :nt]
    if not full_cov:
        return mean
    return mean, cov[..., :nt, :nt]


# ---------------------------------------------------------------------------
# NLML pieces (consumed by mll.nlml_lowrank).
# ---------------------------------------------------------------------------


def nlml_from_lowrank_state(state: LowRankState, *, dtype=None):
    """Woodbury / matrix-determinant-lemma NLML from the cached pieces:

        0.5 [ σ⁻² yᵀy − σ⁻⁴ cᵀ A⁻¹ c + n log σ²
              + log det B + n log 2π ]

    (log det A − log det K_uu = log det B in the whitened form.)
    """
    dtype = state.c_chunks.dtype if dtype is None else jnp.dtype(dtype)
    mu_tiles = state.u_chunks.shape[-3]
    noise = jnp.asarray(state.kernel.noise(state.params))
    inv = 1.0 / noise
    quad = inv * state.yty - inv * inv * jnp.sum(
        state.c_chunks * state.gamma, axis=(-2, -1)
    )
    logdet_b = triangular.logdet_from_factor(state.lb_packed, mu_tiles)
    nv = jnp.asarray(state.n if state.n_valid is None else state.n_valid, dtype)
    return 0.5 * (
        quad + nv * jnp.log(noise) + logdet_b + nv * jnp.log(2.0 * jnp.pi)
    ).astype(dtype)


# ---------------------------------------------------------------------------
# End-to-end traceable predict (benchmarks/fig14; jit covers selection,
# assembly, factorization, and the prediction head in one program).
# ---------------------------------------------------------------------------


def predict_lowrank(
    x: jax.Array,
    y: jax.Array,
    x_test: jax.Array,
    params,
    m_inducing: int,
    tile_size: int,
    *,
    strategy: str = "subset",
    inducing: Optional[jax.Array] = None,
    jitter: float = DEFAULT_JITTER,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    dtype=jnp.float32,
    batch_dispatch: str = "flat",
    kernel=None,
) -> jax.Array:
    """Cold-path low-rank predictive mean: state build + head, arrays in,
    arrays out (traceable end to end for benchmarking)."""
    state = lowrank_state(
        x, y, params, m_inducing, tile_size,
        strategy=strategy, inducing=inducing, jitter=jitter,
        n_streams=n_streams, backend=backend, update_dtype=update_dtype,
        dtype=dtype, batch_dispatch=batch_dispatch, kernel=kernel,
    )
    return predict_from_lowrank_state(
        state, x_test, n_streams=n_streams, backend=backend,
        dtype=dtype, batch_dispatch=batch_dispatch,
    )
