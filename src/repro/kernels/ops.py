"""Jit'd dispatch wrappers for the Pallas kernels.

Exposes the same per-tile signatures as the jnp backend in
``repro.core.cholesky`` (so the level scheduler can vmap them uniformly) plus
the batched entry points and the covariance-assembly routines used by
``repro.core.predict``.

The execution mode follows the default backend: on ``"tpu"`` the
`pallas_call`s lower through Mosaic; on ``"cpu"`` they run in interpret mode
(the kernel bodies execute as jnp on the host), which is how the test suite
validates them.  Any other backend raises rather than silently running the
interpreter in place of the kernels.

Differentiability (DESIGN.md §8): the per-tile ops carry ``jax.custom_vjp``
hooks whose backward passes differentiate the *jnp reference* implementation
of the same tile op (``jnp.linalg.cholesky`` / ``triangular_solve`` / the
rank-update matmuls).  The Pallas kernel is only the forward primal, so the
tiled NLML program stays traceable under ``jax.grad`` with
``op_backend="pallas"`` — gradients are mathematically identical to the jnp
backend because both backends compute the same function.  Covariance
*assembly* still bakes hyperparameters in as compile-time constants; when
the hyperparameters are traced (a gradient trace) the executor falls back to
the differentiable jnp assembly tile automatically
(``repro.core.executor._cov_batch_fn``).

Problem batching (DESIGN.md §9): these per-tile signatures are what makes
the executor's problem-batch dimension free on the Pallas backend.  A tile
op never knows *which* problem a tile belongs to, so the executor's
``batch_dispatch="flat"`` mode reshapes the gathered ``(B, G, m, m)``
operands to ``(B*G, m, m)`` and the single ``jax.vmap`` level that batches
a level's tiles becomes the Pallas grid axis covering all B problems — B is
absorbed into the grid of ONE kernel launch.  ``batch_dispatch="vmap"``
instead nests a second ``jax.vmap`` over the problem axis (two batching
dims on the ``pallas_call``).  Both are measured by
``benchmarks/fig9_batched_fleet.py``; the *assembly* kernels stay
single-problem because their baked-in hyperparameters cannot vary across
the batch (per-problem params use the jnp tile kernel,
``executor._cov_batch_fn_batched``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tiling
from repro.kernels import cov_assembly as _cov
from repro.kernels import downdate_tile as _down
from repro.kernels import lrgemm_tile as _lrgemm
from repro.kernels import potrf_tile as _potrf
from repro.kernels import trailing_update as _trail
from repro.kernels import trsm_tile as _trsm


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels lower for 'tpu' and run interpreted on 'cpu'; "
        f"the default backend is {backend!r}"
    )


# ---------------------------------------------------------------------------
# Per-tile ops (vmap-compatible, mirror repro.core.cholesky jnp backend).
# ---------------------------------------------------------------------------


def _cast(x, dt):
    return x if dt is None else x.astype(dt)


# jnp reference tile ops used for the custom-VJP backward passes.  Both
# backends compute the same mathematical function per tile, so the reference
# VJP is the exact gradient of the Pallas forward.

def _potrf_ref(a):
    return jnp.linalg.cholesky(a)


def _trsm_ref(ljj, b):
    return jax.lax.linalg.triangular_solve(
        ljj, b, left_side=False, lower=True, transpose_a=True
    )


def _syrk_ref(update_dtype):
    def f(kii, lij):
        a = _cast(lij, update_dtype)
        return kii - (a @ a.T).astype(kii.dtype)

    return f


def _gemm_ref(update_dtype):
    def f(kik, lij, lkj):
        a, b = _cast(lij, update_dtype), _cast(lkj, update_dtype)
        return kik - (a @ b.T).astype(kik.dtype)

    return f


def _with_ref_vjp(primal, ref):
    """Wrap a Pallas tile op so its VJP differentiates the jnp reference."""
    f = jax.custom_vjp(primal)

    def fwd(*args):
        return primal(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(ref, *args)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def _potrf_impl(a: jax.Array) -> jax.Array:
    return _potrf.potrf(a, interpret=_interpret())


def _trsm_impl(ljj: jax.Array, b: jax.Array) -> jax.Array:
    return _trsm.trsm(ljj, b, interpret=_interpret())


potrf = _with_ref_vjp(_potrf_impl, _potrf_ref)
trsm = _with_ref_vjp(_trsm_impl, _trsm_ref)


@functools.lru_cache(maxsize=None)
def _syrk_cv(update_dtype):
    def impl(kii, lij):
        out = _trail.trailing_update(
            kii[None],
            _cast(lij, update_dtype)[None],
            _cast(lij, update_dtype)[None],
            block=_pick_block(kii.shape[-1]),
            interpret=_interpret(),
        )[0]
        return out.astype(kii.dtype)

    return _with_ref_vjp(impl, _syrk_ref(update_dtype))


@functools.lru_cache(maxsize=None)
def _gemm_cv(update_dtype):
    def impl(kik, lij, lkj):
        out = _trail.trailing_update(
            kik[None],
            _cast(lij, update_dtype)[None],
            _cast(lkj, update_dtype)[None],
            block=_pick_block(kik.shape[-1]),
            interpret=_interpret(),
        )[0]
        return out.astype(kik.dtype)

    return _with_ref_vjp(impl, _gemm_ref(update_dtype))


def syrk(kii: jax.Array, lij: jax.Array, update_dtype=None) -> jax.Array:
    return _syrk_cv(update_dtype)(kii, lij)


def gemm(kik: jax.Array, lij: jax.Array, lkj: jax.Array, update_dtype=None) -> jax.Array:
    return _gemm_cv(update_dtype)(kik, lij, lkj)


def _pick_block(m: int) -> int:
    # largest power-of-two block <= min(m, 256); MXU-aligned when m >= 128
    b = 1
    while b * 2 <= min(m, 256):
        b *= 2
    return b


def _lrgemm_ref(a, v):
    return a @ v


def _lrgemm_impl(a: jax.Array, v: jax.Array) -> jax.Array:
    return _lrgemm.lrgemm(a, v, interpret=_interpret())


# low-rank contraction tile (DESIGN.md §14); the reference VJP keeps the
# lowrank NLML differentiable under op_backend="pallas"
lrgemm = _with_ref_vjp(_lrgemm_impl, _lrgemm_ref)


def carry_update(w: jax.Array, l_new: jax.Array, y: jax.Array, c: jax.Array) -> jax.Array:
    """Fused up/downdate carry transform  (W - L' Y) C^{-T}  (DESIGN.md §10).

    The streaming-update sweep is not differentiated (it maintains a cached
    posterior, it is not a training path), so no reference VJP is attached.
    """
    return _down.carry_update(w, l_new, y, c, interpret=_interpret())


# ---------------------------------------------------------------------------
# Batched entry points (one kernel launch per scheduler level).
# ---------------------------------------------------------------------------


def trsm_panel(ljj: jax.Array, b_stack: jax.Array) -> jax.Array:
    return _trsm.trsm_batched(ljj, b_stack, interpret=_interpret())


def trailing_update_batch(c_stack, a_stack, b_stack, *, update_dtype=None):
    return _trail.trailing_update(
        c_stack,
        _cast(a_stack, update_dtype),
        _cast(b_stack, update_dtype),
        block=_pick_block(c_stack.shape[-1]),
        interpret=_interpret(),
    ).astype(c_stack.dtype)


# ---------------------------------------------------------------------------
# Covariance assembly (paper's custom CUDA kernels → Pallas).
# ---------------------------------------------------------------------------


def assemble_packed_covariance(
    x_chunks: jax.Array, params, n_valid, kernel=None
) -> jax.Array:
    """(M, m, D) padded chunks -> packed lower covariance tiles (T, m, m).

    ``kernel`` picks the registered covariance family (None -> the paper's
    SE).  Hyperparameters must be concrete (the Pallas path bakes them in as
    compile-time constants; use the jnp backend for NLML differentiation).
    ``n_valid`` may be a Python int or a traced scalar — it reaches the
    kernel as a (1,)-block i32 operand, not a compile-time constant.
    """
    from repro.core import kernels_math as km

    m_tiles, m, _ = x_chunks.shape
    rows, cols = tiling._packed_coords(m_tiles)
    return _cov.cov_tiles(
        x_chunks[rows],
        x_chunks[cols],
        jnp.asarray(rows * m, jnp.int32),
        jnp.asarray(cols * m, jnp.int32),
        kernel=km.resolve_kernel(kernel),
        params=params,
        n_valid_r=n_valid,
        n_valid_c=n_valid,
        symmetric=True,
        interpret=_interpret(),
    )


def assemble_cross_tiles(
    xt_chunks: jax.Array, x_chunks: jax.Array, params, nt_valid, n_valid, kernel=None
) -> jax.Array:
    """K_{X̂,X} tile grid (Mhat, M, m, m) via one batched kernel launch."""
    from repro.core import kernels_math as km

    mh, m, _ = xt_chunks.shape
    mt = x_chunks.shape[0]
    rows = np.repeat(np.arange(mh), mt)
    cols = np.tile(np.arange(mt), mh)
    flat = _cov.cov_tiles(
        xt_chunks[rows],
        x_chunks[cols],
        jnp.asarray(rows * m, jnp.int32),
        jnp.asarray(cols * m, jnp.int32),
        kernel=km.resolve_kernel(kernel),
        params=params,
        n_valid_r=nt_valid,
        n_valid_c=n_valid,
        symmetric=False,
        interpret=_interpret(),
    )
    return flat.reshape(mh, mt, m, m)
