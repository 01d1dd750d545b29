"""GP hyperparameter training through the negative log marginal likelihood.

Beyond the paper's scope (it fixes l=1, v=1, sigma^2=0.1) but part of the
GPRat library proper; DESIGN.md §7–§8 cover how the training path relates to
the fused program IR.

    nlml = 0.5 * ( y^T alpha + log det K + n log 2 pi )

Three evaluation paths:

* :func:`negative_log_marginal_likelihood` — the monolithic dense reference
  (one-call Cholesky, differentiated by JAX autodiff).
* :func:`nlml_from_state` — evaluation at fixed hyperparameters from a
  cached tiled :class:`repro.core.predict.PosteriorState` (quadratic term
  from the alpha chunks, log-determinant from the packed factor's diagonal
  tiles) — no re-factorization, exact for any n thanks to identity padding.
* :func:`nlml_tiled` — the *trainable* tiled NLML (DESIGN.md §8): the fused
  program with ``q_tiles=0`` (assembly → tiled Cholesky → both
  substitutions) plus the quad/logdet heads.  Differentiable w.r.t.
  ``(x, y, params)`` either through a blocked reverse-mode ``custom_vjp``
  (default — one tiled triangular matrix solve + gram for K^{-1}, instead
  of autodiff back through every wavefront launch) or by plain autodiff
  through the program (``vjp="autodiff"``; Pallas tile ops carry reference
  VJPs, see repro.kernels.ops).

:func:`optimize_hyperparameters` runs Adam on either path as ONE jitted
``lax.scan`` — the whole optimization is a single compiled program, not a
Python loop that re-enters jit every step.  Hyperparameters live in
unconstrained log-space (softplus).

Problem-batched variants (DESIGN.md §9): :func:`nlml_tiled_batched`
evaluates B stacked GPs' NLMLs through ONE problem-batched fused program
(per-problem losses (B,), per-problem hyperparameter leaves (B,)), and
:func:`optimize_hyperparameters_batched` trains all B GPs in one jitted
``lax.scan`` with independent elementwise Adam states
(:func:`adam_scan_batched`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import cholesky as chol
from repro.core import kernels_math as km
from repro.core import precision, tiling, triangular


@precision.f32_matmuls
def negative_log_marginal_likelihood(
    x: jax.Array,
    y: jax.Array,
    params,
    *,
    dtype=jnp.float32,
    kernel=None,
) -> jax.Array:
    """Exact NLML through the monolithic Cholesky (differentiable)."""
    x = x.astype(dtype)
    y = y.astype(dtype)
    n = y.shape[0]
    k = km.assemble_covariance(x, params, kernel=kernel, dtype=dtype)
    l = chol.monolithic_cholesky(k)
    beta = jax.lax.linalg.triangular_solve(l, y[:, None], left_side=True, lower=True)
    quad = jnp.sum(beta * beta)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(l)))
    return 0.5 * (quad + logdet + n * math.log(2.0 * math.pi))


def nlml_from_state(state, y: jax.Array, *, dtype=jnp.float32, n_valid=None) -> jax.Array:
    """NLML from a cached tiled posterior (no re-factorization).

    quad   = y^T alpha            (alpha = K^{-1} y, cached chunks; padded
                                   rows contribute 0 because y pads with 0)
    logdet = 2 sum log diag(L)    (packed factor's diagonal tiles; padded
                                   rows contribute log 1 = 0)

    Batch-aware: a stacked state (leading B axis) with y (B, n) returns the
    per-problem NLML vector (B,).

    Ragged states (DESIGN.md §11): per-problem frontiers from ``n_valid``
    (or ``state.n_valid``) replace the shared n in the constant term and
    mask the factor diagonal — per-problem NLMLs stay exact even though
    every problem in the bucket shares the padded stack shape.
    """
    y = y.astype(dtype)
    yc = tiling.pad_vector(y, state.m)
    quad = jnp.sum(yc * state.alpha, axis=(-2, -1))
    m_tiles = state.alpha.shape[-2]
    nv = getattr(state, "n_valid", None) if n_valid is None else n_valid
    n = y.shape[-1] if nv is None else jnp.asarray(nv, yc.dtype)
    logdet = triangular.logdet_from_factor(state.lpacked, m_tiles, n_valid=nv)
    return 0.5 * (quad + logdet + n * math.log(2.0 * math.pi))


# ---------------------------------------------------------------------------
# The trainable tiled NLML (DESIGN.md §8).
#
# Forward: the fused program with q_tiles=0 (scheduler.build_nlml_schedule)
# — the NLML program IS the prediction program minus the test-point stages,
# sharing its plan/jit caches.  Heads: quad = sum(yc * alpha) and logdet
# from the factor's diagonal tiles.
#
# Backward (vjp="custom", default): blocked reverse-mode from the closed
# form  dNLML/dK = 0.5 (K^{-1} - alpha alpha^T) =: S.  The O(n^3) piece is
# K^{-1} = L^{-T} L^{-1}, computed with the *tiled* machinery (one matrix
# forward solve on identity tiles + one tiled gram —
# triangular.kinv_tiles_from_factor); the O(n^2) contractions with dK/dtheta
# are dense:
#
#   dNLML/dl      = sum(S ∘ K_se ∘ D2) / (2 l^2)     (K_se = v exp(-D2/2l))
#   dNLML/dv      = sum(S ∘ K_se) / v
#   dNLML/dsigma2 = tr(S)
#   dNLML/dy      = alpha
#   dNLML/dx_i    = -(2/l) sum_j S_ij K_se_ij (x_i - x_j)
#
# Padding never enters: the padded block of K is a constant identity, so its
# derivative is zero and everything is computed on the unpadded n×n region.
# ---------------------------------------------------------------------------


def _nlml_cfg(
    tile_size,
    n_streams,
    backend,
    update_dtype,
    dtype,
    batch_dispatch="flat",
    kernel=None,
):
    """Hashable static config for the custom-vjp / jit caches.

    ``kernel`` instances are frozen dataclasses (hashable, structural
    equality) so they slot straight into this tuple."""
    return (
        int(tile_size),
        n_streams,
        backend,
        update_dtype,
        jnp.dtype(dtype).name,
        batch_dispatch,
        km.resolve_kernel(kernel),
    )


def _nlml_forward(cfg, x, y, params):
    """Run the tiled NLML program; returns (value, residuals for the vjp).

    Batch-aware: with x (B, n, D) / y (B, n) the program env is
    problem-batched and the value is the per-problem loss vector (B,).
    """
    from repro.core import predict as pred

    tile_size, n_streams, backend, update_dtype, dtype_name, batch_dispatch, kernel = cfg
    dtype = jnp.dtype(dtype_name)
    n = y.shape[-1]
    env, yc = pred.nlml_program_env(
        x,
        y,
        params,
        tile_size,
        n_streams=n_streams,
        backend=backend,
        update_dtype=update_dtype,
        dtype=dtype,
        batch_dispatch=batch_dispatch,
        kernel=kernel,
    )
    quad = jnp.sum(yc * env["alpha"], axis=(-2, -1))
    logdet = triangular.logdet_from_factor(env["packed"], env["alpha"].shape[-2])
    val = 0.5 * (quad + logdet + n * math.log(2.0 * math.pi))
    return val, (env["packed"], env["alpha"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nlml_tiled_cv(cfg, x, y, params):
    val, _ = _nlml_forward(cfg, x, y, params)
    return val


def _nlml_cv_fwd(cfg, x, y, params):
    val, (lpacked, alpha_c) = _nlml_forward(cfg, x, y, params)
    return val, (x, y, params, lpacked, alpha_c)


def _nlml_dense_grads(kernel, params, xd, alpha, kinv):
    """O(n^2) dense contraction of S = 0.5(K^{-1} - aa^T) with dK/dtheta.

    One problem: xd (n, D), alpha (n,), kinv (n, n), scalar params leaves.
    Returns (g_x, g_y, g_params) with g_params matching the params pytree:
    the kernel's hand-derived ``kfree_vjp`` supplies every noise-free
    derivative (and the x cotangents), and dK/dsigma2 = I adds tr(S) onto
    the noise leaf.  The batched backward pass vmaps this over the problem
    axis.
    """
    s = 0.5 * (kinv - jnp.outer(alpha, alpha))
    g_params, g_xa, g_xb = kernel.kfree_vjp(params, xd, xd, s)
    g_params = dataclasses.replace(
        g_params, noise=g_params.noise + jnp.trace(s)
    )
    return g_xa + g_xb, alpha, g_params


@precision.f32_matmuls
def _nlml_cv_bwd(cfg, res, ct):
    # analytic-vjp kernels only (SE, Matérn 5/2): nlml_tiled routes every
    # other family to vjp="autodiff" before this rule can be installed.
    _, n_streams, _, _, dtype_name, _, kernel = cfg
    dtype = jnp.dtype(dtype_name)
    x, y, params, lpacked, alpha_c = res
    n = y.shape[0]
    # O(n^3): K^{-1} through the tiled solve executor (blocked reverse-mode).
    kinv_t = triangular.kinv_tiles_from_factor(lpacked, n_streams=n_streams)
    kinv = tiling.untile_dense(kinv_t)[:n, :n]
    alpha = alpha_c.reshape(-1)[:n]
    # O(n^2): contract S with the analytic kernel derivatives.
    params_d = jax.tree_util.tree_map(
        lambda p: jnp.asarray(p, dtype), params
    )
    g_x, g_y, g_params = _nlml_dense_grads(
        kernel, params_d, x.astype(dtype), alpha, kinv
    )
    ct = jnp.asarray(ct, dtype)
    return (
        ct * g_x,
        ct * g_y,
        jax.tree_util.tree_map(lambda g: ct * g, g_params),
    )


_nlml_tiled_cv.defvjp(_nlml_cv_fwd, _nlml_cv_bwd)


# -- problem-batched trainable NLML (DESIGN.md §9) --------------------------
#
# Forward: ONE problem-batched program (q_tiles=0) evaluates B independent
# NLMLs; the per-problem losses come back as a vector (B,).  Backward: the
# blocked reverse-mode rule per problem — K^{-1} for all B factors through
# ONE batched tiled matrix solve + gram, then the O(n^2) dense contraction
# vmapped over the problem axis.  Hyperparameter leaves are (B,) throughout
# (callers broadcast shared scalars up front).


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nlml_tiled_batched_cv(cfg, x, y, params):
    val, _ = _nlml_forward(cfg, x, y, params)
    return val


def _nlml_batched_cv_fwd(cfg, x, y, params):
    val, (lpacked, alpha_c) = _nlml_forward(cfg, x, y, params)
    return val, (x, y, params, lpacked, alpha_c)


@precision.f32_matmuls
def _nlml_batched_cv_bwd(cfg, res, ct):
    _, n_streams, _, _, dtype_name, _, kernel = cfg
    dtype = jnp.dtype(dtype_name)
    x, y, params, lpacked, alpha_c = res
    b, n = y.shape
    # O(n^3): B inverses through ONE problem-batched tiled solve + gram.
    kinv_t = triangular.kinv_tiles_from_factor(lpacked, n_streams=n_streams)
    kinv = tiling.untile_dense(kinv_t)[:, :n, :n]
    alpha = alpha_c.reshape(b, -1)[:, :n]
    # per-problem leaves (B,) — callers broadcast shared scalars up front
    params_b = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(jnp.asarray(p, dtype), (b,)), params
    )
    g_x, g_y, g_params = jax.vmap(
        lambda p, xd, a, ki: _nlml_dense_grads(kernel, p, xd, a, ki)
    )(params_b, x.astype(dtype), alpha, kinv)
    ct = jnp.asarray(ct, dtype)  # (B,) — one cotangent per problem loss
    return (
        ct[:, None, None] * g_x,
        ct[:, None] * g_y,
        jax.tree_util.tree_map(lambda g: ct * g, g_params),
    )


_nlml_tiled_batched_cv.defvjp(_nlml_batched_cv_fwd, _nlml_batched_cv_bwd)


def nlml_tiled_batched(
    x: jax.Array,
    y: jax.Array,
    params,
    *,
    tile_size: int = 256,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    dtype=jnp.float32,
    vjp: str = "custom",
    batch_dispatch: str = "flat",
    kernel=None,
) -> jax.Array:
    """Per-problem NLML vector (B,) for B stacked GPs, in ONE batched program.

    x (B, n, D) / y (B, n); hyperparameter leaves scalar (shared) or (B,)
    (per-problem) — scalars are broadcast so the gradient contract is always
    per-problem leaves (B,).  Differentiable like :func:`nlml_tiled`:
    ``vjp="custom"`` (default) runs the blocked reverse-mode rule batched,
    ``vjp="autodiff"`` differentiates straight through the program.  Kernels
    without a hand-derived dK/dtheta (``kernel.analytic_vjp`` False) fall
    back to autodiff automatically.
    """
    x = jnp.asarray(x, dtype)
    if x.ndim == 2:
        x = x[..., None]
    y = jnp.asarray(y, dtype)
    if x.ndim != 3 or y.ndim != 2 or x.shape[:2] != y.shape:
        raise ValueError(
            f"batched NLML needs x (B, n, D) and y (B, n); got {x.shape}, {y.shape}"
        )
    kernel = km.resolve_kernel(kernel)
    params = km.broadcast_params(params, x.shape[0], kernel)
    cfg = _nlml_cfg(
        tile_size, n_streams, op_backend, update_dtype, dtype, batch_dispatch, kernel
    )
    if vjp == "custom" and not kernel.analytic_vjp:
        vjp = "autodiff"
    if vjp == "custom":
        return _nlml_tiled_batched_cv(cfg, x, y, params)
    if vjp == "autodiff":
        val, _ = _nlml_forward(cfg, x, y, params)
        return val
    raise ValueError(f"vjp must be 'custom' or 'autodiff', got {vjp!r}")


def nlml_tiled(
    x: jax.Array,
    y: jax.Array,
    params,
    *,
    tile_size: int = 256,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    dtype=jnp.float32,
    vjp: str = "custom",
    kernel=None,
) -> jax.Array:
    """NLML through the tiled fused program — differentiable (DESIGN.md §8).

    Value-equivalent to :func:`negative_log_marginal_likelihood` for any n
    (identity padding).  ``vjp="custom"`` (default) installs the blocked
    reverse-mode backward pass; ``vjp="autodiff"`` differentiates straight
    through the program's wavefront launches (the jnp ops natively, the
    Pallas tile ops via their reference VJPs) — kept as the correctness
    baseline the custom rule is tested against.

    The blocked reverse-mode rule contracts hand-derived kernel
    derivatives, so only kernels with ``analytic_vjp`` (SE, Matérn-5/2)
    use it; any other registered ``kernel`` silently falls back to
    ``vjp="autodiff"``.
    """
    x = jnp.asarray(x, dtype)
    if x.ndim == 1:
        x = x[:, None]
    y = jnp.asarray(y, dtype).reshape(-1)
    kernel = km.resolve_kernel(kernel)
    cfg = _nlml_cfg(
        tile_size, n_streams, op_backend, update_dtype, dtype, kernel=kernel
    )
    if vjp == "custom" and not kernel.analytic_vjp:
        vjp = "autodiff"
    if vjp == "custom":
        return _nlml_tiled_cv(cfg, x, y, params)
    if vjp == "autodiff":
        val, _ = _nlml_forward(cfg, x, y, params)
        return val
    raise ValueError(f"vjp must be 'custom' or 'autodiff', got {vjp!r}")


# ---------------------------------------------------------------------------
# Low-rank (Nyström / DTC) NLML — O(n m^2) per evaluation (DESIGN.md §14).
#
# Forward: the whitened inner system from repro.core.lowrank (K_un through
# the CROSS family, c = K_un y through LRGEMM, chol(K_uu)/chol(B) through
# the fused POTRF/TRSM/SYRK plans).  Backward (vjp="custom"): the blocked
# reverse-mode rule below — all cotangents contract against *dense* m×m /
# m×n quantities, so the backward pass is O(n m^2) like the forward.  With
#   A = K_uu + s^-2 K_un K_nu,   b = A^{-1} K_un y,
# the NLML derivatives are
#   G_A    = 0.5 A^{-1} + 0.5 s^-4 b b^T
#   G_Kuu  = G_A - 0.5 K_uu^{-1}
#   G_Kun  = 2 s^-2 G_A K_un - s^-4 b y^T
#   g_s2   = -0.5 s^-4 y^T y + s^-6 c^T b + 0.5 n s^-2
#            - s^-4 tr(G_A K_un K_nu)
#   g_y    = s^-2 (y - s^-2 K_nu b)
# and the kernel-level cotangents route through kernel.kfree_vjp exactly
# like the exact tier's rule.  The inducing inputs are stop_gradient'ed in
# the forward builder, so their cotangent is zero by construction.
# ---------------------------------------------------------------------------


def _dense_from_packed(packed):
    """Packed lower tiles (T, m, m) -> dense lower-triangular (M*m, M*m)."""
    t, m, _ = packed.shape[-3:]
    m_tiles = int((math.isqrt(8 * t + 1) - 1) // 2)
    rows, cols = tiling._packed_coords(m_tiles)
    grid = jnp.zeros((m_tiles, m_tiles, m, m), packed.dtype)
    grid = grid.at[rows, cols].set(packed)
    return tiling.untile_dense(grid)


def _lr_state(cfg, x, y, u, params):
    from repro.core import lowrank

    (mu, tile_size, jitter, n_streams, backend, update_dtype, dtype_name,
     kernel) = cfg
    return lowrank.lowrank_state(
        x, y, params, mu, tile_size,
        inducing=u, jitter=jitter, n_streams=n_streams, backend=backend,
        update_dtype=update_dtype, dtype=jnp.dtype(dtype_name), kernel=kernel,
    )


def _nlml_lr_value(cfg, x, y, u, params):
    from repro.core import lowrank

    state = _lr_state(cfg, x, y, u, params)
    return lowrank.nlml_from_lowrank_state(state), state


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nlml_lr_cv(cfg, x, y, u, params):
    val, _ = _nlml_lr_value(cfg, x, y, u, params)
    return val


def _nlml_lr_fwd(cfg, x, y, u, params):
    val, state = _nlml_lr_value(cfg, x, y, u, params)
    return val, (x, y, u, params, state.luu_packed, state.lb_packed, state.gamma)


@precision.f32_matmuls
def _nlml_lr_bwd(cfg, res, ct):
    mu, _, _, _, _, _, dtype_name, kernel = cfg
    dtype = jnp.dtype(dtype_name)
    x, y, u, params, luu_packed, lb_packed, gamma = res
    n = y.shape[0]
    params_d = jax.tree_util.tree_map(lambda p: jnp.asarray(p, dtype), params)
    xd, yd, ud = x.astype(dtype), y.astype(dtype), u.astype(dtype)
    # O(m^3) dense sandwich for A^{-1} / K_uu^{-1} from the saved factors
    luu_d = _dense_from_packed(luu_packed)[:mu, :mu]
    lb_d = _dense_from_packed(lb_packed)[:mu, :mu]
    eye = jnp.eye(mu, dtype=dtype)
    linv = jax.scipy.linalg.solve_triangular(luu_d, eye, lower=True)
    t = jax.scipy.linalg.solve_triangular(lb_d, linv, lower=True)
    ainv = t.T @ t
    kuuinv = linv.T @ linv
    kun = kernel.kfree(params_d, ud, xd)  # (m, n)
    c = kun @ yd
    b = gamma.reshape(-1)[:mu]  # A^{-1} c, solved stably in the forward
    inv = 1.0 / jnp.asarray(kernel.noise(params_d))
    ga = 0.5 * ainv + 0.5 * inv * inv * jnp.outer(b, b)
    g_kuu = ga - 0.5 * kuuinv
    ga_kun = ga @ kun
    g_kun = 2.0 * inv * ga_kun - inv * inv * jnp.outer(b, yd)
    g_noise = (
        -0.5 * inv * inv * jnp.sum(yd * yd)
        + inv * inv * inv * jnp.dot(c, b)
        + 0.5 * n * inv
        - inv * inv * jnp.sum(ga_kun * kun)
    )
    g_y = inv * yd - inv * inv * (kun.T @ b)
    gp_uu, _, _ = kernel.kfree_vjp(params_d, ud, ud, g_kuu)
    gp_un, _, g_x = kernel.kfree_vjp(params_d, ud, xd, g_kun)
    g_params = jax.tree_util.tree_map(jnp.add, gp_uu, gp_un)
    g_params = dataclasses.replace(
        g_params, noise=g_params.noise + g_noise
    )
    ct = jnp.asarray(ct, dtype)
    return (
        ct * g_x,
        ct * g_y,
        jnp.zeros_like(u),  # inducing inputs are stop_gradient'ed
        jax.tree_util.tree_map(lambda g: ct * g, g_params),
    )


_nlml_lr_cv.defvjp(_nlml_lr_fwd, _nlml_lr_bwd)


def nlml_lowrank(
    x: jax.Array,
    y: jax.Array,
    params,
    *,
    m_inducing: int,
    tile_size: int = 256,
    strategy: str = "subset",
    inducing=None,
    jitter=None,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    dtype=jnp.float32,
    vjp: str = "custom",
    kernel=None,
) -> jax.Array:
    """Nyström low-rank NLML — O(n m^2), differentiable (DESIGN.md §14).

    Same contract as :func:`nlml_tiled` but through the low-rank tier:
    ``vjp="custom"`` installs the blocked O(n m^2) reverse-mode rule above
    (analytic-vjp kernels only — others fall back to autodiff through the
    builder, which works on both backends via the tile ops' reference
    VJPs).  The inducing set is selected once per call from the *primal*
    inputs and carries no gradient.
    """
    from repro.core import lowrank

    x = jnp.asarray(x, dtype)
    if x.ndim == 1:
        x = x[:, None]
    y = jnp.asarray(y, dtype).reshape(-1)
    kernel = km.resolve_kernel(kernel)
    jitter = lowrank.DEFAULT_JITTER if jitter is None else float(jitter)
    u, _ = lowrank.select_inducing(
        x, m_inducing, strategy=strategy, inducing=inducing
    )
    cfg = (
        int(m_inducing), int(tile_size), jitter, n_streams, op_backend,
        update_dtype, jnp.dtype(dtype).name, kernel,
    )
    if vjp == "custom" and not kernel.analytic_vjp:
        vjp = "autodiff"
    if vjp == "custom":
        return _nlml_lr_cv(cfg, x, y, u, params)
    if vjp == "autodiff":
        val, _ = _nlml_lr_value(cfg, x, y, u, params)
        return val
    raise ValueError(f"vjp must be 'custom' or 'autodiff', got {vjp!r}")


def nlml_lowrank_batched(
    x: jax.Array,
    y: jax.Array,
    params,
    *,
    m_inducing: int,
    tile_size: int = 256,
    strategy: str = "subset",
    inducing=None,
    jitter=None,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    dtype=jnp.float32,
    batch_dispatch: str = "flat",
    n_valid=None,
    kernel=None,
) -> jax.Array:
    """Per-problem low-rank NLML vector (B,) in one batched build.

    Differentiates through the builder (autodiff; the custom rule is
    single-problem).  Hyperparameter leaves scalar or (B,) as usual.
    """
    from repro.core import lowrank

    x = jnp.asarray(x, dtype)
    if x.ndim == 2:
        x = x[..., None]
    y = jnp.asarray(y, dtype)
    if x.ndim != 3 or y.ndim != 2 or x.shape[:2] != y.shape:
        raise ValueError(
            f"batched NLML needs x (B, n, D) and y (B, n); got {x.shape}, {y.shape}"
        )
    kernel = km.resolve_kernel(kernel)
    state = lowrank.lowrank_state(
        x, y, params, m_inducing, tile_size,
        strategy=strategy, inducing=inducing,
        jitter=lowrank.DEFAULT_JITTER if jitter is None else float(jitter),
        n_streams=n_streams, backend=op_backend, update_dtype=update_dtype,
        dtype=dtype, batch_dispatch=batch_dispatch, n_valid=n_valid,
        kernel=kernel,
    )
    return lowrank.nlml_from_lowrank_state(state, dtype=dtype)


# ---------------------------------------------------------------------------
# Unconstrained-space packing and the jitted lax.scan Adam optimizer.
# ---------------------------------------------------------------------------


def _softplus(z: jax.Array) -> jax.Array:
    # softplus keeps hyperparameters positive; logaddexp is overflow-safe
    return jnp.logaddexp(z, 0.0)


def _inv_softplus(p: jax.Array) -> jax.Array:
    """Numerically stable softplus inverse, exact from tiny up to f32 max.

    The naive ``log(expm1(p))`` overflows expm1 for p ≳ 88 in float32 (and
    ≳ 709 in float64), turning any large hyperparameter into inf at pack
    time; the algebraically identical ``p + log1p(-exp(-p))`` never forms
    e^p but loses to ``exp(-p) == 1`` rounding below p ≈ 1e-7.  So: branch
    at 20 (each arm clamped into its own safe range — the classic
    double-where against NaN gradients from the untaken branch), and floor
    p at the dtype's tiny (where log(expm1(p)) ≈ log(p) stays finite)
    instead of the old lossy 1e-6 clamp that collapsed every smaller
    hyperparameter onto the same raw value.
    """
    p = jnp.maximum(p, jnp.finfo(jnp.result_type(p)).tiny)
    small = jnp.log(jnp.expm1(jnp.minimum(p, 20.0)))
    big = p + jnp.log1p(-jnp.exp(-jnp.maximum(p, 20.0)))
    return jnp.where(p > 20.0, big, small)


def unpack_params(raw):
    """Softplus every leaf of an unconstrained kernel-params pytree."""
    return jax.tree_util.tree_map(_softplus, raw)


def pack_params(params, dtype=None):
    """Inverse-softplus every leaf of a kernel-params pytree (generic
    counterpart of :func:`_pack` for the kernel zoo — every registered
    family keeps all its hyperparameter leaves positive, so one
    unconstrained map serves the whole registry)."""
    if dtype is None:
        dtype = jnp.result_type(*jax.tree_util.tree_leaves(params))
    return jax.tree_util.tree_map(
        lambda p: _inv_softplus(jnp.asarray(p).astype(dtype)), params
    )


def _unpack(raw: jax.Array) -> km.SEKernelParams:
    # raw is in R^3 — or (B, 3) for B problems (the SE hyperparameter triple
    # always lives on the last axis)
    return km.SEKernelParams(
        lengthscale=_softplus(raw[..., 0]),
        vertical=_softplus(raw[..., 1]),
        noise=_softplus(raw[..., 2]),
    )


def _pack(params: km.SEKernelParams, dtype=None) -> jax.Array:
    """Inverse softplus into R^3 (or (B, 3) for per-problem leaves (B,)).
    ``dtype=None`` keeps the leaves' common dtype (float64 params no longer
    silently round-trip through float32)."""
    leaves = [
        jnp.asarray(p) for p in (params.lengthscale, params.vertical, params.noise)
    ]
    if dtype is None:
        dtype = jnp.result_type(*leaves)
    return jnp.stack([_inv_softplus(p.astype(dtype)) for p in leaves], axis=-1)


def _raw_codec(kernel):
    """(pack, unpack) pair for a kernel's unconstrained parameterization.

    SE keeps the legacy stacked (…, 3) raw layout (the optimizer-state shape
    tests and benchmarks rely on); every other family round-trips its whole
    params pytree leaf-by-leaf.
    """
    if isinstance(kernel, km.SquaredExponential):
        return _pack, _unpack
    return pack_params, unpack_params


def nlml_loss_fn(
    x: jax.Array,
    y: jax.Array,
    *,
    method: str = "monolithic",
    dtype=jnp.float32,
    tile_size: int = 256,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    vjp: str = "custom",
    kernel=None,
    m_inducing=None,
    strategy: str = "subset",
    inducing=None,
    jitter=None,
):
    """loss(raw) over unconstrained hyperparameters, for any NLML path."""
    kernel = km.resolve_kernel(kernel)
    _, unpack = _raw_codec(kernel)
    if method == "monolithic":
        return lambda raw: negative_log_marginal_likelihood(
            x, y, unpack(raw), dtype=dtype, kernel=kernel
        )
    if method == "tiled":
        return lambda raw: nlml_tiled(
            x,
            y,
            unpack(raw),
            tile_size=tile_size,
            n_streams=n_streams,
            op_backend=op_backend,
            update_dtype=update_dtype,
            dtype=dtype,
            vjp=vjp,
            kernel=kernel,
        )
    if method == "lowrank":
        if m_inducing is None:
            raise ValueError("method='lowrank' needs m_inducing")
        return lambda raw: nlml_lowrank(
            x,
            y,
            unpack(raw),
            m_inducing=m_inducing,
            tile_size=tile_size,
            strategy=strategy,
            inducing=inducing,
            jitter=jitter,
            n_streams=n_streams,
            op_backend=op_backend,
            update_dtype=update_dtype,
            dtype=dtype,
            vjp=vjp,
            kernel=kernel,
        )
    raise ValueError(
        f"method must be 'monolithic', 'tiled' or 'lowrank', got {method!r}"
    )


def _adam_scan_impl(vg, steps: int, lr: float):
    """Shared Adam core: ``vg(raw) -> ((objective, report), grad)``.

    The scan records ``report`` (the loss value(s) *before* update t) and
    updates elementwise — the same code serves one problem (scalar
    objective == report) and B independent problems (objective = sum of
    per-problem losses, report = the (B,) loss vector; independence makes
    the summed gradient the stacked per-problem gradients, and elementwise
    moments on (B, 3) raws ARE B independent optimizers).

    ``raw`` may be any pytree (the SE stacked (…, 3) array, or a full
    kernel-params pytree from :func:`pack_params`) — the update is a
    ``tree_map`` so arbitrary registered kernels train through the same
    compiled scan.
    """
    b1, b2, eps = 0.9, 0.999, 1e-8
    tmap = jax.tree_util.tree_map

    def step(carry, t):
        raw, m, v = carry
        (_, report), g = vg(raw)
        m = tmap(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = tmap(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        raw = tmap(
            lambda r_, m_, v_: r_
            - lr * (m_ / (1 - b1**t)) / (jnp.sqrt(v_ / (1 - b2**t)) + eps),
            raw,
            m,
            v,
        )
        return (raw, m, v), report

    def run(raw0):
        z = tmap(jnp.zeros_like, raw0)
        ts = jnp.arange(
            1, steps + 1, dtype=jax.tree_util.tree_leaves(raw0)[0].dtype
        )
        (raw, _, _), losses = jax.lax.scan(step, (raw0, z, z), ts)
        return raw, losses

    return jax.jit(precision.f32_matmuls(run))


def adam_scan(loss, steps: int, lr: float):
    """The whole Adam run as ONE jitted ``lax.scan`` over optimizer steps.

    Returns a compiled function ``raw0 -> (raw_final, losses)`` where
    ``losses[t]`` is the loss *before* update t (``losses[0]`` is the loss
    at the initial point, matching the old Python-loop semantics).  One
    trace, one compile, zero per-step dispatch from Python — the paper's
    "recurring O(n^3) cost per optimizer step" runs entirely on device.
    """

    def total(raw):
        val = loss(raw)
        return val, val

    return _adam_scan_impl(jax.value_and_grad(total, has_aux=True), steps, lr)


def adam_scan_batched(loss, steps: int, lr: float):
    """B independent Adam runs in ONE jitted ``lax.scan`` (DESIGN.md §9).

    ``loss`` maps raw (B, 3) -> per-problem losses (B,).  Differentiating the
    *sum* of independent per-problem losses yields exactly the stacked
    per-problem gradients (zero cross-terms), and Adam's update is
    elementwise, so one (B, 3) moment pair IS B independent optimizers.
    Returns ``raw0 (B, 3) -> (raw_final (B, 3), losses (steps, B))`` with
    the same loss-before-update-t semantics as :func:`adam_scan`.
    """

    def total(raw):
        losses = loss(raw)
        return jnp.sum(losses), losses

    return _adam_scan_impl(jax.value_and_grad(total, has_aux=True), steps, lr)


def optimize_hyperparameters(
    x: jax.Array,
    y: jax.Array,
    init,
    *,
    steps: int = 100,
    lr: float = 0.05,
    dtype=jnp.float32,
    method: str = "monolithic",
    tile_size: int = 256,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    vjp: str = "custom",
    kernel=None,
    m_inducing=None,
    strategy: str = "subset",
    inducing=None,
    jitter=None,
) -> Tuple:
    """Adam on the NLML in unconstrained space.  Returns (params, loss curve).

    ``method="monolithic"`` differentiates the dense reference NLML;
    ``method="tiled"`` trains through the tiled fused program
    (:func:`nlml_tiled` — no monolithic Cholesky anywhere in the loop);
    ``method="lowrank"`` trains the O(n m^2) Nyström NLML
    (:func:`nlml_lowrank`, requires ``m_inducing``).
    Either way the optimizer is one jitted ``lax.scan`` (:func:`adam_scan`).
    Any registered ``kernel`` trains: ``init`` is that kernel's params
    pytree, optimized leaf-by-leaf through softplus space (SE keeps its
    analytic backward pass; other families autodiff through the program).
    """
    x = jnp.asarray(x, dtype)
    if x.ndim == 1:
        x = x[:, None]
    y = jnp.asarray(y, dtype).reshape(-1)
    kernel = km.resolve_kernel(kernel)
    pack, unpack = _raw_codec(kernel)
    loss = nlml_loss_fn(
        x,
        y,
        method=method,
        dtype=dtype,
        tile_size=tile_size,
        n_streams=n_streams,
        op_backend=op_backend,
        update_dtype=update_dtype,
        vjp=vjp,
        kernel=kernel,
        m_inducing=m_inducing,
        strategy=strategy,
        inducing=inducing,
        jitter=jitter,
    )
    raw, losses = adam_scan(loss, steps, lr)(pack(init, dtype=dtype))
    return unpack(raw), losses


def optimize_hyperparameters_batched(
    x: jax.Array,
    y: jax.Array,
    init,
    *,
    steps: int = 100,
    lr: float = 0.05,
    dtype=jnp.float32,
    method: str = "tiled",
    tile_size: int = 256,
    n_streams=None,
    op_backend: str = "jnp",
    update_dtype=None,
    vjp: str = "custom",
    batch_dispatch: str = "flat",
    kernel=None,
    m_inducing=None,
    strategy: str = "subset",
    inducing=None,
    jitter=None,
    n_valid=None,
) -> Tuple:
    """Train B GPs' hyperparameters in ONE jitted Adam scan (DESIGN.md §9).

    x (B, n, D) / y (B, n); ``init`` leaves scalar (shared start) or (B,)
    (per-problem starts).  Returns (params with (B,) leaves, loss curves
    (steps, B)).  ``method="tiled"`` (default) evaluates all B NLMLs through
    one problem-batched fused program per optimizer step;
    ``method="monolithic"`` vmaps the dense reference NLML — the
    equivalence baseline; ``method="lowrank"`` evaluates the Nyström NLML
    (:func:`nlml_lowrank_batched`, requires ``m_inducing``; trains by
    autodiff through the builder).
    """
    x = jnp.asarray(x, dtype)
    if x.ndim == 2:
        x = x[..., None]
    y = jnp.asarray(y, dtype)
    if x.ndim != 3 or y.ndim != 2 or x.shape[:2] != y.shape:
        raise ValueError(
            f"batched optimize needs x (B, n, D) and y (B, n); got "
            f"{tuple(x.shape)}, {tuple(y.shape)}"
        )
    b = x.shape[0]
    kernel = km.resolve_kernel(kernel)
    pack, unpack = _raw_codec(kernel)
    init = km.broadcast_params(init, b, kernel)
    if method == "tiled":
        loss = lambda raw: nlml_tiled_batched(
            x,
            y,
            unpack(raw),
            tile_size=tile_size,
            n_streams=n_streams,
            op_backend=op_backend,
            update_dtype=update_dtype,
            dtype=dtype,
            vjp=vjp,
            batch_dispatch=batch_dispatch,
            kernel=kernel,
        )
    elif method == "lowrank":
        if m_inducing is None:
            raise ValueError("method='lowrank' needs m_inducing")
        loss = lambda raw: nlml_lowrank_batched(
            x,
            y,
            unpack(raw),
            m_inducing=m_inducing,
            tile_size=tile_size,
            strategy=strategy,
            inducing=inducing,
            jitter=jitter,
            n_streams=n_streams,
            op_backend=op_backend,
            update_dtype=update_dtype,
            dtype=dtype,
            batch_dispatch=batch_dispatch,
            n_valid=n_valid,
            kernel=kernel,
        )
    elif method == "monolithic":
        mono = jax.vmap(
            lambda x1, y1, raw1: negative_log_marginal_likelihood(
                x1, y1, unpack(raw1), dtype=dtype, kernel=kernel
            ),
            in_axes=(0, 0, 0),
        )
        loss = lambda raw: mono(x, y, raw)
    else:
        raise ValueError(
            f"method must be 'monolithic', 'tiled' or 'lowrank', got {method!r}"
        )
    raw, losses = adam_scan_batched(loss, steps, lr)(pack(init, dtype=dtype))
    return unpack(raw), losses
