"""Gradient-verification suite for the differentiable tiled NLML (DESIGN.md §8).

The tiled NLML (`mll.nlml_tiled`, the fused program with q_tiles=0) must be
value-equivalent to the monolithic reference AND produce matching gradients —
via the blocked reverse-mode custom VJP (default) and via plain autodiff
through the program — across tile counts, padding, backends, stream pools and
dtypes.  float64 cells additionally check against central finite differences.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import kernels_math as km
from repro.core import mll, tiling
from repro.core import predict as pred
from repro.core.kernels_math import SEKernelParams

# tile sizes chosen so the grid covers M in {2, 4} with and without padding
# (n=200 pads to 256; n=16/64/512 are exact multiples)
_TILE = {16: 8, 64: 16, 200: 64, 512: 128}

# float32 acceptance: <= 1e-3 rtol vs the monolithic gradients; float64: 1e-6.
# The Pallas kernels compute internally in float32 regardless of the storage
# dtype (trsm_tile casts operands to f32, trailing_update accumulates with
# preferred_element_type=f32 — the TPU MXU has no f64), so pallas cells are
# held to the float32 tolerance even when storage is float64.
_GRAD_RTOL = {"float32": 1e-3, "float64": 1e-6}
_VALUE_RTOL = {"float32": 1e-4, "float64": 1e-10}


def _tols(backend, dt):
    eff = "float32" if backend == "pallas" else dt
    return _VALUE_RTOL[eff], _GRAD_RTOL[eff]


def _ctx(dt):
    return compat.enable_x64() if dt == "float64" else contextlib.nullcontext()


def _data(n, dt):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal((n, 2)).astype(dt))
    y = jnp.asarray(rng.standard_normal(n).astype(dt))
    return x, y


def _params(dtype):
    return SEKernelParams(
        jnp.asarray(0.8, dtype), jnp.asarray(1.3, dtype), jnp.asarray(0.2, dtype)
    )


def _grid():
    cells = []
    for n in (16, 64, 200, 512):
        for backend in ("jnp", "pallas"):
            for ns in (None, 1, 4):
                for dt in ("float32", "float64"):
                    marks = []
                    if n == 512 or (backend == "pallas" and n >= 200):
                        marks.append(pytest.mark.slow)
                    cells.append(
                        pytest.param(
                            n, backend, ns, dt,
                            marks=marks,
                            id=f"n{n}-{backend}-ns{ns}-{dt}",
                        )
                    )
    return cells


@pytest.mark.parametrize("n,backend,ns,dt", _grid())
def test_nlml_tiled_value_and_grad_match_monolithic(n, backend, ns, dt):
    with _ctx(dt):
        dtype = jnp.dtype(dt)
        x, y = _data(n, dt)
        params = _params(dtype)
        kw = dict(
            tile_size=_TILE[n], n_streams=ns, op_backend=backend, dtype=dtype
        )

        value_rtol, grad_rtol = _tols(backend, dt)

        # value equivalence: nlml_tiled == negative_log_marginal_likelihood
        v_t = float(mll.nlml_tiled(x, y, params, **kw))
        v_m = float(mll.negative_log_marginal_likelihood(x, y, params, dtype=dtype))
        assert v_t == pytest.approx(v_m, rel=value_rtol)

        # gradient equivalence in unconstrained space (what the optimizer sees)
        raw = mll._pack(params, dtype=dtype)
        g_m = np.asarray(
            jax.grad(
                lambda r: mll.negative_log_marginal_likelihood(
                    x, y, mll._unpack(r), dtype=dtype
                )
            )(raw)
        )
        g_t = np.asarray(
            jax.grad(lambda r: mll.nlml_tiled(x, y, mll._unpack(r), **kw))(raw)
        )
        np.testing.assert_allclose(
            g_t, g_m, rtol=grad_rtol, atol=grad_rtol * np.abs(g_m).max()
        )


@pytest.mark.parametrize(
    "n,backend",
    [(16, "jnp"), (64, "jnp"), (200, "jnp"), (16, "pallas")],
    ids=lambda v: str(v),
)
def test_nlml_tiled_grad_matches_finite_differences(n, backend):
    """Central finite differences in float64 pin the analytic VJP.

    The jnp backend is f64 end-to-end, so a tiny step resolves the gradient
    to ~1e-9; the Pallas forward rounds internally through f32, so its step
    must be large enough for the secant to dominate that rounding noise."""
    with compat.enable_x64():
        dtype = jnp.float64
        x, y = _data(n, "float64")
        params = _params(dtype)
        kw = dict(tile_size=_TILE[n], op_backend=backend, dtype=dtype)
        raw = mll._pack(params, dtype=dtype)
        g = np.asarray(
            jax.grad(lambda r: mll.nlml_tiled(x, y, mll._unpack(r), **kw))(raw)
        )
        eps, rtol = (1e-6, 1e-5) if backend == "jnp" else (1e-3, 5e-3)
        fd = []
        for i in range(3):
            e = jnp.zeros(3, raw.dtype).at[i].set(eps)
            hi = mll.nlml_tiled(x, y, mll._unpack(raw + e), **kw)
            lo = mll.nlml_tiled(x, y, mll._unpack(raw - e), **kw)
            fd.append((float(hi) - float(lo)) / (2 * eps))
        fd = np.asarray(fd)
        np.testing.assert_allclose(g, fd, rtol=rtol, atol=rtol * np.abs(fd).max())


@pytest.mark.parametrize("method", ["tiled", "lowrank"])
def test_matern52_analytic_vjp_matches_finite_differences(method):
    """The hand-derived Matérn-5/2 kfree VJP, contracted by both blocked
    custom rules (exact tier and Woodbury low-rank tier), against central
    finite differences in float64."""
    with compat.enable_x64():
        dtype = jnp.float64
        n = 48
        x, y = _data(n, "float64")
        kern = km.get_kernel("matern52")
        raw = mll.pack_params(_params(dtype), dtype=dtype)

        if method == "tiled":
            def loss(r):
                return mll.nlml_tiled(
                    x, y, mll.unpack_params(r),
                    tile_size=16, dtype=dtype, kernel=kern, vjp="custom",
                )
        else:
            def loss(r):
                return mll.nlml_lowrank(
                    x, y, mll.unpack_params(r),
                    m_inducing=16, tile_size=16, jitter=1e-10,
                    dtype=dtype, kernel=kern, vjp="custom",
                )

        g_leaves = jax.tree_util.tree_leaves(jax.grad(loss)(raw))
        leaves, tree = jax.tree_util.tree_flatten(raw)
        eps, rtol = 1e-6, 1e-5
        fd = []
        for i in range(len(leaves)):
            hi = list(leaves)
            hi[i] = leaves[i] + eps
            lo = list(leaves)
            lo[i] = leaves[i] - eps
            fd.append((
                float(loss(jax.tree_util.tree_unflatten(tree, hi)))
                - float(loss(jax.tree_util.tree_unflatten(tree, lo)))
            ) / (2 * eps))
        fd = np.asarray(fd)
        g = np.asarray([float(v) for v in g_leaves])
        assert np.abs(fd).max() > 1e-3, "degenerate cell: all-zero gradients"
        np.testing.assert_allclose(g, fd, rtol=rtol, atol=rtol * np.abs(fd).max())


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_custom_vjp_matches_autodiff_through_program(backend):
    """The blocked reverse-mode rule equals differentiating every wavefront
    launch (jnp ops natively; Pallas tile ops via their reference VJPs)."""
    n = 48
    x, y = _data(n, "float32")
    params = _params(jnp.float32)
    raw = mll._pack(params)
    kw = dict(tile_size=16, n_streams=4, op_backend=backend)
    g_c = np.asarray(
        jax.grad(lambda r: mll.nlml_tiled(x, y, mll._unpack(r), vjp="custom", **kw))(raw)
    )
    g_a = np.asarray(
        jax.grad(lambda r: mll.nlml_tiled(x, y, mll._unpack(r), vjp="autodiff", **kw))(raw)
    )
    np.testing.assert_allclose(g_c, g_a, rtol=1e-3, atol=1e-3 * np.abs(g_a).max())


def test_nlml_tiled_grads_wrt_inputs_match_monolithic():
    """The custom VJP also carries exact cotangents for x and y."""
    n = 30
    x, y = _data(n, "float32")
    params = _params(jnp.float32)
    gm_x, gm_y = jax.grad(
        lambda a, b: mll.negative_log_marginal_likelihood(a, b, params), argnums=(0, 1)
    )(x, y)
    gt_x, gt_y = jax.grad(
        lambda a, b: mll.nlml_tiled(a, b, params, tile_size=8), argnums=(0, 1)
    )(x, y)
    np.testing.assert_allclose(
        np.asarray(gt_x), np.asarray(gm_x), rtol=1e-3,
        atol=1e-4 * np.abs(np.asarray(gm_x)).max(),
    )
    np.testing.assert_allclose(
        np.asarray(gt_y), np.asarray(gm_y), rtol=1e-3,
        atol=1e-4 * np.abs(np.asarray(gm_y)).max(),
    )


def test_pack_preserves_float64():
    """Regression: _pack hard-coded float32, silently rounding f64 params."""
    with compat.enable_x64():
        p = SEKernelParams(
            jnp.asarray(1.5, jnp.float64),
            jnp.asarray(2.0, jnp.float64),
            jnp.asarray(0.3, jnp.float64),
        )
        raw = mll._pack(p)
        assert raw.dtype == jnp.float64
        rt = mll._unpack(raw)
        np.testing.assert_allclose(float(rt.lengthscale), 1.5, rtol=1e-12)
        np.testing.assert_allclose(float(rt.vertical), 2.0, rtol=1e-12)
        np.testing.assert_allclose(float(rt.noise), 0.3, rtol=1e-12)
    # default stays float32 when given plain python floats
    assert mll._pack(SEKernelParams.paper_defaults()).dtype == jnp.float32


def test_tiled_optimizer_matches_monolithic_trajectory():
    """Same init, same step count: the lax.scan Adam loop over the tiled NLML
    follows the monolithic loss curve and lands on the same hyperparameters."""
    rng = np.random.default_rng(7)
    n = 40
    x = jnp.asarray(rng.uniform(-3, 3, (n, 1)).astype(np.float32))
    y = jnp.asarray(
        (np.sin(2 * np.asarray(x)[:, 0]) + 0.1 * rng.standard_normal(n)).astype(
            np.float32
        )
    )
    init = SEKernelParams.paper_defaults()
    p_t, l_t = mll.optimize_hyperparameters(
        x, y, init, steps=20, lr=0.05, method="tiled", tile_size=16
    )
    p_m, l_m = mll.optimize_hyperparameters(
        x, y, init, steps=20, lr=0.05, method="monolithic"
    )
    np.testing.assert_allclose(np.asarray(l_t), np.asarray(l_m), rtol=1e-3, atol=1e-2)
    for a, b in zip(
        (p_t.lengthscale, p_t.vertical, p_t.noise),
        (p_m.lengthscale, p_m.vertical, p_m.noise),
    ):
        np.testing.assert_allclose(float(a), float(b), rtol=2e-2, atol=1e-4)
    assert float(l_t[-1]) < float(l_t[0])


def test_gp_optimize_tiled_runs_zero_monolithic_choleskys(rng, monkeypatch):
    """pipeline="tiled" training must never touch the monolithic path."""
    from repro.core import GaussianProcess
    from repro.core import cholesky as chol

    n = 32
    x = rng.uniform(-3, 3, (n, 1)).astype(np.float32)
    y = (np.sin(2 * x[:, 0]) + 0.1 * rng.standard_normal(n)).astype(np.float32)
    gp = GaussianProcess(x, y, tile_size=16)
    before = float(gp.nlml())
    calls = {"n": 0}
    orig = chol.monolithic_cholesky

    def wrapped(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(chol, "monolithic_cholesky", wrapped)
    gp.optimize(steps=10, lr=0.05)
    assert calls["n"] == 0, "tiled optimize() ran the monolithic Cholesky"
    after = float(gp.nlml())
    assert after < before


def test_nlml_program_env_matches_posterior_state(rng):
    """The q_tiles=0 program env slices equal the staged posterior state."""
    n = 50
    x = jnp.asarray(rng.standard_normal((n, 3)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    params = _params(jnp.float32)
    env, yc = pred.nlml_program_env(x, y, params, 16)
    state = pred.posterior_state(x, y, params, 16)
    np.testing.assert_allclose(
        np.asarray(env["packed"]), np.asarray(state.lpacked), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(env["alpha"]), np.asarray(state.alpha), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(yc), np.asarray(tiling.pad_vector(y, 16)), rtol=0, atol=0
    )
