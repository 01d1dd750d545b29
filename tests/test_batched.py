"""Batched multi-GP execution (DESIGN.md §9).

The batched program must be *bit-for-purpose* equivalent to a Python loop of
single GPs: same predictions, uncertainties, NLMLs and gradients, while the
executor reuses the exact same lru-cached Plan for every B (the DAG depends
only on the tile geometry).  Heavy grid cells are marked ``slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import GaussianProcess, GPBatch, SEKernelParams
from repro.core import executor, mll, tiling
from repro.core import predict as pred


def _problems(rng, b, n, d=2, nh=13):
    x = rng.standard_normal((b, n, d)).astype(np.float32)
    y = rng.standard_normal((b, n)).astype(np.float32)
    xt = rng.standard_normal((b, nh, d)).astype(np.float32)
    params = SEKernelParams(
        jnp.asarray(rng.uniform(0.6, 1.4, b).astype(np.float32)),
        jnp.asarray(rng.uniform(0.8, 1.2, b).astype(np.float32)),
        jnp.asarray(rng.uniform(0.05, 0.2, b).astype(np.float32)),
    )
    return x, y, xt, params


def _single(params, i):
    return SEKernelParams(
        float(params.lengthscale[i]),
        float(params.vertical[i]),
        float(params.noise[i]),
    )


def _grid():
    """B x n x backend x n_streams equivalence grid; heavy cells slow."""
    cells = []
    for b in (1, 3, 8):
        for n in (64, 200):
            for backend in ("jnp", "pallas"):
                for ns in (1, 4, None):
                    # fast lane keeps the full jnp n=64 grid and ONE pallas
                    # interpret cell; everything else is slow-lane (coverage
                    # ratchet: interpret-mode pallas cells dominate runtime
                    # without adding line coverage beyond the first cell)
                    heavy = n == 200 or (
                        backend == "pallas" and not (b == 1 and ns is None)
                    )
                    marks = [pytest.mark.slow] if heavy else []
                    cells.append(
                        pytest.param(b, n, backend, ns, marks=marks,
                                     id=f"B{b}-n{n}-{backend}-ns{ns}")
                    )
    return cells


@pytest.mark.parametrize("b,n,backend,ns", _grid())
def test_gpbatch_matches_loop(rng, b, n, backend, ns):
    """GPBatch predict / uncertainty / nlml == a loop of GaussianProcess."""
    x, y, xt, params = _problems(rng, b, n)
    m = 16 if n == 64 else 64
    fleet = GPBatch(x, y, params=params, tile_size=m, n_streams=ns, op_backend=backend)
    mu_b, var_b = fleet.predict_with_uncertainty(xt)
    nlml_b = fleet.nlml()
    assert mu_b.shape == (b, xt.shape[1]) and nlml_b.shape == (b,)
    for i in range(b):
        gp = GaussianProcess(
            x[i], y[i], params=_single(params, i), tile_size=m,
            n_streams=ns, op_backend=backend,
        )
        mu_i, var_i = gp.predict_with_uncertainty(xt[i])
        np.testing.assert_allclose(np.asarray(mu_b[i]), np.asarray(mu_i),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(var_b[i]), np.asarray(var_i),
                                   rtol=1e-3, atol=1e-4)
        ref = float(gp.nlml())
        assert abs(float(nlml_b[i]) - ref) < 1e-3 * abs(ref) + 5e-2


@pytest.mark.parametrize("vjp", ["custom", "autodiff"])
def test_batched_nlml_gradients_match_loop(rng, vjp):
    """d(sum_i NLML_i)/d(params, x, y) == the stacked per-problem gradients."""
    b, n, d, m = 3, 48, 2, 16
    x, y, _, params = _problems(rng, b, n, d=d)
    xj, yj = jnp.asarray(x), jnp.asarray(y)

    def loss(xa, ya, p):
        return jnp.sum(mll.nlml_tiled_batched(xa, ya, p, tile_size=m, vjp=vjp))

    g_x, g_y, g_p = jax.grad(loss, argnums=(0, 1, 2))(xj, yj, params)
    for i in range(b):
        pi = _single(params, i)
        gi_x, gi_y, gi_p = jax.grad(
            lambda xa, ya, p: mll.nlml_tiled(xa, ya, p, tile_size=m, vjp=vjp),
            argnums=(0, 1, 2),
        )(xj[i], yj[i], pi)
        np.testing.assert_allclose(np.asarray(g_x[i]), np.asarray(gi_x),
                                   rtol=2e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g_y[i]), np.asarray(gi_y),
                                   rtol=2e-3, atol=1e-4)
        for leaf, ref in (
            (g_p.lengthscale[i], gi_p.lengthscale),
            (g_p.vertical[i], gi_p.vertical),
            (g_p.noise[i], gi_p.noise),
        ):
            np.testing.assert_allclose(float(leaf), float(ref), rtol=2e-3, atol=1e-4)


def test_plan_reuse_across_batch_sizes(rng):
    """Acceptance: the B=8, n=200 batched program executes with the SAME
    number of executor launches as B=1 — literally the same lru-cached Plan
    object; B never enters the plan key."""
    n, nh, m = 200, 50, 64
    x1, y1, xt1, params1 = _problems(rng, 1, n, nh=nh)
    pred.predict_fused_batched(x1, y1, xt1, params1, m)
    m_tiles = (n + m - 1) // m
    q_tiles = (nh + m - 1) // m
    info_after_b1 = executor.program_plan.cache_info()
    plan_b1 = executor.program_plan(m_tiles, q_tiles, False, None)

    x8, y8, xt8, params8 = _problems(rng, 8, n, nh=nh)
    pred.predict_fused_batched(x8, y8, xt8, params8, m)
    info_after_b8 = executor.program_plan.cache_info()
    plan_b8 = executor.program_plan(m_tiles, q_tiles, False, None)

    assert plan_b1 is plan_b8, "plan must be B-invariant (same cached object)"
    assert info_after_b8.misses == info_after_b1.misses, (
        "running B=8 compiled a new plan — the executor launch count changed"
    )
    # the launch count both runs executed is the plan's batch count
    assert plan_b8.n_batches == plan_b1.n_batches


def test_batched_optimize_matches_independent_runs(rng):
    """One jitted batched Adam scan == B independent single-GP Adam runs."""
    b, n, m, steps = 2, 48, 16, 12
    x, y, _, params = _problems(rng, b, n)
    opt_b, losses_b = mll.optimize_hyperparameters_batched(
        x, y, params, steps=steps, lr=0.1, tile_size=m
    )
    assert losses_b.shape == (steps, b)
    for i in range(b):
        opt_i, losses_i = mll.optimize_hyperparameters(
            x[i], y[i], _single(params, i), steps=steps, lr=0.1,
            method="tiled", tile_size=m,
        )
        np.testing.assert_allclose(np.asarray(losses_b[:, i]),
                                   np.asarray(losses_i), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(float(opt_b.lengthscale[i]),
                                   float(opt_i.lengthscale), rtol=1e-3, atol=1e-4)


def test_gpbatch_cache_contract(rng):
    """Posterior cache populated by cold predict, reused warm, invalidated
    by optimize — the GaussianProcess contract, stacked."""
    b, n = 3, 40
    x, y, xt, params = _problems(rng, b, n)
    fleet = GPBatch(x, y, params=params, tile_size=16)
    assert fleet._posterior is None
    mu_cold = fleet.predict(xt)
    assert fleet._posterior is not None, "cold fused predict must populate cache"
    assert fleet._posterior.lpacked.shape[0] == b
    mu_warm = fleet.predict(xt)
    np.testing.assert_allclose(np.asarray(mu_warm), np.asarray(mu_cold),
                               rtol=1e-4, atol=1e-5)
    # warm full-covariance tail off the cached stacked factor
    mu_w, sig_w = fleet.predict_full_cov(xt)
    assert sig_w.shape == (b, xt.shape[1], xt.shape[1])
    np.testing.assert_allclose(np.asarray(mu_w), np.asarray(mu_cold),
                               rtol=1e-4, atol=1e-5)
    fleet.optimize(steps=3, lr=0.05)
    assert fleet._posterior is None, "optimize must invalidate the cache"
    assert fleet.params.lengthscale.shape == (b,)
    nl = fleet.nlml()  # repopulates via the q_tiles=0 program
    assert nl.shape == (b,) and np.isfinite(np.asarray(nl)).all()


def test_gpbatch_validation_and_broadcast(rng):
    x = rng.standard_normal((3, 40, 2)).astype(np.float32)
    y = rng.standard_normal((3, 40)).astype(np.float32)
    with pytest.raises(ValueError, match="GPBatch"):
        GPBatch(x[0], y)  # unstacked x
    with pytest.raises(ValueError, match="GPBatch"):
        GPBatch(x, y[:2])  # mismatched B
    # shared scalar params stay scalar (keeps Pallas assembly usable);
    # wrong-length per-problem leaves raise
    fleet = GPBatch(x, y, tile_size=16)
    assert jnp.ndim(fleet.params.lengthscale) == 0
    with pytest.raises(ValueError, match="params"):
        GPBatch(x, y, params=SEKernelParams(jnp.ones(2), 1.0, 0.1), tile_size=16)
    # shared (n̂, D) test block broadcasts; wrong leading axis raises
    assert fleet.predict(x[0, :7]).shape == (3, 7)
    with pytest.raises(ValueError, match="x_test"):
        fleet.predict(rng.standard_normal((2, 5, 2)).astype(np.float32))
    # (B, n) 1-D convenience, incl. stacked/shared test-point forms
    f1 = GPBatch(y, y, tile_size=16)
    assert f1.x_train.shape == (3, 40, 1)
    assert f1.predict(rng.standard_normal((3, 5)).astype(np.float32)).shape == (3, 5)
    assert f1.predict(rng.standard_normal(7).astype(np.float32)).shape == (3, 7)
    assert f1.predict(rng.standard_normal((7, 1)).astype(np.float32)).shape == (3, 7)
    # mixed scalar/(B,) hyperparameter leaves are legal end-to-end
    mixed = GPBatch(
        x, y, params=SEKernelParams(jnp.ones(3), 1.0, 0.1), tile_size=16
    )
    assert mixed.predict(x[:, :5]).shape == (3, 5)
    assert mixed.nlml().shape == (3,)


def test_padding_helpers_batched(rng):
    """tiling.pad_* are batch-aware (the predict.pad_* deprecation aliases
    were removed; tiling owns the implementations)."""
    assert not hasattr(pred, "pad_features")
    assert not hasattr(pred, "pad_vector")
    x = jnp.asarray(rng.standard_normal((3, 10, 2)).astype(np.float32))
    xc = tiling.pad_features(x, 4)
    assert xc.shape == (3, 3, 4, 2)
    np.testing.assert_array_equal(np.asarray(xc[:, 2, 2:]), 0.0)
    y = jnp.asarray(rng.standard_normal((3, 10)).astype(np.float32))
    yc = tiling.pad_vector(y, 4)
    assert yc.shape == (3, 3, 4)
    # unbatched layout unchanged
    assert tiling.pad_features(x[0], 4).shape == (3, 4, 2)
    assert tiling.pad_vector(y[0], 4).shape == (3, 4)
    # dtype kw casts; default preserves
    assert tiling.pad_vector(y, 4, dtype=jnp.bfloat16).dtype == jnp.bfloat16
    assert tiling.pad_vector(y, 4).dtype == y.dtype


def test_run_cholesky_batched_matches_loop(rng, spd):
    """The executor's factorization itself accepts a leading B axis."""
    b, m_tiles, m = 3, 3, 8
    n = m_tiles * m
    ks = np.stack([spd(rng, n) for _ in range(b)])
    packed = jnp.stack([tiling.pack_lower(jnp.asarray(k), m) for k in ks])
    for dispatch in ("flat", "vmap"):
        lb = executor.run_cholesky(packed, batch_dispatch=dispatch)
        for i in range(b):
            li = executor.run_cholesky(packed[i])
            np.testing.assert_allclose(np.asarray(lb[i]), np.asarray(li),
                                       rtol=1e-4, atol=1e-4)


def test_dtype_flows_float64(rng):
    """The dtype knob reaches padding + assembly end-to-end (no implicit
    float32): float64 GPs stay float64 through predict and nlml."""
    with compat.enable_x64():
        n, d = 40, 2
        x = rng.standard_normal((n, d))
        y = rng.standard_normal(n)
        xt = rng.standard_normal((11, d))
        gp = GaussianProcess(x, y, tile_size=16, dtype=jnp.float64)
        mu, var = gp.predict_with_uncertainty(xt)
        assert mu.dtype == jnp.float64 and var.dtype == jnp.float64
        assert gp.posterior().lpacked.dtype == jnp.float64
        mu_m = pred.predict_monolithic(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), gp.params,
            dtype=jnp.float64,
        )
        np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_m),
                                   rtol=1e-8, atol=1e-10)
        # batched fleet in float64
        xs = np.stack([x, x + 0.1])
        ys = np.stack([y, y * 0.5])
        fleet = GPBatch(xs, ys, tile_size=16, dtype=jnp.float64)
        mu_b = fleet.predict(np.stack([xt, xt]))
        assert mu_b.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(mu_b[0]), np.asarray(mu),
                                   rtol=1e-8, atol=1e-10)
        assert fleet.nlml().dtype == jnp.float64


try:
    import hypothesis  # noqa: F401

    _HAVE_HYP = True
except ImportError:
    _HAVE_HYP = False

if _HAVE_HYP:
    from hypothesis import given, settings, strategies as st

    @given(
        b=st.integers(1, 4),
        n=st.integers(8, 40),
        d=st.integers(1, 3),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_batched_equals_loop(b, n, d, seed):
        """Any ragged-free stacked problem set: batched == per-problem loop."""
        rng = np.random.default_rng(seed)
        x, y, xt, params = _problems(rng, b, n, d=d, nh=max(n // 3, 2))
        mu_b = pred.predict_fused_batched(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), params, 16
        )
        for i in range(b):
            mu_i = pred.predict_fused(
                jnp.asarray(x[i]), jnp.asarray(y[i]), jnp.asarray(xt[i]),
                _single(params, i), 16,
            )
            np.testing.assert_allclose(np.asarray(mu_b[i]), np.asarray(mu_i),
                                       rtol=1e-3, atol=2e-3)
