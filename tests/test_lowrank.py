"""Tiled Nyström low-rank tier (DESIGN.md §14).

Core invariants: (a) with m_inducing = n the DTC posterior equals the exact
GP up to the K_uu jitter; (b) predictive variances are never negative; (c)
the batched/fleet paths match a per-problem Python loop while adding ZERO
executor Plan-cache misses as B varies; (d) streaming absorb/forget through
the rank-m inner system matches a cold rebuild; (e) the Woodbury NLML trains
end-to-end on both op backends; (f) the serving loop batches low-rank
buckets with the same wave-ordering/masking contract as the exact tier.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import GaussianProcess, GPBatch, GPFleet
from repro.core import executor, lowrank, mll
from repro.core.kernels_math import SEKernelParams

M = 16
PARAMS = SEKernelParams(lengthscale=0.7, vertical=1.2, noise=0.05)


def _data(rng, n, d=2, nt=7):
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = (np.sin(x[:, 0]) + 0.1 * rng.standard_normal(n)).astype(np.float32)
    xt = rng.standard_normal((nt, d)).astype(np.float32)
    return x, y, xt


def _plan_misses():
    return tuple(
        c.cache_info().misses
        for c in (executor.cholesky_plan, executor.lowrank_plan, executor.program_plan)
    )


# ---------------------------------------------------------------------------
# Exactness / positivity / padding.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [48, 57])  # exact tile multiple and odd n
def test_lowrank_full_rank_matches_exact(rng, n):
    """m_inducing = n (u = x): DTC == exact GP up to the K_uu jitter."""
    x, y, xt = _data(rng, n)
    g_lr = GaussianProcess(
        x, y, params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=n, inducing=x,
    )
    g_ex = GaussianProcess(x, y, params=PARAMS, tile_size=M)
    m_lr, c_lr = g_lr.predict_full_cov(xt)
    m_ex, c_ex = g_ex.predict_full_cov(xt)
    np.testing.assert_allclose(np.asarray(m_lr), np.asarray(m_ex), atol=2e-2)
    np.testing.assert_allclose(np.asarray(c_lr), np.asarray(c_ex), atol=2e-2)
    # NLML via Woodbury agrees with the exact tiled NLML
    np.testing.assert_allclose(
        float(g_lr.nlml()), float(g_ex.nlml()), rtol=2e-2
    )


def test_lowrank_variance_nonnegative_and_rmse_reasonable(rng):
    x, y, xt = _data(rng, 120, nt=21)
    g = GaussianProcess(
        x, y, params=PARAMS, tile_size=M, method="lowrank", m_inducing=32
    )
    mean, var = g.predict_with_uncertainty(xt)
    assert np.all(np.asarray(var) >= 0.0)
    ex = GaussianProcess(x, y, params=PARAMS, tile_size=M)
    rmse = float(jnp.sqrt(jnp.mean((mean - ex.predict(xt)) ** 2)))
    assert np.isfinite(rmse) and rmse < 0.5


@pytest.mark.parametrize("strategy", ["subset", "kmeans-lite"])
def test_inducing_strategies(rng, strategy):
    x, y, xt = _data(rng, 90)
    g = GaussianProcess(
        x, y, params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=24, strategy=strategy,
    )
    mean, cov = g.predict_full_cov(xt)
    assert np.isfinite(np.asarray(mean)).all()
    assert np.all(np.diagonal(np.asarray(cov)) >= 0.0)
    assert np.isfinite(float(g.nlml()))


def test_method_validation():
    x = np.zeros((4, 1), np.float32)
    y = np.zeros(4, np.float32)
    with pytest.raises(ValueError, match="method"):
        GaussianProcess(x, y, method="nope")
    with pytest.raises(ValueError, match="m_inducing"):
        GaussianProcess(x, y, method="lowrank")
    with pytest.raises(ValueError, match="m_inducing"):
        GPBatch(x[None], y[None], method="lowrank")
    with pytest.raises(ValueError, match="m_inducing"):
        GPFleet([x], [y], method="lowrank")
    with pytest.raises(ValueError, match="inducing"):
        lowrank.select_inducing(jnp.asarray(x), 8, inducing=jnp.zeros((5, 1)))
    with pytest.raises(ValueError, match="strategy"):
        lowrank.select_inducing(jnp.asarray(x), 2, strategy="bogus")


def test_pallas_backend_parity(rng):
    x, y, xt = _data(rng, 64)
    outs = {}
    for backend in ("jnp", "pallas"):
        g = GaussianProcess(
            x, y, params=PARAMS, tile_size=M,
            method="lowrank", m_inducing=M, op_backend=backend,
        )
        outs[backend] = g.predict_full_cov(xt)
    np.testing.assert_allclose(
        np.asarray(outs["jnp"][0]), np.asarray(outs["pallas"][0]), atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(outs["jnp"][1]), np.asarray(outs["pallas"][1]), atol=2e-3
    )


# ---------------------------------------------------------------------------
# Streaming absorb / forget (the rank-m fast path; never O(n^3)).
# ---------------------------------------------------------------------------


def test_update_absorbs_warm_and_matches_cold_rebuild(rng):
    x, y, xt = _data(rng, 70)
    xb, yb, _ = _data(rng, 9)
    u = x[:24]  # pinned inducing set so warm and cold are the same model
    g = GaussianProcess(
        x, y, params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=24, inducing=u,
    )
    g.predict(xt)  # warm the cache
    g.update(xb, yb)
    assert g._lowrank_warm(), "update must keep the low-rank cache warm"
    cold = GaussianProcess(
        np.concatenate([x, xb]), np.concatenate([y, yb]),
        params=PARAMS, tile_size=M, method="lowrank", m_inducing=24, inducing=u,
    )
    np.testing.assert_allclose(
        np.asarray(g.predict(xt)), np.asarray(cold.predict(xt)), atol=2e-3
    )
    np.testing.assert_allclose(float(g.nlml()), float(cold.nlml()), rtol=1e-3)


def test_forget_downdates_warm_any_k(rng):
    """sign=-1 absorb needs NO tile alignment — any k stays on the fast path."""
    x, y, xt = _data(rng, 80)
    u = x[40:64]
    g = GaussianProcess(
        x, y, params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=24, inducing=u,
    )
    g.predict(xt)
    g.forget(13)  # deliberately NOT a multiple of tile_size
    assert g._lowrank_warm()
    cold = GaussianProcess(
        x[13:], y[13:], params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=24, inducing=u,
    )
    np.testing.assert_allclose(
        np.asarray(g.predict(xt)), np.asarray(cold.predict(xt)), atol=5e-3
    )


def test_sliding_window_evicts_exact_count(rng):
    x, y, xt = _data(rng, 60)
    u = x[:16]
    g = GaussianProcess(
        x, y, params=PARAMS, tile_size=M, sliding_window=60,
        method="lowrank", m_inducing=16, inducing=u,
    )
    g.predict(xt)
    xb, yb, _ = _data(rng, 10)
    g.update(xb, yb)
    assert g.x_train.shape[0] == 60  # exact eviction, no tile rounding
    assert g._lowrank_warm()
    assert np.isfinite(float(g.nlml()))


# ---------------------------------------------------------------------------
# Batched / fleet equivalence + Plan-cache invariance across B.
# ---------------------------------------------------------------------------


def test_gpbatch_matches_per_problem_loop_f64(rng):
    """float64 pins the loop equivalence to 1e-5 (f32 einsum-order roundoff
    would dominate otherwise); also: growing B adds ZERO Plan-cache misses."""
    with compat.enable_x64():
        B, n, mi = 3, 64, 32
        x = rng.standard_normal((B, n, 2))
        y = rng.standard_normal((B, n))
        xt = rng.standard_normal((B, 5, 2))
        kw = dict(
            params=PARAMS, tile_size=M, method="lowrank", m_inducing=mi,
            jitter=1e-10, dtype=jnp.float64,
        )
        gb = GPBatch(x, y, **kw)
        mean, cov = gb.predict_full_cov(xt)
        nlml = np.asarray(gb.nlml())
        misses0 = _plan_misses()
        for i in range(B):
            gi = GaussianProcess(x[i], y[i], **kw)
            mi_, ci_ = gi.predict_full_cov(xt[i])
            np.testing.assert_allclose(
                np.asarray(mean[i]), np.asarray(mi_), atol=1e-5
            )
            np.testing.assert_allclose(
                np.asarray(cov[i]), np.asarray(ci_), atol=1e-5
            )
            np.testing.assert_allclose(nlml[i], float(gi.nlml()), rtol=1e-8)
        # doubling B reuses every executor Plan (geometry-keyed, B-invariant)
        misses1 = _plan_misses()
        x2, y2 = np.concatenate([x, x]), np.concatenate([y, y])
        gb2 = GPBatch(x2, y2, **kw)
        gb2.predict_full_cov(np.concatenate([xt, xt]))
        gb2.nlml()
        assert _plan_misses() == misses1, "growing B must not re-plan"
        del misses0


def test_gpbatch_update_forget_warm(rng):
    B, n = 3, 48
    x = rng.standard_normal((B, n, 2)).astype(np.float32)
    y = rng.standard_normal((B, n)).astype(np.float32)
    xt = rng.standard_normal((B, 4, 2)).astype(np.float32)
    u = x[:, :16]
    gb = GPBatch(
        x, y, params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=16, inducing=u,
    )
    gb.predict(xt)
    xb = rng.standard_normal((B, 6, 2)).astype(np.float32)
    yb = rng.standard_normal((B, 6)).astype(np.float32)
    gb.update(xb, yb)
    assert gb._lowrank_warm()
    cold = GPBatch(
        np.concatenate([x, xb], 1), np.concatenate([y, yb], 1),
        params=PARAMS, tile_size=M, method="lowrank", m_inducing=16, inducing=u,
    )
    np.testing.assert_allclose(
        np.asarray(gb.predict(xt)), np.asarray(cold.predict(xt)), atol=2e-3
    )
    gb.forget(6)
    assert gb._lowrank_warm()
    np.testing.assert_allclose(
        np.asarray(gb.predict(xt)),
        np.asarray(GPBatch(
            np.concatenate([x[:, 6:], xb], 1), np.concatenate([y[:, 6:], yb], 1),
            params=PARAMS, tile_size=M,
            method="lowrank", m_inducing=16, inducing=u,
        ).predict(xt)),
        atol=5e-3,
    )


def test_gpfleet_lowrank_matches_per_problem_loop(rng):
    sizes = (30, 45, 70, 100)
    xs = [rng.standard_normal((n, 2)).astype(np.float32) for n in sizes]
    ys = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    xt = rng.standard_normal((6, 2)).astype(np.float32)
    fl = GPFleet(xs, ys, params=PARAMS, tile_size=M, method="lowrank", m_inducing=16)
    mean, cov = fl.predict_full_cov(xt)
    nlml = np.asarray(fl.nlml())
    for i, n in enumerate(sizes):
        gi = GaussianProcess(
            xs[i], ys[i], params=PARAMS, tile_size=M,
            method="lowrank", m_inducing=16,
        )
        mu_i, cov_i = gi.predict_full_cov(xt)
        np.testing.assert_allclose(np.asarray(mean[i]), np.asarray(mu_i), atol=3e-4)
        np.testing.assert_allclose(np.asarray(cov[i]), np.asarray(cov_i), atol=3e-4)
        np.testing.assert_allclose(nlml[i], float(gi.nlml()), rtol=2e-5)
    # ragged per-problem test sets slice back through nt_valid masking
    tests = [rng.standard_normal((k, 2)).astype(np.float32) for k in (3, 0, 5, 2)]
    outs = fl.predict_each(tests)
    for i, out in enumerate(outs):
        assert out.shape == (tests[i].shape[0],)
        if tests[i].shape[0]:
            ref = GaussianProcess(
                xs[i], ys[i], params=PARAMS, tile_size=M,
                method="lowrank", m_inducing=16,
            ).predict(tests[i])
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4)


def test_gpfleet_lowrank_migration_is_a_row_gather(rng):
    """A problem outgrowing its bucket transfers by pure row gather (the
    low-rank state is mu-sized) and absorbs warm — no re-factorization."""
    sizes = (30, 45, 70, 100)
    xs = [rng.standard_normal((n, 2)).astype(np.float32) for n in sizes]
    ys = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    xt = rng.standard_normal((5, 2)).astype(np.float32)
    u = rng.standard_normal((16, 2)).astype(np.float32)  # shared, pinned
    fl = GPFleet(
        xs, ys, params=PARAMS, tile_size=M,
        method="lowrank", m_inducing=16, inducing=u,
    )
    fl.predict(xt)  # warm every bucket
    arr_x = [rng.standard_normal((k, 2)).astype(np.float32) for k in (40, 0, 4, 10)]
    arr_y = [rng.standard_normal(k).astype(np.float32) for k in (40, 0, 4, 10)]
    assign_before = fl.bucket_assignment()
    fl.update(arr_x, arr_y)
    assert fl.bucket_assignment() != assign_before  # problem 0 migrated
    # every destination bucket stayed warm through the migration
    for cap, rec in fl._buckets.items():
        assert rec.state is not None, f"bucket {cap} went cold"
    cold = GPFleet(
        [np.concatenate([xs[i], arr_x[i]]) for i in range(4)],
        [np.concatenate([ys[i], arr_y[i]]) for i in range(4)],
        params=PARAMS, tile_size=M, method="lowrank", m_inducing=16, inducing=u,
    )
    np.testing.assert_allclose(
        np.asarray(fl.predict(xt)), np.asarray(cold.predict(xt)), atol=3e-3
    )
    np.testing.assert_allclose(
        np.asarray(fl.nlml()), np.asarray(cold.nlml()), rtol=1e-3
    )


# ---------------------------------------------------------------------------
# Training (Woodbury NLML through adam_scan; both backends).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_lowrank_training_improves(rng, backend):
    n = 64
    x = rng.uniform(-3, 3, (n, 1)).astype(np.float32)
    y = (np.sin(1.5 * x[:, 0]) + 0.1 * rng.standard_normal(n)).astype(np.float32)
    _, losses = mll.optimize_hyperparameters(
        jnp.asarray(x), jnp.asarray(y), SEKernelParams.paper_defaults(),
        steps=10, lr=0.05, method="lowrank",
        m_inducing=24, tile_size=M, op_backend=backend,
    )
    losses = np.asarray(losses)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_gp_optimize_routes_lowrank(rng):
    n = 64
    x = rng.uniform(-3, 3, (n, 1)).astype(np.float32)
    y = (np.sin(1.5 * x[:, 0]) + 0.1 * rng.standard_normal(n)).astype(np.float32)
    g = GaussianProcess(x, y, tile_size=M, method="lowrank", m_inducing=24)
    before = float(g.nlml())
    g.optimize(steps=10, lr=0.05)
    assert float(g.nlml()) < before


def test_gpbatch_optimize_lowrank(rng):
    B, n = 3, 48
    x = rng.uniform(-3, 3, (B, n, 1)).astype(np.float32)
    y = (np.sin(1.5 * x[..., 0]) + 0.1 * rng.standard_normal((B, n))).astype(
        np.float32
    )
    gb = GPBatch(x, y, tile_size=M, method="lowrank", m_inducing=16)
    before = np.asarray(gb.nlml())
    gb.optimize(steps=8, lr=0.05)
    after = np.asarray(gb.nlml())
    assert np.isfinite(after).all()
    assert (after < before).all()


def test_lowrank_custom_vjp_matches_autodiff(rng):
    n = 56
    x = jnp.asarray(rng.standard_normal((n, 2)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    raw = mll._pack(PARAMS)
    kw = dict(m_inducing=16, tile_size=M)
    g_c = np.asarray(jax.grad(
        lambda r: mll.nlml_lowrank(x, y, mll._unpack(r), vjp="custom", **kw)
    )(raw))
    g_a = np.asarray(jax.grad(
        lambda r: mll.nlml_lowrank(x, y, mll._unpack(r), vjp="autodiff", **kw)
    )(raw))
    np.testing.assert_allclose(g_c, g_a, rtol=2e-2, atol=2e-2 * np.abs(g_a).max())


def test_lowrank_ragged_batched_nlml_matches_loop(rng):
    """Zero-padded ragged problems through ONE batched low-rank build give
    per-problem NLMLs equal to the single-problem loop."""
    sizes = (40, 64)
    cap = 64
    xs = [rng.standard_normal((n, 2)).astype(np.float32) for n in sizes]
    ys = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    x = jnp.stack([jnp.pad(jnp.asarray(x), ((0, cap - x.shape[0]), (0, 0)))
                   for x in xs])
    y = jnp.stack([jnp.pad(jnp.asarray(y), (0, cap - y.shape[0])) for y in ys])
    nv = jnp.asarray(sizes, jnp.int32)
    vals = mll.nlml_lowrank_batched(
        x, y, PARAMS, m_inducing=16, tile_size=M, n_valid=nv
    )
    for i, n in enumerate(sizes):
        ref = mll.nlml_lowrank(
            jnp.asarray(xs[i]), jnp.asarray(ys[i]), PARAMS,
            m_inducing=16, tile_size=M, vjp="autodiff",
        )
        np.testing.assert_allclose(float(vals[i]), float(ref), rtol=2e-3)


# ---------------------------------------------------------------------------
# Serving: continuous batching over a low-rank fleet (DESIGN.md §11 + §14).
# ---------------------------------------------------------------------------


def test_continuous_batcher_lowrank_bucket(rng):
    """The serving loop drives low-rank buckets with the exact tier's
    contract: observes land before predicts inside a wave, per-request rows
    slice back out of the shared nt_valid-masked launch, and post-update
    predictions equal a cold GP on the grown problem."""
    from repro.serve import ContinuousBatcher

    sizes = (40, 60)
    xs = [rng.standard_normal((n, 2)).astype(np.float32) for n in sizes]
    ys = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    u = rng.standard_normal((16, 2)).astype(np.float32)
    kw = dict(
        params=PARAMS, tile_size=M, method="lowrank", m_inducing=16, inducing=u
    )
    fleet = GPFleet(xs, ys, **kw)
    ticks = iter(range(1000))
    srv = ContinuousBatcher(fleet, clock=lambda: float(next(ticks)))

    xt = rng.standard_normal((4, 2)).astype(np.float32)
    r1 = srv.submit_predict(0, xt)
    r2 = srv.submit_predict(0, xt[:2], uncertainty=True)
    xo = rng.standard_normal((30, 2)).astype(np.float32)
    yo = rng.standard_normal(30).astype(np.float32)
    r3 = srv.submit_observe(1, xo, yo)
    stats = srv.step()
    assert (stats.n_predict, stats.n_observe, stats.points_absorbed) == (2, 1, 30)
    assert stats.migrations == 1  # 60 + 30 crosses the cap-4 boundary at 64

    # wave-ordering + masking identical to the exact tier: both problem-0
    # requests share one launch and slice their own rows back out
    g0 = GaussianProcess(xs[0], ys[0], **kw)
    np.testing.assert_allclose(srv.result(r1), np.asarray(g0.predict(xt)), atol=3e-4)
    m2, v2 = srv.result(r2)
    np.testing.assert_allclose(m2, np.asarray(g0.predict(xt[:2])), atol=3e-4)
    assert (v2 >= 0).all()
    assert srv.result(r3) == 30

    # the post-update state answers like a fresh low-rank GP (same pinned u)
    rid = srv.submit_predict(1, xt)
    srv.run_until_idle()
    g1 = GaussianProcess(
        np.concatenate([xs[1], xo]), np.concatenate([ys[1], yo]), **kw
    )
    np.testing.assert_allclose(srv.result(rid), np.asarray(g1.predict(xt)), atol=3e-3)
