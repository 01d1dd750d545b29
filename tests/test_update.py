"""Streaming updates (DESIGN.md §10): block Cholesky append / evict.

Correctness bar: a posterior maintained incrementally (extend / shrink)
must match a from-scratch fit of the same dataset — factor, weights and
predictions — across backends, dtypes and the problem-batch axis, and the
numerical-stability guardrail (NaN heads -> CholeskyUpdateError -> full
refactorization) must actually fire.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import GaussianProcess, GPBatch, SEKernelParams
from repro.core import executor, scheduler, tiling, triangular, update
from repro.core import predict as pred
from repro.core.scheduler import Head

PARAMS = SEKernelParams.paper_defaults()


def _data(rng, n, d=2, dtype=np.float32):
    x = rng.standard_normal((n, d)).astype(dtype)
    y = rng.standard_normal(n).astype(dtype)
    return x, y


def _scratch(x, y, m, **kw):
    return pred.posterior_state(jnp.asarray(x), jnp.asarray(y), PARAMS, m, **kw)


# ---------------------------------------------------------------------------
# Scheduler: the two update-DAG families.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r", [0, 1, 3, 6])
def test_append_dag_invariants(r):
    """Task counts, topological order, and wavefront antichains."""
    sched = scheduler.build_update_schedule(r, kind="update_append")
    counts = sched.op_counts()
    assert counts.get(scheduler.UASM, 0) == r
    assert counts[scheduler.UASMD] == 1
    assert counts.get(scheduler.UTRSM, 0) == r
    assert counts.get(scheduler.UGEMM, 0) == r * (r - 1) // 2
    assert counts.get(scheduler.USYRK, 0) == r
    assert counts[scheduler.UPOTRF] == 1
    level_of = {t: i for i, lv in enumerate(sched.levels) for t in lv}
    for t, lv in level_of.items():
        for d in scheduler.append_deps(t, r):
            assert level_of[d] < lv, (t, d)


@pytest.mark.parametrize("m_tiles", [1, 2, 4, 7])
@pytest.mark.parametrize("ns", [None, 1, 4])
def test_rank_update_dag_invariants(m_tiles, ns):
    if ns is None:
        sched = scheduler.build_update_schedule(m_tiles, kind="update_rank")
    else:
        sched = scheduler.build_wavefront_schedule(
            m_tiles, ns, kind="update_rank"
        )
    counts = sched.op_counts()
    assert counts[scheduler.UPREP] == m_tiles
    assert counts.get(scheduler.UPROW, 0) == m_tiles * (m_tiles - 1) // 2
    assert counts.get(scheduler.UCARRY, 0) == m_tiles * (m_tiles - 1) // 2
    level_of = {t: i for i, lv in enumerate(sched.levels) for t in lv}
    assert len(level_of) == sched.n_tasks  # no task lost or duplicated
    for t, lv in level_of.items():
        for d in scheduler.rank_update_deps(t, m_tiles):
            assert level_of[d] < lv, (t, d)


def test_update_plans_are_cached():
    executor.update_append_plan.cache_clear()
    p1 = executor.update_append_plan(3, 3, None)
    p2 = executor.update_append_plan(3, 3, None)
    assert p1 is p2
    assert executor.update_append_plan.cache_info().misses == 1
    # a plan's flat tasks cover the DAG exactly once
    sched = scheduler.build_update_schedule(3, kind="update_append")
    assert sorted(p1.flat_tasks()) == sorted(
        t for lv in sched.levels for t in lv
    )


# ---------------------------------------------------------------------------
# extend: incremental factor == from-scratch factorization of the grown set.
# ---------------------------------------------------------------------------


def _extend_grid():
    cells = []
    for n0, b in [(32, 5), (30, 5), (30, 40), (10, 3), (48, 16)]:
        for backend in ("jnp", "pallas"):
            heavy = backend == "pallas" and (n0 + b) > 50
            marks = [pytest.mark.slow] if heavy else []
            cells.append(
                pytest.param(n0, b, backend, marks=marks,
                             id=f"n{n0}-b{b}-{backend}")
            )
    return cells


@pytest.mark.parametrize("n0,b,backend", _extend_grid())
def test_extend_matches_scratch(rng, n0, b, backend):
    m = 16
    x, y = _data(rng, n0 + b)
    state = _scratch(x[:n0], y[:n0], m, backend=backend)
    grown = state.extend(x[n0:], y[n0:], backend=backend)
    ref = _scratch(x, y, m, backend=backend)
    assert grown.n == n0 + b
    np.testing.assert_allclose(
        np.asarray(grown.lpacked), np.asarray(ref.lpacked), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(grown.alpha), np.asarray(ref.alpha), rtol=1e-3, atol=1e-4
    )
    xt = rng.standard_normal((7, x.shape[1])).astype(np.float32)
    mu, cov = pred.predict_from_state(grown, jnp.asarray(xt), head=Head.COVARIANCE)
    mu_r, cov_r = pred.predict_from_state(ref, jnp.asarray(xt), head=Head.COVARIANCE)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(cov), np.asarray(cov_r), atol=1e-4)


def test_extend_float64_exactish(rng):
    """The f64 guardrail path: append error at the 1e-12 level."""
    with compat.enable_x64():
        n0, b, m = 40, 13, 16
        x, y = _data(rng, n0 + b, dtype=np.float64)
        state = pred.posterior_state(
            jnp.asarray(x[:n0]), jnp.asarray(y[:n0]), PARAMS, m, dtype=jnp.float64
        )
        grown = state.extend(x[n0:], y[n0:])
        ref = pred.posterior_state(
            jnp.asarray(x), jnp.asarray(y), PARAMS, m, dtype=jnp.float64
        )
        assert grown.lpacked.dtype == jnp.float64
        np.testing.assert_allclose(
            np.asarray(grown.lpacked), np.asarray(ref.lpacked), atol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(grown.alpha), np.asarray(ref.alpha), atol=1e-10
        )


def test_extend_legacy_state_without_live_fields(rng):
    """Pre-§10 states (beta/y_chunks None) are reconstructed on the fly."""
    n0, b, m = 32, 7, 16
    x, y = _data(rng, n0 + b)
    s = _scratch(x[:n0], y[:n0], m)
    legacy = pred.PosteriorState(
        lpacked=s.lpacked, alpha=s.alpha, x_chunks=s.x_chunks,
        n=s.n, m=s.m, params=s.params,
    )
    grown = legacy.extend(x[n0:], y[n0:])
    ref = _scratch(x, y, m)
    np.testing.assert_allclose(
        np.asarray(grown.alpha), np.asarray(ref.alpha), rtol=1e-3, atol=1e-4
    )


def test_packed_matvec_roundtrip(rng):
    """beta = L^T alpha and y = L beta reconstruct the live chunks."""
    n, m = 48, 16
    x, y = _data(rng, n)
    s = _scratch(x, y, m)
    beta = triangular.packed_matvec(s.lpacked, s.alpha, transpose=True)
    np.testing.assert_allclose(
        np.asarray(beta), np.asarray(s.beta), rtol=1e-4, atol=1e-5
    )
    yc = triangular.packed_matvec(s.lpacked, beta, transpose=False)
    np.testing.assert_allclose(
        np.asarray(yc), np.asarray(s.y_chunks), rtol=1e-3, atol=1e-4
    )


# ---------------------------------------------------------------------------
# shrink / rank updates / downdate round-trip.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [(48, 16), (50, 16), (64, 32)])
def test_shrink_matches_scratch(rng, n, k):
    m = 16
    x, y = _data(rng, n)
    state = _scratch(x, y, m)
    kept = state.shrink(k)
    ref = _scratch(x[k:], y[k:], m)
    assert kept.n == n - k
    np.testing.assert_allclose(
        np.asarray(kept.lpacked), np.asarray(ref.lpacked), rtol=1e-3, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(kept.alpha), np.asarray(ref.alpha), rtol=1e-3, atol=2e-4
    )


def test_shrink_validates(rng):
    x, y = _data(rng, 48)
    state = _scratch(x, y, 16)
    with pytest.raises(ValueError, match="multiple"):
        state.shrink(10)
    with pytest.raises(ValueError, match="evict"):
        state.shrink(48)


def _spd_factor(rng, n, m):
    a = rng.standard_normal((n, n))
    k = a @ a.T + n * np.eye(n)
    return k, tiling.pack_lower(jnp.asarray(np.linalg.cholesky(k), jnp.float32), m)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_rank_update_matches_dense(rng, backend):
    n, m, r = 48, 16, 5
    k, lp = _spd_factor(rng, n, m)
    w = np.zeros((n // m, m, m), np.float32)
    wv = rng.standard_normal((n, r)).astype(np.float32) * 0.3
    w[:, :, :r] = wv.reshape(n // m, m, r)
    up = update.update_factor(lp, jnp.asarray(w), backend=backend)
    ref = tiling.pack_lower(
        jnp.asarray(np.linalg.cholesky(k + wv @ wv.T), jnp.float32), m
    )
    np.testing.assert_allclose(np.asarray(up), np.asarray(ref), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_downdate_then_update_roundtrip(rng, backend):
    """downdate(update(L, W), W) == L — the hyperbolic sweep inverts the
    positive one (and exercises the new Pallas carry kernel)."""
    n, m, r = 48, 16, 4
    _, lp = _spd_factor(rng, n, m)
    w = np.zeros((n // m, m, m), np.float32)
    w[:, :, :r] = (rng.standard_normal((n, r)) * 0.5).reshape(n // m, m, r)
    wj = jnp.asarray(w)
    up = update.update_factor(lp, wj, backend=backend)
    back = update.downdate_factor(up, wj, backend=backend)
    np.testing.assert_allclose(np.asarray(back), np.asarray(lp), rtol=1e-3, atol=1e-3)


def test_nonpd_downdate_raises(rng):
    n, m = 48, 16
    _, lp = _spd_factor(rng, n, m)
    w = jnp.asarray(
        rng.standard_normal((n // m, m, m)).astype(np.float32) * 100.0
    )
    with pytest.raises(update.CholeskyUpdateError, match="refactorization"):
        update.downdate_factor(lp, w)


# ---------------------------------------------------------------------------
# GaussianProcess / GPBatch front-ends: cache contract + fleet equivalence.
# ---------------------------------------------------------------------------


def test_gp_update_extends_warm_cache(rng, monkeypatch):
    """A warm update must extend the cached posterior — zero refactorizations
    — and the following predict must match a from-scratch GP."""
    x, y = _data(rng, 50)
    xt = rng.standard_normal((9, 2)).astype(np.float32)
    gp = GaussianProcess(x[:40], y[:40], tile_size=16)
    gp.predict(xt)  # warm the cache
    calls = {"n": 0}
    orig = pred.posterior_state

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(pred, "posterior_state", counted)
    gp.update(x[40:], y[40:])
    assert gp._cache_warm(), "warm update must keep the posterior cache"
    mu = gp.predict(xt)
    assert calls["n"] == 0, "update ran a full refactorization"
    ref = GaussianProcess(x, y, tile_size=16).predict(xt)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(ref), atol=1e-4)
    assert float(gp.nlml()) == pytest.approx(
        float(GaussianProcess(x, y, tile_size=16).nlml()), rel=1e-4
    )


def test_gp_update_cold_cache_invalidates(rng):
    x, y = _data(rng, 50)
    xt = rng.standard_normal((5, 2)).astype(np.float32)
    gp = GaussianProcess(x[:40], y[:40], tile_size=16)
    gp.update(x[40:], y[40:])  # nothing cached yet
    assert gp._posterior is None, "cold update must leave the cache cold"
    mu = gp.predict(xt)
    ref = GaussianProcess(x, y, tile_size=16).predict(xt)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(ref), atol=1e-5)


def test_gp_update_numerical_fallback(rng, monkeypatch):
    """A numerically failed append falls back to cache invalidation; the
    next predict refactorizes and stays correct."""
    x, y = _data(rng, 50)
    xt = rng.standard_normal((5, 2)).astype(np.float32)
    gp = GaussianProcess(x[:40], y[:40], tile_size=16)
    gp.predict(xt)

    def boom(*a, **kw):
        raise update.CholeskyUpdateError("synthetic instability")

    monkeypatch.setattr(update, "extend_state", boom)
    gp.update(x[40:], y[40:])
    assert gp._posterior is None, "failed append must invalidate the cache"
    monkeypatch.undo()
    mu = gp.predict(xt)
    ref = GaussianProcess(x, y, tile_size=16).predict(xt)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(ref), atol=1e-5)


def test_gp_update_validates_shapes(rng):
    x, y = _data(rng, 32)
    gp = GaussianProcess(x, y, tile_size=16)
    with pytest.raises(ValueError, match="update"):
        gp.update(rng.standard_normal((3, 2)).astype(np.float32), np.zeros(4, np.float32))


def test_gp_sliding_window(rng):
    """update() with sliding_window evicts the oldest rows and keeps the
    cache warm end-to-end (append + evict both on the fast path)."""
    x, y = _data(rng, 48)
    xt = rng.standard_normal((7, 2)).astype(np.float32)
    gp = GaussianProcess(x[:32], y[:32], tile_size=16, sliding_window=32)
    gp.predict(xt)
    gp.update(x[32:48], y[32:48])  # 48 > 32: evict the oldest 16
    assert gp.y_train.shape[0] == 32
    assert gp._cache_warm()
    ref = GaussianProcess(x[16:48], y[16:48], tile_size=16).predict(xt)
    np.testing.assert_allclose(
        np.asarray(gp.predict(xt)), np.asarray(ref), atol=1e-4
    )


def test_gp_forget_unaligned_falls_back(rng):
    x, y = _data(rng, 40)
    xt = rng.standard_normal((5, 2)).astype(np.float32)
    gp = GaussianProcess(x, y, tile_size=16)
    gp.predict(xt)
    gp.forget(10)  # not tile-aligned: cache must invalidate, result correct
    assert gp._posterior is None
    ref = GaussianProcess(x[10:], y[10:], tile_size=16).predict(xt)
    np.testing.assert_allclose(np.asarray(gp.predict(xt)), np.asarray(ref), atol=1e-5)
    with pytest.raises(ValueError, match="forget"):
        gp.forget(40)


def test_gpbatch_update_matches_loop(rng):
    """Fleet update == per-problem single-GP updates (one batched sweep)."""
    b, n0, badd, m = 3, 30, 10, 16
    xs = rng.standard_normal((b, n0 + badd, 2)).astype(np.float32)
    ys = rng.standard_normal((b, n0 + badd)).astype(np.float32)
    xt = rng.standard_normal((6, 2)).astype(np.float32)
    fleet = GPBatch(xs[:, :n0], ys[:, :n0], tile_size=m)
    fleet.predict(xt)
    fleet.update(xs[:, n0:], ys[:, n0:])
    assert fleet._cache_warm(), "fleet update must keep the stacked cache"
    mu = fleet.predict(xt)
    for i in range(b):
        gp = GaussianProcess(xs[i, :n0], ys[i, :n0], tile_size=m)
        gp.predict(xt)
        gp.update(xs[i, n0:], ys[i, n0:])
        np.testing.assert_allclose(
            np.asarray(mu[i]), np.asarray(gp.predict(xt)), rtol=1e-4, atol=1e-4
        )
    # fleet eviction
    fleet.forget(m)
    assert fleet._cache_warm()
    mu2 = fleet.predict(xt)
    ref = GaussianProcess(xs[1, m:], ys[1, m:], tile_size=m).predict(xt)
    np.testing.assert_allclose(np.asarray(mu2[1]), np.asarray(ref), rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="GPBatch.update"):
        fleet.update(xs[:2, :2], ys[:2, :2])


# ---------------------------------------------------------------------------
# Property: any sequence of small appends converges to the from-scratch fit.
# ---------------------------------------------------------------------------

try:
    import hypothesis  # noqa: F401

    _HAVE_HYP = True
except ImportError:
    _HAVE_HYP = False

if _HAVE_HYP:
    from hypothesis import given, settings, strategies as st

    @given(
        n0=st.integers(4, 40),
        chunks=st.lists(st.integers(1, 12), min_size=1, max_size=4),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=10, deadline=None)
    def test_property_repeated_appends(n0, chunks, seed):
        rng = np.random.default_rng(seed)
        m = 16
        total = n0 + sum(chunks)
        x = rng.standard_normal((total, 2)).astype(np.float32)
        y = rng.standard_normal(total).astype(np.float32)
        state = pred.posterior_state(
            jnp.asarray(x[:n0]), jnp.asarray(y[:n0]), PARAMS, m
        )
        pos = n0
        for c in chunks:
            state = state.extend(x[pos : pos + c], y[pos : pos + c])
            pos += c
        ref = pred.posterior_state(jnp.asarray(x), jnp.asarray(y), PARAMS, m)
        assert state.n == total
        np.testing.assert_allclose(
            np.asarray(state.alpha), np.asarray(ref.alpha), rtol=5e-3, atol=5e-4
        )
