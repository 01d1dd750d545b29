"""Schedule-driven executor: equivalence vs monolithic references, plan
structure (batch counts match Schedule.levels), and GP factor caching."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GaussianProcess, SEKernelParams
from repro.core import cholesky as chol
from repro.core import executor, tiling, triangular
from repro.core import predict as pred
from repro.core import scheduler as sch


def _spd(rng, n, dtype=np.float32):
    a = rng.standard_normal((n, n)).astype(dtype)
    return a @ a.T + n * np.eye(n, dtype=dtype)


# ---------------------------------------------------------------------------
# Cholesky equivalence: executor vs monolithic reference.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_streams", [None, 1, 2])
@pytest.mark.parametrize("n,m", [(64, 16), (200, 40), (512, 128)])
def test_executor_cholesky_matches_monolithic(rng, n, m, n_streams):
    k = _spd(rng, n)
    l_e = np.asarray(
        chol.cholesky_dense_via_tiles(jnp.asarray(k), m, n_streams=n_streams)
    )
    l_m = np.asarray(chol.monolithic_cholesky(jnp.asarray(k)))
    np.testing.assert_allclose(l_e, l_m, atol=2e-3)


@pytest.mark.parametrize("n_streams", [None, 2])
def test_executor_pallas_backend(rng, n_streams):
    k = _spd(rng, 64)
    l_p = np.asarray(
        chol.cholesky_dense_via_tiles(
            jnp.asarray(k), 16, backend="pallas", n_streams=n_streams
        )
    )
    l_m = np.asarray(chol.monolithic_cholesky(jnp.asarray(k)))
    np.testing.assert_allclose(l_p, l_m, atol=1e-3)


def test_executor_mixed_precision(rng):
    k = _spd(rng, 64)
    l32 = np.asarray(chol.cholesky_dense_via_tiles(jnp.asarray(k), 16))
    lmp = np.asarray(
        chol.cholesky_dense_via_tiles(jnp.asarray(k), 16, update_dtype=jnp.bfloat16)
    )
    assert np.abs(lmp - l32).max() / np.abs(l32).max() < 0.02


# ---------------------------------------------------------------------------
# Schedule-driven triangular solves.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_streams", [None, 1, 2])
def test_solves_match_dense(rng, n_streams):
    n, m = 128, 16
    k = _spd(rng, n)
    lref = np.linalg.cholesky(k)
    lp = chol.tiled_cholesky(tiling.pack_lower(jnp.asarray(k), m))
    y = rng.standard_normal(n).astype(np.float32)
    b = triangular.forward_substitution(
        lp, jnp.asarray(y).reshape(-1, m), n_streams=n_streams
    )
    np.testing.assert_allclose(
        np.asarray(b).reshape(-1), np.linalg.solve(lref, y), atol=1e-3
    )
    a = triangular.backward_substitution(lp, b, n_streams=n_streams)
    np.testing.assert_allclose(
        np.asarray(a).reshape(-1), np.linalg.solve(k, y), rtol=2e-2, atol=2e-3
    )
    q = 32
    bm = rng.standard_normal((n, q)).astype(np.float32)
    bt = tiling.tile_dense(jnp.asarray(bm), m)
    v = triangular.forward_substitution_matrix(lp, bt, n_streams=n_streams)
    np.testing.assert_allclose(
        np.asarray(tiling.untile_dense(v)), np.linalg.solve(lref, bm), atol=1e-3
    )
    x = triangular.backward_substitution_matrix(lp, bt, n_streams=n_streams)
    np.testing.assert_allclose(
        np.asarray(tiling.untile_dense(x)), np.linalg.solve(lref.T, bm), atol=1e-3
    )


# ---------------------------------------------------------------------------
# End-to-end prediction equivalence (padding remainders included).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_streams", [None, 1, 2])
@pytest.mark.parametrize("n,m", [(64, 16), (200, 48), (512, 128)])
def test_predict_matches_monolithic(rng, n, m, n_streams):
    # (200, 48) and (512, 128)→n=512 exact; 200 % 48 != 0 exercises padding
    d, nt = 3, 29
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    xt = rng.standard_normal((nt, d)).astype(np.float32)
    p = SEKernelParams.paper_defaults()
    mu_t, cov_t = pred.predict(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), p, m,
        full_cov=True, n_streams=n_streams,
    )
    mu_m, cov_m = pred.predict_monolithic(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), p, full_cov=True
    )
    np.testing.assert_allclose(np.asarray(mu_t), np.asarray(mu_m), atol=5e-3)
    np.testing.assert_allclose(np.asarray(cov_t), np.asarray(cov_m), atol=5e-3)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_predict_backends_agree(rng, backend):
    n, nt, d, m = 70, 11, 2, 16  # padding remainder on both train and test
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    xt = rng.standard_normal((nt, d)).astype(np.float32)
    p = SEKernelParams.paper_defaults()
    mu = np.asarray(
        pred.predict(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), p, m,
            backend=backend, n_streams=2,
        )
    )
    mu_m = np.asarray(
        pred.predict_monolithic(jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), p)
    )
    np.testing.assert_allclose(mu, mu_m, atol=2e-3)


# ---------------------------------------------------------------------------
# Fused whole-pipeline program: equivalence vs the staged baseline.
# ---------------------------------------------------------------------------


# tile size per (n, backend): padding remainders covered (200 % 32 != 0) and
# the Pallas trailing-update kernel needs power-of-two-divisible tiles; the
# larger Pallas cases use coarser tiles because interpret mode pays per launch
_FUSED_M = {(64, "jnp"): 16, (200, "jnp"): 32, (512, "jnp"): 128,
            (64, "pallas"): 16, (200, "pallas"): 64, (512, "pallas"): 128}
_FUSED_DATA = {}


def _fused_case(n, uncertainty, backend):
    """Deterministic inputs + the staged reference, shared across the
    n_streams sweep (staged results differ across n_streams only by fp
    noise orders below the 1e-4 acceptance rtol)."""
    key = (n, uncertainty, backend)
    if key not in _FUSED_DATA:
        d, nt, m = 3, 29, _FUSED_M[(n, backend)]
        r = np.random.default_rng(n)
        x = jnp.asarray(r.standard_normal((n, d)).astype(np.float32))
        y = jnp.asarray(r.standard_normal(n).astype(np.float32))
        xt = jnp.asarray(r.standard_normal((nt, d)).astype(np.float32))
        p = SEKernelParams.paper_defaults()
        staged = pred.predict_staged(
            x, y, xt, p, m,
            full_cov=uncertainty, n_streams=4, backend=backend,
        )
        _FUSED_DATA[key] = (x, y, xt, p, m, staged)
    return _FUSED_DATA[key]


@pytest.mark.parametrize("n_streams", [None, 1, 4])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("uncertainty", [False, True])
@pytest.mark.parametrize("n", [64, 200, 512])
def test_fused_matches_staged(n, uncertainty, backend, n_streams):
    """Acceptance grid: the fused program and the staged pipeline must agree
    to <= 1e-4 rtol for n x uncertainty x op_backend x n_streams."""
    x, y, xt, p, m, staged = _fused_case(n, uncertainty, backend)
    fused = pred.predict(
        x, y, xt, p, m,
        full_cov=uncertainty, n_streams=n_streams, backend=backend,
    )
    if not uncertainty:
        fused, staged = (fused,), (staged,)
    for f, s in zip(fused, staged):
        # atol floors the rtol for near-zero predictive means, where jit
        # (fused) vs eager (staged) reduction order leaves ~1e-5 fp noise
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(s), rtol=1e-4, atol=1e-4
        )


def test_fused_state_slice_matches_staged_state(rng):
    """PosteriorState sliced from the program env == the staged builder's."""
    n, d, m = 96, 2, 16
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    xt = jnp.asarray(rng.standard_normal((5, d)).astype(np.float32))
    p = SEKernelParams.paper_defaults()
    _, st_f = pred.predict_fused(x, y, xt, p, m, with_state=True)
    st_s = pred.posterior_state(x, y, p, m)
    np.testing.assert_allclose(
        np.asarray(st_f.lpacked), np.asarray(st_s.lpacked), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(st_f.alpha), np.asarray(st_s.alpha), atol=2e-4
    )


# ---------------------------------------------------------------------------
# Acceptance: for M >= 8 the fused program issues strictly fewer batched
# launches than the sum of the staged pipeline's launches.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("uncertainty", [False, True, pytest.param(sch.Head.VARIANCE, id="variance")])
@pytest.mark.parametrize("m_tiles", [8, 12, 16])
def test_fused_program_fewer_launches(m_tiles, uncertainty):
    q_tiles = max(m_tiles // 4, 1)
    for ns in (None, 4, 16):
        fused = executor.program_plan(m_tiles, q_tiles, uncertainty, ns).n_batches
        staged = executor.staged_launch_count(
            m_tiles, head=uncertainty, n_streams=ns
        )
        assert fused < staged, (m_tiles, uncertainty, ns, fused, staged)
    # n_streams=1 is the fully sequential baseline: one task per launch
    # leaves nothing to fuse — the program must still never be *worse*.
    # Likewise the n_streams == M boundary may tie.
    for ns in (1, 8):
        fused = executor.program_plan(m_tiles, q_tiles, uncertainty, ns).n_batches
        staged = executor.staged_launch_count(
            m_tiles, head=uncertainty, n_streams=ns
        )
        assert fused <= staged, (m_tiles, uncertainty, ns, fused, staged)


@pytest.mark.parametrize("uncertainty", [False, True, pytest.param(sch.Head.VARIANCE, id="variance")])
@pytest.mark.parametrize("n_streams", [None, 1, 4])
def test_program_plan_covers_dag(uncertainty, n_streams):
    m_tiles, q_tiles = 6, 2
    plan = executor.program_plan(m_tiles, q_tiles, uncertainty, n_streams)
    tasks = sch.program_tasks(m_tiles, q_tiles, head=uncertainty)
    assert sorted(plan.flat_tasks()) == sorted(tasks)
    level_of = {
        t: li for li, lvl in enumerate(plan.levels) for b in lvl for t in b.tasks
    }
    for t in tasks:
        for d in sch.program_deps(t, m_tiles, q_tiles, uncertainty):
            assert level_of[d] < level_of[t], (t, d)


# Launches and waves of the parent's program plans at M = 8, Q = 4, pinned per
# n_streams: the variance head leaves the covariance head's plan as it was.
_PINNED_PLANS = {  # n_streams: (covariance batches, waves; mean-only batches)
    None: (73, 40, 55), 1: (234, 230, 195), 2: (152, 117, 123),
    4: (120, 61, 92), 8: (99, 41, 78), 16: (79, 40, 60),
}


@pytest.mark.parametrize("n_streams", list(_PINNED_PLANS))
def test_program_plan_counts_pinned(n_streams):
    cov_batches, cov_waves, none_batches = _PINNED_PLANS[n_streams]
    cov = executor.program_plan(8, 4, sch.Head.COVARIANCE, n_streams)
    assert (cov.n_batches, len(cov.levels)) == (cov_batches, cov_waves)
    assert executor.program_plan(8, 4, True, n_streams) is cov  # the old flag
    assert executor.program_plan(8, 4, sch.Head.NONE, n_streams).n_batches == none_batches
    # the variance head launches as often: one diagonal PRIOR batch where the
    # covariance head has its full grid, the same single GRAM
    var = executor.program_plan(8, 4, sch.Head.VARIANCE, n_streams)
    assert var.n_batches == cov_batches


# ---------------------------------------------------------------------------
# Plan structure: batch counts must match the Schedule's levels.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m_tiles", [1, 2, 5, 8])
def test_cholesky_plan_counts_match_schedule(m_tiles):
    s = sch.build_schedule(m_tiles)
    plan = executor.cholesky_plan(m_tiles, None)
    assert plan.level_task_counts() == [len(l) for l in s.levels]
    assert sorted(plan.flat_tasks()) == sorted(sch.all_tasks(m_tiles))


@pytest.mark.parametrize("m_tiles", [1, 2, 5, 8])
@pytest.mark.parametrize("n_streams", [1, 3])
def test_cholesky_wavefront_plan_covers_dag(m_tiles, n_streams):
    s = sch.build_wavefront_schedule(m_tiles, n_streams)
    plan = executor.cholesky_plan(m_tiles, n_streams)
    assert plan.level_task_counts() == [len(l) for l in s.levels]
    assert sorted(plan.flat_tasks()) == sorted(sch.all_tasks(m_tiles))
    assert all(b.size <= n_streams for lvl in plan.levels for b in lvl)
    assert all(len(lvl) <= n_streams for lvl in s.levels)


@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("m_tiles", [1, 2, 6])
def test_solve_plan_counts_match_schedule(m_tiles, lower):
    s = sch.build_solve_schedule(m_tiles, lower=lower)
    plan = executor.solve_plan(m_tiles, lower=lower, n_streams=None)
    assert plan.level_task_counts() == [len(l) for l in s.levels]
    assert sorted(plan.flat_tasks()) == sorted(sch.solve_tasks(m_tiles, lower=lower))


def test_wavefront_batches_across_columns():
    """The executor's raison d'être: with a finite stream pool, trailing
    updates of column j co-batch with panel tasks of column j+1."""
    plan = executor.cholesky_plan(8, 4)
    mixed_wave = any(
        len({t[2] for b in lvl for t in b.tasks}) > 1 for lvl in plan.levels
    )
    assert mixed_wave, "no wave ever contained tasks from multiple columns"
    mixed_batch = any(
        len({t[2] for t in b.tasks}) > 1 for lvl in plan.levels for b in lvl
    )
    assert mixed_batch, "no single batched launch mixed columns"


# ---------------------------------------------------------------------------
# GaussianProcess factor caching.
# ---------------------------------------------------------------------------


def _counting(monkeypatch):
    """Count O(n^3) posterior builds: staged (posterior_state) or fused
    (predict_fused populating the cache via with_state=True)."""
    calls = {"n": 0}
    orig_state = pred.posterior_state
    orig_fused = pred.predict_fused

    def wrapped_state(*a, **kw):
        calls["n"] += 1
        return orig_state(*a, **kw)

    def wrapped_fused(*a, **kw):
        if kw.get("with_state"):
            calls["n"] += 1
        return orig_fused(*a, **kw)

    monkeypatch.setattr(pred, "posterior_state", wrapped_state)
    monkeypatch.setattr(pred, "predict_fused", wrapped_fused)
    return calls


def test_gp_caches_factor_across_predicts(rng, monkeypatch):
    calls = _counting(monkeypatch)
    n, d = 48, 2
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    gp = GaussianProcess(x, y, tile_size=16)
    xt = rng.standard_normal((7, d)).astype(np.float32)
    mu1 = gp.predict(xt)
    gp.predict(rng.standard_normal((5, d)).astype(np.float32))
    gp.predict_full_cov(xt)
    assert calls["n"] == 1
    assert gp.posterior() is gp.posterior()
    # param change invalidates
    gp.params = SEKernelParams(0.5, 1.0, 0.1)
    mu2 = gp.predict(xt)
    assert calls["n"] == 2
    assert not np.allclose(np.asarray(mu1), np.asarray(mu2))


def test_gp_data_rebind_invalidates_cache(rng):
    n, d = 48, 2
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    xt = rng.standard_normal((5, d)).astype(np.float32)
    gp = GaussianProcess(x, y, tile_size=16)
    mu1 = np.asarray(gp.predict(xt))
    gp.y_train = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    mu2 = np.asarray(gp.predict(xt))  # must not serve the stale factor
    assert not np.allclose(mu1, mu2)


def test_gp_optimize_invalidates_cache(rng, monkeypatch):
    calls = _counting(monkeypatch)
    x = rng.uniform(-3, 3, (32, 1)).astype(np.float32)
    y = np.sin(2 * x[:, 0]).astype(np.float32)
    gp = GaussianProcess(x, y, tile_size=16)
    gp.predict(x[:4])
    assert calls["n"] == 1
    gp.optimize(steps=2, lr=0.05)
    gp.predict(x[:4])
    assert calls["n"] == 2


def test_cached_prediction_matches_uncached(rng):
    n, d = 100, 3  # not a tile multiple
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    xt = rng.standard_normal((13, d)).astype(np.float32)
    p = SEKernelParams.paper_defaults()
    gp = GaussianProcess(x, y, tile_size=16)
    mu_gp = np.asarray(gp.predict(xt))       # populates the cache
    mu_gp2 = np.asarray(gp.predict(xt))      # served from the cache
    mu_ref = np.asarray(
        pred.predict(jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), p, 16)
    )
    np.testing.assert_allclose(mu_gp, mu_ref, atol=1e-5)
    np.testing.assert_allclose(mu_gp2, mu_ref, atol=1e-5)
