"""Ragged fleets (DESIGN.md §11): per-problem n_valid masking, bucketing,
plan reuse, ragged streaming updates, and the continuous-batching loop.

The load-bearing invariant: executing B zero-padded problems of different
sizes through ONE fused bucket program (frontiers as traced operands) is
numerically identical — within backend tolerance — to a Python loop of
single-problem programs, for every head (mean, uncertainty, NLML)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GaussianProcess, GPFleet
from repro.core import executor, mll, tiling, triangular
from repro.core import predict as pred
from repro.core import update as upd
from repro.core.kernels_math import SEKernelParams
from repro.core.scheduler import Head

M = 32
NS_MIX = (48, 64, 200)            # spans <1 tile slack, exact fit, 7 tiles
PARAMS = SEKernelParams(lengthscale=0.6, vertical=1.1, noise=0.05)


def _problems(rng, ns=NS_MIX, d=2):
    xs = [rng.standard_normal((n, d)).astype(np.float32) for n in ns]
    ys = [rng.standard_normal(n).astype(np.float32) for n in ns]
    return xs, ys


def _stack(xs, ys, cap):
    """Zero-pad to a shared capacity (the bucket contract)."""
    x = jnp.stack([jnp.pad(jnp.asarray(x), ((0, cap - x.shape[0]), (0, 0)))
                   for x in xs])
    y = jnp.stack([jnp.pad(jnp.asarray(y), (0, cap - y.shape[0])) for y in ys])
    nv = jnp.asarray([x.shape[0] for x in xs], jnp.int32)
    return x, y, nv


# ---------------------------------------------------------------------------
# The equivalence grid: ragged fused vs per-problem loop.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("n_streams", [1, None])
def test_ragged_fused_matches_per_problem_loop(rng, backend, n_streams):
    xs, ys = _problems(rng)
    cap = -(-max(NS_MIX) // M) * M
    xst, yst, nv = _stack(xs, ys, cap)
    nh = 9
    xt = rng.standard_normal((nh, 2)).astype(np.float32)
    xtb = jnp.broadcast_to(jnp.asarray(xt)[None], (len(xs), nh, 2))

    atol_m, atol_s = (3e-4, 3e-3) if backend == "jnp" else (5e-4, 5e-3)
    (mean, sigma), state = pred.predict_fused_batched(
        xst, yst, xtb, PARAMS, M, head=Head.COVARIANCE, n_streams=n_streams,
        backend=backend, with_state=True, n_valid=nv,
    )
    nlml = mll.nlml_from_state(state, yst)
    for i, (x, y) in enumerate(zip(xs, ys)):
        mr, sr = pred.predict_fused(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt), PARAMS, M,
            head=Head.COVARIANCE, n_streams=n_streams, backend=backend,
        )
        np.testing.assert_allclose(np.asarray(mean[i]), np.asarray(mr), atol=atol_m)
        np.testing.assert_allclose(np.asarray(sigma[i]), np.asarray(sr), atol=atol_s)
        st = pred.posterior_state(
            jnp.asarray(x), jnp.asarray(y), PARAMS, M,
            n_streams=n_streams, backend=backend,
        )
        ref = mll.nlml_from_state(st, jnp.asarray(y))
        np.testing.assert_allclose(
            float(nlml[i]), float(ref), rtol=2e-4, atol=5e-3
        )

    # warm path off the ragged state must mask the cross covariance too
    xt2 = rng.standard_normal((5, 2)).astype(np.float32)
    warm = pred.predict_from_state_batched(
        state, jnp.broadcast_to(jnp.asarray(xt2)[None], (len(xs), 5, 2)),
        n_streams=n_streams,
    )
    for i, (x, y) in enumerate(zip(xs, ys)):
        mr = pred.predict_fused(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt2), PARAMS, M,
            n_streams=n_streams, backend=backend,
        )
        np.testing.assert_allclose(np.asarray(warm[i]), np.asarray(mr), atol=atol_m)


def test_ragged_nt_valid_masks_test_rows(rng):
    """Per-problem test counts: rows past a problem's own n̂_i come back 0."""
    xs, ys = _problems(rng, ns=(20, 40))
    xst, yst, nv = _stack(xs, ys, 64)
    xtb = jnp.asarray(rng.standard_normal((2, 6, 2)).astype(np.float32))
    mean = pred.predict_fused_batched(
        xst, yst, xtb, PARAMS, M, n_valid=nv, nt_valid=jnp.asarray([3, 6]),
    )
    np.testing.assert_array_equal(np.asarray(mean[0, 3:]), 0.0)
    ref = pred.predict_fused(
        jnp.asarray(xs[0]), jnp.asarray(ys[0]), xtb[0, :3], PARAMS, M
    )
    np.testing.assert_allclose(np.asarray(mean[0, :3]), np.asarray(ref), atol=3e-4)


# ---------------------------------------------------------------------------
# Plan / trace reuse: one Plan per bucket geometry — never per size mix or B.
# ---------------------------------------------------------------------------


def test_ragged_plan_and_trace_reuse(rng):
    # the ragged program fn is ONE lru-cached object regardless of frontiers
    fn_a = pred._fused_program_fn(Head.NONE, None, "jnp", None, None, None, "flat")
    fn_b = pred._fused_program_fn(Head.NONE, None, "jnp", None, None, None, "flat")
    assert fn_a is fn_b

    cap = 4 * M
    xt = jnp.asarray(rng.standard_normal((2, 5, 2)).astype(np.float32))
    xs, ys = _problems(rng, ns=(40, 100))
    xst, yst, nv = _stack(xs, ys, cap)
    pred.predict_fused_batched(xst, yst, xt, PARAMS, M, n_valid=nv)
    before = executor.program_plan.cache_info()

    # same geometry, different per-problem sizes: no new plan
    xs2, ys2 = _problems(rng, ns=(17, 128))
    xst2, yst2, nv2 = _stack(xs2, ys2, cap)
    pred.predict_fused_batched(xst2, yst2, xt, PARAMS, M, n_valid=nv2)
    # same geometry, different batch width B=3: no new plan either
    xs3, ys3 = _problems(rng, ns=(33, 65, 97))
    xst3, yst3, nv3 = _stack(xs3, ys3, cap)
    xt3 = jnp.asarray(rng.standard_normal((3, 5, 2)).astype(np.float32))
    pred.predict_fused_batched(xst3, yst3, xt3, PARAMS, M, n_valid=nv3)

    after = executor.program_plan.cache_info()
    assert after.misses == before.misses, "a size mix or B change re-planned"
    assert after.hits > before.hits


# ---------------------------------------------------------------------------
# Ragged streaming updates + migration embedding.
# ---------------------------------------------------------------------------


def test_extend_state_ragged_matches_rebuild(rng):
    xs, ys = _problems(rng, ns=(30, 64, 90))
    cap = 4 * M
    xst, yst, nv = _stack(xs, ys, cap)
    env, yc = pred.nlml_program_env(xst, yst, PARAMS, M, n_valid=nv)
    state = pred.PosteriorState(
        lpacked=env["packed"], alpha=env["alpha"],
        x_chunks=tiling.pad_features(xst, M), n=cap, m=M, params=PARAMS,
        beta=env["y"], y_chunks=yc, n_valid=nv,
    )
    counts = np.array([5, 0, 33])
    b_max = counts.max()
    xn = [rng.standard_normal((c, 2)).astype(np.float32) for c in counts]
    yn = [rng.standard_normal(c).astype(np.float32) for c in counts]
    xa = jnp.stack([jnp.pad(jnp.asarray(x), ((0, b_max - len(x)), (0, 0)))
                    for x in xn])
    ya = jnp.stack([jnp.pad(jnp.asarray(y), (0, b_max - len(y))) for y in yn])
    new = upd.extend_state_ragged(state, xa, ya, counts)
    assert np.array_equal(np.asarray(new.n_valid), np.asarray(nv) + counts)

    xt = rng.standard_normal((7, 2)).astype(np.float32)
    warm = pred.predict_from_state_batched(
        new, jnp.broadcast_to(jnp.asarray(xt)[None], (3, 7, 2))
    )
    for i in range(3):
        x2 = np.concatenate([xs[i], xn[i]])
        y2 = np.concatenate([ys[i], yn[i]])
        ref = pred.predict_fused(
            jnp.asarray(x2), jnp.asarray(y2), jnp.asarray(xt), PARAMS, M
        )
        np.testing.assert_allclose(np.asarray(warm[i]), np.asarray(ref), atol=1e-3)

    # outgrowing the capacity is a migration — rejected here, GPFleet's job
    wide = cap - 90 + 1
    with pytest.raises(ValueError, match="migrate"):
        upd.extend_state_ragged(
            state,
            jnp.zeros((3, wide, 2)),
            jnp.zeros((3, wide)),
            np.array([0, 0, wide]),
        )


def test_embed_packed_is_blockdiag_identity(rng):
    """Migration re-embed: factor at the larger geometry == blockdiag(L, I)."""
    n, m = 48, 16
    a = rng.standard_normal((n, n)).astype(np.float32)
    k = a @ a.T + n * np.eye(n, dtype=np.float32)
    from repro.core import cholesky as chol

    lp = chol.tiled_cholesky(tiling.pack_lower(jnp.asarray(k), m))
    lp_big = tiling.embed_packed(lp, 3, 5)
    kpad = np.eye(80, dtype=np.float32)
    kpad[:n, :n] = k
    ref = chol.tiled_cholesky(tiling.pack_lower(jnp.asarray(kpad), m))
    np.testing.assert_allclose(np.asarray(lp_big), np.asarray(ref), atol=1e-5)
    # logdet of the embedded factor is unchanged (identity padding)
    np.testing.assert_allclose(
        float(triangular.logdet_from_factor(lp_big, 5, n_valid=n)),
        float(triangular.logdet_from_factor(lp, 3)),
        rtol=1e-6,
    )


# ---------------------------------------------------------------------------
# GPFleet: bucketed front-end, migration on update.
# ---------------------------------------------------------------------------


def test_gpfleet_matches_single_gps(rng):
    xs, ys = _problems(rng, ns=(48, 64, 200, 17))
    fleet = GPFleet(xs, ys, params=PARAMS, tile_size=M)
    assert fleet.bucket_assignment() == {1: [3], 2: [0, 1], 8: [2]}
    xt = rng.standard_normal((9, 2)).astype(np.float32)
    mean, var = fleet.predict_with_uncertainty(xt)
    nlml = fleet.nlml()
    tests = [rng.standard_normal((k, 2)).astype(np.float32) for k in (3, 0, 7, 1)]
    each = fleet.predict_each(tests)
    for i, (x, y) in enumerate(zip(xs, ys)):
        g = GaussianProcess(x, y, params=PARAMS, tile_size=M)
        mr, vr = g.predict_with_uncertainty(xt)
        np.testing.assert_allclose(np.asarray(mean[i]), np.asarray(mr), atol=3e-4)
        np.testing.assert_allclose(np.asarray(var[i]), np.asarray(vr), atol=3e-3)
        ref = mll.nlml_from_state(g.posterior(), jnp.asarray(y))
        np.testing.assert_allclose(float(nlml[i]), float(ref), rtol=2e-4, atol=5e-3)
        assert each[i].shape == (tests[i].shape[0],)
        if tests[i].shape[0]:
            np.testing.assert_allclose(
                np.asarray(each[i]), np.asarray(g.predict(tests[i])), atol=3e-4
            )


def test_gpfleet_bucket_migration_on_update(rng):
    xs, ys = _problems(rng, ns=(40, 60, 120))
    fleet = GPFleet(xs, ys, params=PARAMS, tile_size=M)
    xt = rng.standard_normal((6, 2)).astype(np.float32)
    fleet.predict(xt)                         # warm every bucket
    assert fleet.bucket_assignment() == {2: [0, 1], 4: [2]}

    # problem 1 crosses 64 -> cap 4; problem 2 crosses 128 -> cap 8
    xn = [np.zeros((0, 2), np.float32),
          rng.standard_normal((30, 2)).astype(np.float32),
          rng.standard_normal((20, 2)).astype(np.float32)]
    yn = [np.zeros((0,), np.float32),
          rng.standard_normal(30).astype(np.float32),
          rng.standard_normal(20).astype(np.float32)]
    fleet.update(xn, yn)
    assert fleet.bucket_assignment() == {2: [0], 4: [1], 8: [2]}
    # migration kept every bucket warm — no cold re-factorization pending
    assert all(rec.state is not None for rec in fleet._buckets.values())

    mean = fleet.predict(xt)
    for i in range(3):
        x2 = np.concatenate([xs[i], xn[i]])
        y2 = np.concatenate([ys[i], yn[i]])
        g = GaussianProcess(x2, y2, params=PARAMS, tile_size=M)
        np.testing.assert_allclose(
            np.asarray(mean[i]), np.asarray(g.predict(xt)), atol=1e-3
        )


def test_gpfleet_validation(rng):
    xs, ys = _problems(rng, ns=(20, 30))
    with pytest.raises(ValueError, match="equal-length"):
        GPFleet(xs, ys[:1])
    with pytest.raises(ValueError, match="share D"):
        GPFleet([xs[0], rng.standard_normal((30, 3))], ys)
    with pytest.raises(ValueError, match="per-problem"):
        GPFleet(xs, ys, params=SEKernelParams(jnp.ones(3), 1.0, 0.1))
    fleet = GPFleet(xs, ys, params=PARAMS, tile_size=M)
    with pytest.raises(ValueError, match="one test set per problem"):
        fleet.predict_each([xs[0]])
    with pytest.raises(ValueError, match="one arrival block per problem"):
        fleet.update([xs[0]], [ys[0]])


# ---------------------------------------------------------------------------
# Bucketing invariance: boundaries change cost, never results.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("boundaries", [1, 2, "pow2", (2, 8)])
def test_bucketing_never_changes_results(rng, boundaries):
    xs, ys = _problems(rng, ns=(18, 48, 70, 200))
    xt = rng.standard_normal((5, 2)).astype(np.float32)
    base = GPFleet(xs, ys, params=PARAMS, tile_size=M, boundaries="pow2")
    got = GPFleet(xs, ys, params=PARAMS, tile_size=M, boundaries=boundaries)
    np.testing.assert_allclose(
        np.asarray(got.predict(xt)), np.asarray(base.predict(xt)), atol=5e-4
    )
    np.testing.assert_allclose(
        np.asarray(got.nlml()), np.asarray(base.nlml()), rtol=2e-4, atol=5e-3
    )


def test_bucket_boundaries_and_assignment():
    assert tiling.bucket_boundaries(8, "pow2") == (1, 2, 4, 8)
    assert tiling.bucket_boundaries(5, "pow2") == (1, 2, 4, 8)
    assert tiling.bucket_boundaries(9, 1) == (9,)
    assert tiling.bucket_boundaries(16, (2, 8)) == (2, 8, 16)
    assign = tiling.bucket_problems((10, 33, 64, 65, 256), 32, "pow2")
    assert assign == {1: [0], 2: [1, 2], 4: [3], 8: [4]}
    with pytest.raises(ValueError):
        tiling.bucket_problems((0,), 32, "pow2")


# ---------------------------------------------------------------------------
# Continuous-batching serving loop.
# ---------------------------------------------------------------------------


def test_continuous_batcher_waves(rng):
    from repro.serve import ContinuousBatcher

    xs, ys = _problems(rng, ns=(40, 60))
    fleet = GPFleet(xs, ys, params=PARAMS, tile_size=M)
    ticks = iter(range(1000))
    srv = ContinuousBatcher(fleet, clock=lambda: float(next(ticks)))

    xt = rng.standard_normal((4, 2)).astype(np.float32)
    r1 = srv.submit_predict(0, xt)
    r2 = srv.submit_predict(0, xt[:2], uncertainty=True)
    xo = rng.standard_normal((30, 2)).astype(np.float32)
    yo = rng.standard_normal(30).astype(np.float32)
    r3 = srv.submit_observe(1, xo, yo)
    assert srv.pending == 3
    stats = srv.step()
    assert srv.pending == 0
    assert (stats.n_predict, stats.n_observe, stats.points_absorbed) == (2, 1, 30)
    assert stats.migrations == 1                  # 60 + 30 crosses cap 2 -> 4
    assert fleet.bucket_assignment() == {2: [0], 4: [1]}

    # observations land before predictions inside a wave; both requests on
    # problem 0 share one launch and slice their own rows back out
    g0 = GaussianProcess(xs[0], ys[0], params=PARAMS, tile_size=M)
    np.testing.assert_allclose(srv.result(r1), np.asarray(g0.predict(xt)), atol=3e-4)
    m2, v2 = srv.result(r2)
    np.testing.assert_allclose(m2, np.asarray(g0.predict(xt[:2])), atol=3e-4)
    assert (v2 > 0).all()
    assert srv.result(r3) == 30
    with pytest.raises(KeyError):
        srv.result(r3)                            # results pop exactly once

    s = srv.summary()
    assert s["requests"] == 3.0 and s["waves"] == 1.0
    assert s["p99_ms"] >= s["p50_ms"] >= 0.0

    # the post-update state answers like a fresh GP on the grown problem
    rid = srv.submit_predict(1, xt)
    srv.run_until_idle()
    g1 = GaussianProcess(
        np.concatenate([xs[1], xo]), np.concatenate([ys[1], yo]),
        params=PARAMS, tile_size=M,
    )
    np.testing.assert_allclose(srv.result(rid), np.asarray(g1.predict(xt)), atol=1e-3)


def test_continuous_batcher_dispatch_overlap_ordering(rng):
    """Waves dispatch without blocking; results arrive one wave late but
    are computed against the state snapshot at dispatch time."""
    from repro.serve import ContinuousBatcher

    xs, ys = _problems(rng, ns=(40, 60))
    fleet = GPFleet(xs, ys, params=PARAMS, tile_size=M)
    srv = ContinuousBatcher(fleet)
    xt = rng.standard_normal((4, 2)).astype(np.float32)

    # wave 0: predict against the initial state
    r0 = srv.submit_predict(0, xt)
    s0 = srv.step()
    assert s0.n_predict == 1
    assert srv._inflight is not None          # dispatched, NOT fetched
    assert r0 not in srv._done

    # wave 1: observe problem 0, predict again.  Entering step() flushes
    # wave 0 FIRST, so r0 must reflect the pre-observation snapshot even
    # though its result is fetched after the update was enqueued.
    xo = rng.standard_normal((8, 2)).astype(np.float32)
    yo = rng.standard_normal(8).astype(np.float32)
    srv.submit_observe(0, xo, yo)
    r1 = srv.submit_predict(0, xt)
    srv.step()
    assert r0 in srv._done                    # one wave late, now finished
    assert r1 not in srv._done

    g_before = GaussianProcess(xs[0], ys[0], params=PARAMS, tile_size=M)
    np.testing.assert_allclose(
        srv.result(r0), np.asarray(g_before.predict(xt)), atol=3e-4
    )
    # flush() via result() materializes the in-flight wave 1: r1 sees the
    # post-observation state — wave N predictions see waves 0..N observes
    g_after = GaussianProcess(
        np.concatenate([xs[0], xo]), np.concatenate([ys[0], yo]),
        params=PARAMS, tile_size=M,
    )
    np.testing.assert_allclose(
        srv.result(r1), np.asarray(g_after.predict(xt)), atol=1e-3
    )
    assert srv._inflight is None
    assert srv.flush() == 0                   # idempotent when drained


try:
    from hypothesis import given, settings, strategies as st

    @given(
        seed=st.integers(0, 2**31 - 1),
        sizes=st.lists(st.integers(1, 80), min_size=1, max_size=5),
        k=st.sampled_from([1, 2, 3, "pow2"]),
    )
    @settings(max_examples=10, deadline=None)
    def test_bucketing_invariance_property(seed, sizes, k):
        rng = np.random.default_rng(seed)
        xs, ys = _problems(rng, ns=tuple(sizes), d=1)
        xt = rng.standard_normal((3, 1)).astype(np.float32)
        a = GPFleet(xs, ys, params=PARAMS, tile_size=16, boundaries="pow2")
        b = GPFleet(xs, ys, params=PARAMS, tile_size=16, boundaries=k)
        np.testing.assert_allclose(
            np.asarray(a.predict(xt)), np.asarray(b.predict(xt)), atol=5e-4
        )
except ImportError:  # pragma: no cover - hypothesis absent in minimal envs
    pass
