"""GPFleet's cold round (DESIGN.md §11): one fused ragged program per bucket.

A small ARBO-shaped fleet — Matérn-5/2-ARD with per-problem (B, 3)
lengthscales, ragged sizes and ragged test counts — answered cold through
the fused program, warm off the cached states, and by a plain float64 dense
posterior per problem (a CPU-size copy of the benchmark reference's
arithmetic).  A bucket split into width-W launches equals the unsplit one and is written
into one stacked copy;
the launch counters read one launch per geometry, or ceil(B / W).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import gp as gp_mod
from repro.core import kernels_math as km
from repro.core.gp import GPFleet

M = 16
SIZES = (5, 9, 16, 17, 23, 31, 33, 40, 52, 64, 65, 70)   # caps 1, 2, 4, 8
TESTS = (3, 0, 7, 1, 5, 5, 2, 8, 4, 6, 1, 3)
KERNEL = km.ARDMatern52(ndim=3)


def _inputs(rng, n):
    """(parallelism, input scale, cluster load) rows, as ARBO's histories."""
    return np.stack([rng.integers(1, 65, n), rng.uniform(0.5, 4.0, n),
                     rng.uniform(0.0, 100.0, n)], -1).astype(np.float32)


@pytest.fixture
def fleet_data(rng):
    xs = [_inputs(rng, n) for n in SIZES]
    ys = [np.sin(x[:, 0] / 8.0).astype(np.float32) + 0.1 * x[:, 1] for x in xs]
    b = len(SIZES)
    params = km.ARDKernelParams(
        jnp.asarray(np.asarray([100.0, 1.0, 100.0]) * np.exp(rng.uniform(-0.5, 0.5, (b, 3))),
                    jnp.float32),
        jnp.asarray(np.exp(rng.uniform(-0.5, 0.5, b)), jnp.float32),
        jnp.asarray(0.03 * np.exp(rng.uniform(-1.0, 1.0, b)), jnp.float32),
    )
    tests = [_inputs(rng, k) for k in TESTS]
    return xs, ys, params, tests


def _fleet(xs, ys, params):
    return GPFleet(xs, ys, params=params, tile_size=M, kernel=KERNEL)


def _reference(x, y, xt, ls, v, s2):
    """Dense float64 posterior mean and covariance of v Matérn-5/2-ARD + s2."""
    def k(a, b):
        r2 = (((a[:, None] - b[None]) ** 2) / ls).sum(-1)
        s = np.sqrt(5.0 * r2)
        return v * (1.0 + s + s * s / 3.0) * np.exp(-s)

    x, xt = np.asarray(x, np.float64), np.asarray(xt, np.float64)
    kxx = k(x, x) + s2 * np.eye(len(x))
    kst = k(xt, x)
    return kst @ np.linalg.solve(kxx, y), k(xt, xt) - kst @ np.linalg.solve(kxx, kst.T)


def _params_of(params, i):
    return (np.asarray(params.lengthscales[i], np.float64), float(params.vertical[i]),
            float(params.noise[i]))


def _close(a, b, atol):
    for (ma, ca), (mb, cb) in zip(a, b):
        np.testing.assert_allclose(np.asarray(ma), np.asarray(mb), rtol=0, atol=atol)
        np.testing.assert_allclose(np.asarray(ca), np.asarray(cb), rtol=0, atol=atol)


def test_cold_round_equals_warm_and_reference(fleet_data):
    xs, ys, params, tests = fleet_data
    fleet = _fleet(xs, ys, params)
    assert sorted(fleet.bucket_assignment()) == [1, 2, 4, 8]
    cold = fleet.predict_each(tests, full_cov=True)
    assert all(rec.state is not None for rec in fleet._buckets.values())
    warm = fleet.predict_each(tests, full_cov=True)
    _close(cold, warm, 1e-4)  # f32: the warm tail orders the solves differently
    for i, (mean, cov) in enumerate(cold):
        assert mean.shape == (TESTS[i],) and cov.shape == (TESTS[i], TESTS[i])
        ref_mean, ref_cov = _reference(xs[i], ys[i], tests[i], *_params_of(params, i))
        np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=0, atol=2e-4)
        np.testing.assert_allclose(np.asarray(cov), ref_cov, rtol=0, atol=2e-4)
    # the mean-only head: new hyperparameters, cold again
    fleet.params = km.ARDKernelParams(params.lengthscales * 1.1, params.vertical, params.noise)
    means = fleet.predict_each(tests)
    for i, mean in enumerate(means):
        ls, v, s2 = _params_of(params, i)
        ref_mean, _ = _reference(xs[i], ys[i], tests[i], ls * 1.1, v, s2)
        np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=0, atol=2e-4)


def test_shared_block_cold_equals_warm(fleet_data):
    xs, ys, params, tests = fleet_data
    fleet = _fleet(xs, ys, params)
    cold = fleet.predict_full_cov(tests[2])
    warm = fleet.predict_full_cov(tests[2])
    _close([cold], [warm], 1e-4)


def test_width_split_equals_unsplit(fleet_data, monkeypatch):
    xs, ys, params, tests = fleet_data
    whole = _fleet(xs, ys, params)
    whole_cold = whole.predict_each(tests, full_cov=True)
    monkeypatch.setattr(gp_mod, "_cold_launch_width", lambda *a: 2)
    split = _fleet(xs, ys, params)
    split_cold = split.predict_each(tests, full_cov=True)
    _close(split_cold, whole_cold, 1e-6)
    # the launches were written back into one copy of each bucket's rows
    for cap, rec in whole._buckets.items():
        other = split._buckets[cap].state
        assert other.lpacked.shape == rec.state.lpacked.shape
        np.testing.assert_array_equal(np.asarray(other.n_valid), np.asarray(rec.state.n_valid))
    _close(split.predict_each(tests, full_cov=True), whole.predict_each(tests, full_cov=True),
           1e-6)
    np.testing.assert_allclose(np.asarray(split.nlml()), np.asarray(whole.nlml()), atol=1e-5)


def test_split_launches_write_into_one_copy(fleet_data, monkeypatch):
    xs, ys, params, tests = fleet_data
    monkeypatch.setattr(gp_mod, "_cold_launch_width", lambda *a: 2)
    written = []
    write = gp_mod._write_rows

    def recording(buf, part, start, rows):
        written.append((jax.tree_util.tree_leaves(buf), start, rows))
        return write(buf, part, start, rows)

    monkeypatch.setattr(gp_mod, "_write_rows", recording)
    fleet = _fleet(xs, ys, params)
    fleet.predict_each(tests, full_cov=True)
    buckets = fleet.bucket_assignment()
    # every launch of a split bucket lands in the bucket's one stacked copy,
    # which each write takes over (donates) rather than copies
    assert len(written) == sum(math.ceil(len(idx) / 2) for idx in buckets.values()
                               if len(idx) > 2)
    assert all(leaf.is_deleted() for leaves, _, _ in written for leaf in leaves)
    for cap, idx in buckets.items():
        assert fleet._buckets[cap].state.lpacked.shape[0] == len(idx)
    assert [rows for _, _, rows in written if rows != 2] == [
        len(idx) % 2 for idx in buckets.values() if len(idx) > 2 and len(idx) % 2]


@pytest.mark.parametrize("width", [None, 3])
def test_launch_counters(fleet_data, monkeypatch, width):
    xs, ys, params, tests = fleet_data
    if width is not None:
        monkeypatch.setattr(gp_mod, "_cold_launch_width", lambda *a: width)
    fleet = _fleet(xs, ys, params)
    buckets = fleet.bucket_assignment()
    launches = {cap: 1 if width is None else math.ceil(len(idx) / width)
                for cap, idx in buckets.items()}
    widths = {cap: len(idx) if width is None else min(width, len(idx))
              for cap, idx in buckets.items()}
    obs.reset()
    obs.enable()
    try:
        fleet.predict_each(tests, full_cov=True)
        cold = obs.snapshot()
        fleet.predict_each(tests, full_cov=True)
        warm = obs.snapshot()
    finally:
        obs.disable()
        obs.reset()
    c = cold["counters"]
    assert c["fleet.cold.launches"] == sum(launches.values())
    assert c["fleet.cold.masked_problems"] == sum(
        launches[cap] * widths[cap] - len(idx) for cap, idx in buckets.items())
    assert c["fleet.cold.rows_valid"] == sum(SIZES)
    assert c["fleet.cold.rows_padded"] == sum(
        launches[cap] * widths[cap] * cap * M for cap in buckets)
    assert c["predict.head.covariance"] == sum(launches.values())
    assert "predict.warm_tail_batched" not in c   # no op-by-op tail in a cold round
    assert cold["histograms"]["fleet.cold.launch_width"]["count"] == sum(launches.values())
    # a warm round launches nothing cold and runs the warm tail per bucket
    assert warm["counters"]["fleet.cold.launches"] == c["fleet.cold.launches"]
    assert warm["counters"]["predict.warm_tail_batched"] == len(buckets)


def test_stacks_kept_across_hyperparameters(fleet_data, monkeypatch):
    xs, ys, params, tests = fleet_data
    calls = []
    orig = GPFleet._stack

    def counted(self, idx, cap):
        calls.append(cap)
        return orig(self, idx, cap)

    monkeypatch.setattr(GPFleet, "_stack", counted)
    fleet = _fleet(xs, ys, params)
    fleet.predict_each(tests)
    assert sorted(calls) == sorted(fleet.bucket_assignment())
    fleet.params = km.ARDKernelParams(params.lengthscales, params.vertical * 1.2, params.noise)
    fleet.predict_each(tests)
    assert len(calls) == len(fleet.bucket_assignment())    # re-factored, not re-stacked
    fleet.update([np.zeros((0, 3), np.float32)] * (len(SIZES) - 1) + [xs[0][:2]],
                 [np.zeros((0,), np.float32)] * (len(SIZES) - 1) + [ys[0][:2]])
    fleet.invalidate_cache()
    fleet.predict_each(tests)
    assert len(calls) == 2 * len(fleet.bucket_assignment())  # new data: stacked again
