"""repro.obs (DESIGN.md §15): registry semantics, zero-cost toggling,
JSONL/Prometheus export, executor wave-trace events, factorization-health
counters, the jitter-retry recovery, and the NLML drift monitor — including
the serving loop's automatic off-hot-path re-optimize.
"""

import importlib
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import GaussianProcess, GPFleet
from repro.core import executor, lowrank
from repro.core import predict as pred
from repro.core import update as upd
from repro.core.kernels_math import SEKernelParams
from repro.serve import ContinuousBatcher

PARAMS = SEKernelParams(lengthscale=0.6, vertical=1.1, noise=0.05)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts disabled and empty, and leaves no global state."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# -- registry semantics ------------------------------------------------------


def test_counter_gauge_semantics():
    obs.enable()
    obs.inc("a")
    obs.inc("a", 4)
    snap = obs.snapshot()
    assert snap["counters"]["a"] == 5.0


def test_disabled_helpers_record_nothing():
    obs.inc("a")
    obs.observe("h", 1.0)
    obs.event("e", x=1)
    obs.health_event("boom")
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    assert snap["events"] == []
    # re-enable: recording resumes on the same registry
    obs.enable()
    obs.inc("a")
    assert obs.snapshot()["counters"]["a"] == 1.0


def test_histogram_percentiles_tiny_samples():
    h = obs.Histogram(obs.DEFAULT_EDGES)
    assert math.isnan(h.percentile(50))  # empty -> NaN, not garbage
    h.observe(3.0)
    # a single sample is every percentile (clamped to [min, max])
    assert h.percentile(0) == h.percentile(50) == h.percentile(99) == 3.0
    h.observe(5.0)
    h.observe(100.0)
    qs = [h.percentile(q) for q in (1, 25, 50, 75, 99)]
    assert qs == sorted(qs)  # monotone in q
    assert qs[0] >= 3.0 and qs[-1] <= 100.0  # clamped to observed range


def test_histogram_overflow_bucket_and_sum():
    h = obs.Histogram(edges=(1.0, 2.0))
    for v in (0.5, 1.5, 99.0):
        h.observe(v)
    assert h.counts == [1, 1, 1]  # last is the implicit +inf bucket
    assert h.sum == pytest.approx(101.0) and h.count == 3
    assert h.percentile(99) <= 99.0


def test_event_ring_buffer_caps_memory():
    obs.enable()
    for i in range(obs.MAX_EVENTS + 10):
        obs.event("e", i=i)
    events = obs.registry().events
    assert len(events) == obs.MAX_EVENTS
    assert events[0]["i"] == 10  # oldest dropped


# -- export round-trips ------------------------------------------------------


def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "m.jsonl"
    obs.enable(str(path))
    obs.event("alpha", v=1)
    obs.event("beta", v=[1, 2])
    obs.disable()  # closes the sink
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["alpha", "beta"]
    assert all("ts" in r for r in recs)
    assert recs[1]["v"] == [1, 2]


def test_to_json_and_prometheus():
    obs.enable()
    obs.inc("serve.requests", 3)
    obs.observe("lat_ms", 2.0, edges=(1.0, 4.0))
    parsed = json.loads(obs.to_json())
    assert parsed["counters"]["serve.requests"] == 3.0
    prom = obs.to_prometheus()
    assert "# TYPE repro_serve_requests counter" in prom
    assert "repro_serve_requests 3" in prom
    # histogram exposition: cumulative buckets + +Inf + sum/count
    assert 'repro_lat_ms_bucket{le="4"} 1' in prom
    assert 'repro_lat_ms_bucket{le="+Inf"} 1' in prom
    assert "repro_lat_ms_count 1" in prom


# -- executor wave traces ----------------------------------------------------


def test_plan_wave_stats_shape():
    plan = executor.program_plan(4, 1, False, 2)
    st = executor.plan_wave_stats(plan)
    assert st["plan"] == "program" and st["n_streams"] == 2
    assert st["tasks"] == st["bulk_tasks"] + st["pool_tasks"]
    assert 0.0 < st["occupancy"] <= 1.0
    assert sum(st["by_op"].values()) == st["tasks"]
    assert executor.plan_wave_stats(plan) is st  # memoized per Plan


def test_fused_predict_emits_wave_event(rng):
    x = rng.standard_normal((40, 2)).astype(np.float32)
    y = rng.standard_normal(40).astype(np.float32)
    gp = GaussianProcess(x, y, params=PARAMS, tile_size=16)
    obs.enable()
    gp.predict(x[:4])
    snap = obs.snapshot()
    assert snap["counters"]["executor.dispatch.run_program"] == 1.0
    assert snap["counters"]["cache.posterior.cold"] == 1.0
    waves = [e for e in snap["events"] if e["kind"] == "executor.wave"]
    assert len(waves) == 1
    ev = waves[0]
    assert ev["dispatch"] == "run_program" and ev["plan"] == "program"
    assert ev["launches"] > 0 and ev["tasks"] > 0
    # second predict: warm tail, NO new program dispatch
    gp.predict(x[:4])
    snap = obs.snapshot()
    assert snap["counters"]["executor.dispatch.run_program"] == 1.0
    assert snap["counters"]["predict.warm_tail"] == 1.0
    assert snap["counters"]["cache.posterior.warm"] == 1.0


def test_update_append_counts_dispatches(rng):
    x = rng.standard_normal((32, 2)).astype(np.float32)
    y = rng.standard_normal(32).astype(np.float32)
    gp = GaussianProcess(x, y, params=PARAMS, tile_size=16)
    gp.posterior()
    obs.enable()
    gp.update(rng.standard_normal((16, 2)).astype(np.float32),
              rng.standard_normal(16).astype(np.float32))
    c = obs.snapshot()["counters"]
    assert c.get("executor.dispatch.run_append", 0) >= 1


def test_cache_stats_reports_plan_caches():
    executor.program_plan(4, 1, False, 2)
    stats = obs.cache_stats()
    assert "executor.program_plan" in stats
    st = stats["executor.program_plan"]
    assert set(st) == {"hits", "misses", "size"} and st["size"] >= 1
    before = st["hits"]
    executor.program_plan(4, 1, False, 2)  # lru hit
    assert obs.cache_stats()["executor.program_plan"]["hits"] == before + 1


# -- front-end spans ---------------------------------------------------------


class _SpanLog:
    """Stands in for the profiler's TraceAnnotation: (depth, name) as opened."""

    def __init__(self):
        self.opened, self._depth = [], 0

    def __call__(self, name):
        log = self

        class _Span:
            def __enter__(self):
                log.opened.append((log._depth, name))
                log._depth += 1

            def __exit__(self, *exc):
                log._depth -= 1

        return _Span()


@pytest.fixture
def span_log(monkeypatch):
    log = _SpanLog()
    monkeypatch.setattr(importlib.import_module("repro.obs.tracer"), "_TraceAnnotation", log)
    obs.enable()
    return log


def test_gp_predict_spans_nest_under_one_predict(rng, span_log):
    x = rng.standard_normal((40, 2)).astype(np.float32)
    y = rng.standard_normal(40).astype(np.float32)
    xt = rng.standard_normal((20, 2)).astype(np.float32)
    gp = GaussianProcess(x, y, params=PARAMS, tile_size=16)
    gp.predict_with_uncertainty(xt)  # cold: the fused program
    assert span_log.opened == [
        (0, "repro.gp.predict"), (1, "repro.gp.lookup"), (1, "repro.predict.pad"),
        (1, "repro.predict.fused"), (1, "repro.predict.untile"), (1, "repro.gp.diag"),
    ]
    span_log.opened.clear()
    gp.predict(xt)  # warm: the tail off the cached factor
    assert span_log.opened == [
        (0, "repro.gp.predict"), (1, "repro.gp.lookup"), (1, "repro.predict.pad"),
    ]


def test_fleet_predict_spans_stack_and_bucket(rng, span_log):
    fleet = _fleet(rng)
    fleet.predict_with_uncertainty(rng.uniform(size=(5, 1)).astype(np.float32))
    names = [name for _, name in span_log.opened]
    assert [d for d, _ in span_log.opened].count(0) == 1
    assert span_log.opened[0] == (0, "repro.gp.predict")
    assert names[-1] == "repro.gp.diag"
    buckets = len(fleet.bucket_assignment())
    assert names.count("repro.gp.bucket") == buckets
    # each cold bucket stacks its problems, then its test block
    assert names.count("repro.gp.stack") == 2 * buckets
    assert names.count("repro.predict.nlml_program") == buckets


# -- factorization health ----------------------------------------------------


def test_refactorize_fallback_counter(rng, monkeypatch):
    x = rng.standard_normal((32, 2)).astype(np.float32)
    y = rng.standard_normal(32).astype(np.float32)
    gp = GaussianProcess(x, y, params=PARAMS, tile_size=16)
    gp.posterior()

    def boom(self, *a, **k):
        raise upd.CholeskyUpdateError("forced")

    monkeypatch.setattr(pred.PosteriorState, "extend", boom)
    obs.enable()
    gp.update(rng.standard_normal((8, 2)).astype(np.float32),
              rng.standard_normal(8).astype(np.float32))
    snap = obs.snapshot()
    assert snap["counters"]["health.refactorize_fallback"] == 1.0
    ev = [e for e in snap["events"] if e["kind"] == "health.refactorize_fallback"]
    assert ev and ev[0]["site"] == "gp.update"
    assert gp._posterior is None  # contract unchanged: cache invalidated


def test_nan_guard_trip_counter():
    obs.enable()
    with pytest.raises(upd.CholeskyUpdateError):
        upd._check((jnp.asarray([np.nan]),), "append")
    c = obs.snapshot()["counters"]
    assert c["health.nan_guard_trip"] == 1.0


def test_lowrank_jitter_retry_recovers(rng):
    # duplicate inducing rows + zero jitter: K_uu is exactly singular, the
    # cold factorization NaNs, and the escalating-jitter retry must recover
    x = np.repeat(rng.standard_normal((4, 2)), 8, axis=0).astype(np.float32)
    y = rng.standard_normal(32).astype(np.float32)
    ind = np.repeat(x[:1], 8, axis=0)  # 8 identical inducing points
    obs.enable()
    gp = GaussianProcess(
        x, y, params=PARAMS, tile_size=16, method="lowrank",
        m_inducing=8, inducing=ind, jitter=0.0,
    )
    mean = np.asarray(gp.predict(x[:4]))
    assert np.isfinite(mean).all()
    c = obs.snapshot()["counters"]
    assert c["health.lowrank_jitter_retry"] >= 1.0
    assert c["cache.lowrank.cold"] == 1.0


# -- zero-cost-when-off ------------------------------------------------------


def test_disabled_obs_is_bitwise_invisible(rng):
    x = rng.standard_normal((48, 2)).astype(np.float32)
    y = rng.standard_normal(48).astype(np.float32)
    xt = rng.standard_normal((8, 2)).astype(np.float32)

    def run():
        gp = GaussianProcess(x, y, params=PARAMS, tile_size=16)
        return np.asarray(gp.predict(xt))

    base = run()
    obs.enable()
    on = run()
    obs.disable()
    off = run()
    assert np.array_equal(base, on) and np.array_equal(base, off)
    # disable stops recording but keeps the data (export still works) ...
    c = obs.snapshot()["counters"]
    assert c["cache.posterior.cold"] == 1.0  # only the enabled run recorded
    # ... and reset wipes it without touching the flag
    obs.reset()
    assert obs.snapshot()["counters"] == {}


# -- drift monitor -----------------------------------------------------------


def test_drift_monitor_stationary_never_triggers():
    rng = np.random.default_rng(0)
    mon = obs.DriftMonitor(alpha=0.3, threshold=0.05, warmup=3, cooldown=8)
    assert not any(mon.observe(1.0 + 0.01 * rng.standard_normal())
                   for _ in range(200))
    assert mon.triggers == 0
    assert mon.level == pytest.approx(1.0, abs=0.05)


def test_drift_monitor_rising_triggers_once():
    mon = obs.DriftMonitor(alpha=0.5, threshold=0.05, warmup=2, cooldown=10 ** 6)
    fired = [i for i in range(50) if mon.observe(1.0 + 0.2 * i)]
    assert len(fired) == 1 and mon.triggers == 1  # cooldown gates repeats
    mon.reset()
    assert mon.level is None and mon.triggers == 1  # lifetime stat survives


def test_drift_monitor_ignores_nan_and_respects_warmup():
    mon = obs.DriftMonitor(alpha=0.5, threshold=0.01, warmup=5, cooldown=0)
    assert mon.observe(float("nan")) is False
    assert mon.level is None  # NaN never becomes the level
    assert not any(mon.observe(1.0 + i) for i in range(4))  # inside warmup


# -- serving loop ------------------------------------------------------------


def _fleet(rng, ns=(20, 33, 50)):
    xs = [rng.uniform(size=(n, 1)).astype(np.float32) for n in ns]
    ys = [np.sin(6 * x[:, 0]).astype(np.float32) for x in xs]
    return GPFleet(xs, ys, tile_size=16)


def test_summary_empty_and_single_request_nan_safe(rng):
    srv = ContinuousBatcher(_fleet(rng))
    s = srv.summary()
    assert s["requests"] == 0.0
    for k in ("p50_ms", "p99_ms", "max_ms", "req_per_s"):
        assert math.isfinite(s[k]) and s[k] >= 0.0
    srv.submit_predict(0, rng.uniform(size=(3, 1)))
    srv.step()
    srv.flush()
    s = srv.summary()
    assert s["requests"] == 1.0
    assert math.isfinite(s["p99_ms"])
    assert s["max_ms"] >= s["p99_ms"] >= s["p50_ms"] > 0.0


def test_serve_wave_metrics_and_events(rng):
    srv = ContinuousBatcher(_fleet(rng))
    obs.enable()
    for i in range(3):
        srv.submit_predict(i, rng.uniform(size=(4, 1)))
    srv.submit_observe(0, rng.uniform(size=(3, 1)), rng.standard_normal(3))
    srv.step()
    srv.flush()
    ev = [e for e in obs.registry().events if e["kind"] == "serve.wave"]
    assert len(ev) == 1
    assert ev[0]["n_predict"] == 3 and ev[0]["n_observe"] == 1
    assert 0.0 < ev[0]["bucket_occupancy"] <= 1.0
    assert 0.0 <= ev[0]["padded_flop_waste"] < 1.0
    snap = srv.metrics_snapshot()
    assert snap["counters"]["serve.waves"] == 1.0
    assert snap["counters"]["serve.points_absorbed"] == 3.0
    assert snap["histograms"]["serve.queue_depth"]["count"] == 1
    # private registry works with global telemetry OFF too
    obs.disable()
    srv.submit_predict(0, rng.uniform(size=(2, 1)))
    srv.step()
    assert srv.metrics_snapshot()["counters"]["serve.waves"] == 2.0
    assert len([e for e in obs.registry().events
                if e["kind"] == "serve.wave"]) == 1


def test_drift_triggers_exactly_one_reoptimize(rng):
    fleet = _fleet(rng)
    mon = obs.DriftMonitor(alpha=0.5, threshold=0.02, warmup=1, cooldown=10 ** 6)
    calls = []
    srv = ContinuousBatcher(
        fleet, drift_monitor=mon, reoptimize=lambda: calls.append(1)
    )
    reopt_waves = 0
    for w in range(6):
        # drifting targets: the per-point NLML trend rises wave over wave
        for i in range(3):
            srv.submit_observe(
                i, rng.uniform(size=(2, 1)),
                np.full(2, 3.0 * w, np.float32),
            )
        reopt_waves += srv.step().reoptimized
    srv.flush()
    assert len(calls) == 1  # exactly one re-optimize (cooldown holds)
    assert reopt_waves == 1 and mon.triggers == 1
    assert srv.summary()["reoptimizations"] == 1.0


def test_drift_default_reoptimize_fits_fleet(rng):
    fleet = _fleet(rng, ns=(18, 22))
    mon = obs.DriftMonitor(alpha=0.5, threshold=0.02, warmup=1, cooldown=10 ** 6)
    srv = ContinuousBatcher(fleet, drift_monitor=mon)
    before = fleet.params
    for w in range(6):
        for i in range(2):
            srv.submit_observe(
                i, rng.uniform(size=(2, 1)), np.full(2, 3.0 * w, np.float32)
            )
        srv.step()
    srv.flush()
    assert mon.triggers == 1
    # the default reoptimize ran fleet.optimize(): new per-problem leaves
    after_leaves = [np.asarray(l) for l in
                    __import__("jax").tree_util.tree_leaves(fleet.params)]
    before_leaves = [np.asarray(l) for l in
                     __import__("jax").tree_util.tree_leaves(before)]
    assert any(b.shape != a.shape or not np.array_equal(b, a)
               for b, a in zip(before_leaves, after_leaves))
    # and serving still works against the re-fitted fleet
    rid = srv.submit_predict(0, rng.uniform(size=(3, 1)))
    srv.step()
    assert np.isfinite(np.asarray(srv.result(rid))).all()


def test_fleet_optimize_improves_nlml(rng):
    fleet = _fleet(rng, ns=(20, 33))
    n0 = np.asarray(fleet.nlml())
    fleet.optimize(steps=30, lr=0.1)
    n1 = np.asarray(fleet.nlml())
    assert (n1 <= n0 + 1e-3).all()  # every problem at least as good
    assert n1.sum() < n0.sum()      # and the fleet strictly improved
