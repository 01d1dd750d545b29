"""Runs one benchmark cell once: set-up, a timed window, the comparison.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the cell's configuration, ``bench/configs/<config>.json`` (its sizes),
  whose ``data`` and ``reference`` keys name ``bench/data/<data>.py`` and
  ``bench/reference/<reference>.py``;
* its traffic, ``bench/traffic/<traffic>.json`` (parameters), whose
  ``entry`` key names the driver ``bench/entries/<entry>.py`` and whose
  ``metric`` key names the end-to-end metric the window yields;
* the limits of its comparison, ``bench/limits/<workload>.json``;
* each per-layer metric's reader, ``bench/metrics/<metric>.py``.

So a cell, a configuration, a traffic mix or a metric is added by adding
files and entries, never by editing one.

An entry module provides ``setup(ctx)`` (data, the system under test, the
warm-up of every shape the window uses), ``iterate(state, i)`` (one whole
iteration, ended by ``block_until_ready``), ``finite(output)``,
``fetch(ctx, state, outputs)`` (what the comparison reads, on the host),
``release(state)``, ``check(ctx, fetched)`` (the comparison: a list of
:class:`Check`), ``work(ctx)`` (FLOPs and bytes per iteration) and
``UNITS`` (how many of the metric's units one iteration completes).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import shutil
import tempfile
import threading
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

# The benchmark's own host spans.  The trace reduction labels idle gaps
# with the innermost of these open at the time.
SPANS = (
    "window", "iteration", "make_inputs", "set_params", "front_end_call",
    "block", "reference",
)


@dataclasses.dataclass
class Check:
    """One number compared with its limit: passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def span(name: str):
    """A host span in the profiler's trace (a no-op cost when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts backend compiles and persistent-cache hits while registered.

    JAX reports a backend compile for every executable it builds or loads;
    a load from the persistent cache also reports a cache hit, so compiles
    are the first count less the second.
    """

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def _duration(self, event, duration, **_):
        if event == self._COMPILE:
            with self._lock:
                self.compiles += 1

    def _event(self, event, **_):
        if event == self._HIT:
            with self._lock:
                self.cache_hits += 1

    def register(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def unregister(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles - self.cache_hits,
                "cache_loads": self.cache_hits,
            }


# ---------------------------------------------------------------------------
# Finding things by name
# ---------------------------------------------------------------------------


def load_spec(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(root: Path, kind: str, name: str) -> dict:
    with open(Path(root) / "bench" / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root``, imported by its path."""
    path = Path(root).resolve() / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    modname = f"_bench_{kind}_{abs(hash(str(path))):x}_{name.replace('.', '_')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What an entry, a reader and a comparison get to see of the cell."""

    root: Path
    workload: str
    seed: int
    cfg: dict
    traffic: dict
    limits: dict
    err: object = sys.stderr

    def load(self, kind: str, name: str):
        return load_module(self.root, kind, name)

    def rng(self, *stream: int) -> np.random.Generator:
        """A generator of one named stream of this run's seed."""
        return np.random.default_rng([self.seed % (1 << 63), *stream])

    def log(self, **fields):
        print(json.dumps(fields), file=self.err, flush=True)


@dataclasses.dataclass
class ReaderInput:
    """What a per-layer metric's reader reads."""

    trace: object            # trace.TraceSummary
    iterations: int
    flops: float             # per iteration, from the dense algorithm
    bytes: float
    peak: object             # peaks.Peak of the device


def cell_metrics(spec: dict, workload: str, reported: str, key: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    out = []
    for m in spec[key]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif key == "end_to_end" or m["moves"] == reported:
            out.append(m)
    return out


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": max(peaks),
    }


def configure_jax() -> str:
    """The persistent compile cache, with every program cached."""
    import jax
    from repro.compile_cache import use_persistent_cache

    where = use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def prepare(root: Path, workload: str, seed: int, err=None):
    """(spec, cell, ctx, entry) of one cell, found by name under ``root``."""
    root = Path(root)
    spec = load_spec(root)
    cell = {w["name"]: w for w in spec["workloads"]}[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(root / config["file"]) as f:
        cfg = json.load(f)
    traffic = load_json(root, "traffic", cell["traffic"])
    limits = load_json(root, "limits", workload)
    ctx = Context(root, workload, int(seed), cfg, traffic, limits,
                  sys.stderr if err is None else err)
    return spec, cell, ctx, ctx.load("entries", traffic["entry"])


def window(entry, state, seconds: float, err=None):
    """Whole iterations until ``seconds`` have passed and the last completes.

    Returns (outputs by iteration, iterations that raised, iterations, seconds).
    """
    err = sys.stderr if err is None else err
    outputs: Dict[int, object] = {}
    raised = 0
    i = 0
    with span("window"):
        t0 = time.perf_counter()
        while True:
            try:
                with span("iteration"):
                    outputs[i] = entry.iterate(state, i)
            except Exception:  # an iteration that raises counts as failed
                raised += 1
                traceback.print_exc(file=err)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    return outputs, raised, i, elapsed


def compare(ctx, entry, state, outputs):
    """(checks, non-finite iterations), with the program's state freed first."""
    bad = sum(1 for o in outputs.values() if not entry.finite(o))
    fetched = entry.fetch(ctx, state, outputs)
    entry.release(state)
    outputs.clear()
    gc.collect()
    t0 = time.perf_counter()
    with span("reference"):
        checks = entry.check(ctx, fetched)
    ctx.log(reference_s=time.perf_counter() - t0)
    return checks, bad


def run_cell(
    root: Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: Optional[float] = None,
    require_chip: bool = True,
    peaks: Optional[Callable] = None,
    out=None,
    err=None,
) -> int:
    """Run one cell once and print its result line; returns the exit code."""
    import jax

    out = sys.stdout if out is None else out
    err = sys.stderr if err is None else err
    t_start = time.perf_counter() if t_start is None else t_start
    spec, cell, ctx, entry = prepare(root, workload, seed, err)
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            print(f"bench: no TPU (jax.devices()[0].platform is {devices[0].platform!r})",
                  file=err)
            return 3
        if len(devices) < cell["chips"]:
            print(f"bench: {workload} needs {cell['chips']} chips, found {len(devices)}",
                  file=err)
            return 3
    if peaks is None:
        from bench.peaks import peak as peaks
    reported = ctx.traffic["metric"]
    device_peak = peaks(devices[0].device_kind) if trace else None

    counter = CompileCounter()
    counter.register()
    state = entry.setup(ctx)
    setup_s = time.perf_counter() - t_start
    before = counter.snapshot()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    outputs, failed, i, elapsed = window(entry, state, seconds, err)
    if trace:
        jax.profiler.stop_trace()
    after = counter.snapshot()
    counter.unregister()
    dev = device_info(cell["chips"])
    print(json.dumps({
        "workload": workload, "seed": int(seed), "iterations": i, "window_s": elapsed,
        "setup_s": setup_s, "setup_compiles": before["compiles"],
        "setup_cache_loads": before["cache_loads"],
        "window_compiles": after["compiles"] - before["compiles"],
        "window_cache_loads": after["cache_loads"] - before["cache_loads"],
    }), file=out, flush=True)

    checks, bad = compare(ctx, entry, state, outputs)
    failed += bad
    del state

    units = entry.UNITS
    metrics = {}
    result_device = dict(dev)
    breakdown = None
    if not trace:
        for m in cell_metrics(spec, workload, reported, "end_to_end"):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] == reported:
                metrics[reported] = {"value": elapsed / (i * units), "unit": m["unit"]}
    else:
        from bench import trace as tr

        try:
            summary = tr.summarize(tr.find_xplane(trace_dir), SPANS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        flops, nbytes = entry.work(ctx)
        reader_in = ReaderInput(summary, i, flops, nbytes, device_peak)
        for m in cell_metrics(spec, workload, reported, "per_layer"):
            value = ctx.load("metrics", m["name"]).read(reader_in)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result_device["busy_s"] = summary.busy_s
        result_device["window_s"] = summary.window_s
        breakdown = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps],
        }

    correct = all(c.ok for c in checks) and failed == 0
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'}",
              file=err, flush=True)
    result = {
        "correct": correct,
        "attempted": i,
        "failed": failed,
        "metrics": metrics,
        "device": result_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    print(json.dumps(result), file=out, flush=True)
    return 0
