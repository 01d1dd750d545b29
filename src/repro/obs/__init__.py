"""repro.obs — zero-cost-when-off telemetry (DESIGN.md §15).

A process-local metrics registry (counters / fixed-bucket histograms /
JSON-lines events), profiler trace spans for the host dispatch boundaries
and named scopes for the ops inside a program, an lru-cache statistics
snapshot, and the NLML-trend drift monitor.  A leaf package: it never imports ``repro.core`` (core
imports it), so instrumentation can thread through every layer without
cycles.

    import repro.obs as obs

    obs.enable("metrics.jsonl")      # flip the one global flag
    ...                              # run the instrumented stack
    print(obs.to_prometheus())       # or obs.to_json() / obs.snapshot()
    print(obs.cache_stats())         # plan/jit lru hit rates
    obs.disable()

Disabled (the default), every helper returns after a single module-level
boolean check — the instrumented hot paths run bit-identically to an
uninstrumented build (benchmarks/fig15_obs_overhead.py measures it).
"""

from repro.obs.drift import DriftMonitor
from repro.obs.registry import (
    COUNT_EDGES,
    DEFAULT_EDGES,
    FRACTION_EDGES,
    MAX_EVENTS,
    Counter,
    Histogram,
    Registry,
    cache_stats,
    disable,
    enable,
    enabled,
    event,
    health_event,
    inc,
    observe,
    register_cache,
    registry,
    reset,
    snapshot,
    to_json,
    to_prometheus,
)
from repro.obs.tracer import Tracer, span

__all__ = [
    "COUNT_EDGES",
    "DEFAULT_EDGES",
    "FRACTION_EDGES",
    "Counter",
    "DriftMonitor",
    "Histogram",
    "MAX_EVENTS",
    "Registry",
    "Tracer",
    "cache_stats",
    "disable",
    "enable",
    "enabled",
    "event",
    "health_event",
    "inc",
    "observe",
    "register_cache",
    "registry",
    "reset",
    "snapshot",
    "span",
    "to_json",
    "to_prometheus",
]
