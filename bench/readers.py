"""The reductions that the per-layer metrics' readers share.

Each takes a :class:`bench.harness.ReaderInput` and returns a number, or
None where the trace holds nothing to read, so that the harness leaves the
metric out of the line.
"""

from __future__ import annotations


def dispatch_ms(r):
    """Mean host time of the benchmark's ``front_end_call`` spans, in ms."""
    calls = r.trace.spans.get("front_end_call", [])
    return 1e3 * sum(calls) / len(calls) if calls else None


def device_ops(r):
    """Op events that started on the device in the window, per iteration."""
    if not r.trace.n_ops or not r.iterations:
        return None
    return r.trace.n_ops / r.iterations


def roofline(r):
    """max(FLOPs / peak FLOP/s, bytes / peak B/s) over busy time per iteration."""
    if r.trace.busy_s <= 0 or not r.iterations:
        return None
    least = max(r.flops / r.peak.flops_per_s, r.bytes / r.peak.bytes_per_s)
    return 100.0 * least / (r.trace.busy_s / r.iterations)


def device_idle(r):
    """100 (1 - busy / window)."""
    if r.trace.busy_s <= 0 or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
