"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the benchmark's numbers.

Device planes are named ``/device:<PLATFORM>:<id>``; the operations that ran
on a device are the events of its ``XLA Ops`` line.  The benchmark's own host
spans (``jax.profiler.TraceAnnotation``) are events of the host plane, on the
same clock.  Everything is clipped to the benchmark's ``window`` span:

* busy: the union of a device's op intervals, averaged over the devices;
* ops: the op events that started in the window;
* top ops: device time by op, named by its HLO result and opcode (the
  trace names an op by its whole HLO instruction), summed over devices;
* idle gaps: the gaps between busy intervals, each labelled by the
  innermost benchmark span open at its middle.

The host and device clocks of a trace agree to about a millisecond, so ops
within that of the window's edges may fall on either side.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float                      # length of the traced window
    busy_s: float                        # device busy time, mean over devices
    n_devices: int                       # devices with a plane in the trace
    n_ops: int                           # op events in the window, all devices
    top_ops: List[Tuple[str, float]]     # (name, seconds), most time first
    gaps: List[Tuple[str, float]]        # (host span, seconds), longest first
    spans: Dict[str, List[float]]        # benchmark span name -> durations, s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^(%?[\w.-]+) = (.*?) ([a-z][\w-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.4 = f32[32,512]{1,0} fusion(...), ...`` -> ``%fusion.4 = f32[32,512] fusion``."""
    m = _INSTR.match(_LAYOUT.sub("", _LAYOUT.sub("", name)))
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else name


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def innermost(spans: Sequence[Tuple[float, float, str]], t: float) -> str:
    """Name of the latest-starting span that contains ``t`` ("none" if none)."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or s >= best[0]):
            best = (s, name)
    return "none" if best is None else best[1]


def reduce_events(
    device_ops: Dict[str, List[Tuple[float, float, str]]],
    host_spans: List[Tuple[float, float, str]],
    *,
    top: int = 10,
) -> TraceSummary:
    """The summary from plain events: times in seconds on one clock.

    ``device_ops`` maps a device to its (start, end, name) op events;
    ``host_spans`` are the benchmark's (start, end, name) spans, which must
    include one named ``window``.
    """
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if not windows:
        raise ValueError("the trace has no benchmark 'window' span")
    lo, hi = windows[0]
    spans = [(s, e, n) for s, e, n in host_spans if e > lo and s < hi]
    per_op: Dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    n_ops = 0
    merged_all = []
    for ops in device_ops.values():
        inside = [(s, e, n) for s, e, n in ops if lo <= s < hi]
        n_ops += len(inside)
        for s, e, n in inside:
            per_op[short_name(n)] += min(e, hi) - s
        merged = union(clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_total += sum(e - s for s, e in merged)
        merged_all.append(merged)
    n_dev = len(device_ops)
    # idle gaps of the first device (one chip per cell unless it says otherwise)
    gaps = []
    if merged_all:
        edges = [lo] + [t for iv in merged_all[0] for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((innermost(spans, 0.5 * (a + b)), b - a))
    gaps.sort(key=lambda g: -g[1])
    durations: Dict[str, List[float]] = collections.defaultdict(list)
    for s, e, n in spans:
        if n != WINDOW and lo <= s and e <= hi:
            durations[n].append(e - s)
    return TraceSummary(
        window_s=hi - lo,
        busy_s=busy_total / n_dev if n_dev else 0.0,
        n_devices=n_dev,
        n_ops=n_ops,
        top_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        gaps=gaps[:top],
        spans=dict(durations),
    )


def read_xplane(path: str, span_names: Sequence[str]):
    """(device_ops, host_spans) from an ``.xplane.pb`` file, times in seconds.

    Host events are kept when their name is in ``span_names``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    keep = set(span_names)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        host.append(
                            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        )
    return device_ops, host


def summarize(path: str, span_names: Sequence[str]) -> TraceSummary:
    device_ops, host = read_xplane(path, span_names)
    return reduce_events(device_ops, host)
