"""Device time by executor stage, and the library's own host spans, from a trace.

The executor runs every batch of its plans inside
``jax.named_scope("repro.exec.<op family>")`` (``repro.core.executor``).
The scope reaches each compiled op's metadata, and a profiler trace keeps it
as the ``tf_op`` stat of the op's event metadata, for example
``jit(fn)/repro.exec.potrf/vmap(jit(cholesky))/cholesky``.
``jax.profiler.ProfileData`` exposes no metadata stats, so :func:`tf_ops`
decodes the ``.xplane.pb`` protobuf wire format itself, with the standard
library alone.

The library's host spans (``repro.gp.*``, ``repro.predict.*``) open only
under ``repro.obs.enable()``.  They are ``TraceAnnotation``s on the trace's
own clock, kept here beside the benchmark's spans: they fill the spans'
durations and label the idle gaps.

    python bench/scopes.py --workload msd_16k.posterior --seed 7 --seconds 40

runs one cell's set-up and window as ``bench/run.py --trace 1`` does, with
the library's spans on, and prints one JSON line: device ms per iteration
by stage and by op family, the share of device time the scopes cover (of
the ops' summed time, and of the busy time), the ops with no scope, the
spans' mean ms, the top ops with their family, the idle gaps labelled by the
innermost span, and the seconds the benchmark's own reduction
(``trace.summarize``) and this module's took.  It makes no comparison.  Exits non-zero where JAX finds no TPU.

The persistent compile cache's key leaves metadata out, so an executable
compiled before the scopes existed loads, runs and profiles with no scope.
Such a window reads as no stage time at all (``None``), never as zero, and
says so on standard error.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":  # run as a script: the checkout and src/ on the path
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402

PREFIX = "repro."
_FAMILY = re.compile(r"repro\.exec\.(\w+)")

# The executor's op families by stage of the fused program.
STAGES = {
    "cov": ("assemble", "cross", "prior"),
    "factor": ("potrf", "trsm", "trail"),
    "solve": ("trsv", "gemv", "trsv_b", "gemv_b", "xgemv", "vinit", "vtrsv", "vgemv"),
    "gram": ("gram",),
}

# Field numbers of the XSpace protobuf (tsl/profiler/protobuf/xplane.proto).
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_NAME = 2
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field, None for a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace") if value is not None else ""


def _map_entry(entry) -> Tuple[int, object]:
    fields = dict(_fields(entry))
    return fields.get(_MAP_KEY, 0), fields.get(_MAP_VALUE, b"")


def tf_ops(path: str) -> Dict[str, str]:
    """Device op name -> the ``tf_op`` stat of its event metadata, over
    every device plane of an ``.xplane.pb`` file."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for field, plane in _fields(space):
        if field != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for f, value in _fields(plane):
            if f == _PLANE_NAME:
                name = _text(value)
            elif f == _PLANE_EVENT_METADATA:
                events.append(value)
            elif f == _PLANE_STAT_METADATA:
                key, meta = _map_entry(value)
                stat_names[key] = _text(dict(_fields(meta)).get(_STAT_META_NAME))
        if not name.startswith("/device:"):
            continue
        for entry in events:
            _, meta = _map_entry(entry)
            op, tf_op = "", None
            for f, value in _fields(meta):
                if f == _EVENT_META_NAME:
                    op = _text(value)
                elif f == _EVENT_META_STATS:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(_STAT_META_ID)) != "tf_op":
                        continue
                    if _STAT_STR in stat:
                        tf_op = _text(stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        tf_op = stat_names.get(stat[_STAT_REF], "")
            if op and tf_op is not None:
                out[op] = tf_op
    return out


def family(tf_op: str) -> Optional[str]:
    """The innermost ``repro.exec.<family>`` scope of a ``tf_op``, if any."""
    found = _FAMILY.findall(tf_op)
    return found[-1] if found else None


def op_families(path: str) -> Dict[str, str]:
    """Device op name -> its executor op family, for the ops that have one."""
    out = {}
    for op, tf_op in tf_ops(path).items():
        fam = family(tf_op)
        if fam is not None:
            out[op] = fam
    return out


def by_scope(device_ops, families: Dict[str, str], lo: float, hi: float) -> Dict[str, float]:
    """Seconds by op family: the ops that started in [lo, hi), clipped at
    ``hi`` and summed over devices, as ``trace.reduce_events`` sums its top
    ops.  Ops with no family are left out."""
    out: Dict[str, float] = collections.defaultdict(float)
    for ops in device_ops.values():
        for s, e, n in ops:
            fam = families.get(n)
            if fam is not None and lo <= s < hi:
                out[fam] += min(e, hi) - s
    return dict(out)


def stage_ms(scoped: Dict[str, float], iterations: int) -> Dict[str, Optional[float]]:
    """Device ms per iteration of each stage in :data:`STAGES`; every stage
    None where no op carries a scope (a program compiled without them)."""
    if not scoped or not iterations:
        return {stage: None for stage in STAGES}
    return {stage: 1e3 * sum(scoped.get(f, 0.0) for f in fams) / iterations
            for stage, fams in STAGES.items()}


def host_spans(path: str, names: Sequence[str], prefix: str = PREFIX):
    """(start, end, name) of the host events named in ``names`` or starting
    with ``prefix``, in seconds."""
    from jax.profiler import ProfileData

    keep = set(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep or ev.name.startswith(prefix):
                        out.append((ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
    return out


@dataclasses.dataclass
class Scoped:
    summary: trace.TraceSummary       # with the library's spans and gap labels
    scoped: Dict[str, float]          # op family -> seconds, summed over devices
    op_s: float                       # every op in the window, by the same rule
    scoped_busy_s: float              # union of the scoped ops, mean over devices
    unscoped: List[Tuple[str, float]]  # the ops with no family, as top_ops
    families: Dict[str, str]          # op name -> family


def summarize(path: str, span_names: Sequence[str], prefix: str = PREFIX) -> Scoped:
    """``trace.summarize(path, span_names)`` with the host spans that start
    with ``prefix`` kept too, and device time by op family."""
    device_ops, _ = trace.read_xplane(path, ())
    host = host_spans(path, span_names, prefix)
    summary = trace.reduce_events(device_ops, host)
    lo, hi = [(s, e) for s, e, n in host if n == trace.WINDOW][0]
    families = op_families(path)
    op_s = covered = 0.0
    unscoped: Dict[str, float] = collections.defaultdict(float)
    for ops in device_ops.values():
        for s, e, n in ops:
            if lo <= s < hi:
                op_s += min(e, hi) - s
                if n not in families:
                    unscoped[trace.short_name(n)] += min(e, hi) - s
        scoped_ops = trace.clip([(s, e) for s, e, n in ops if n in families], lo, hi)
        covered += sum(e - s for s, e in trace.union(scoped_ops))
    return Scoped(summary, by_scope(device_ops, families, lo, hi), op_s,
                  covered / len(device_ops) if device_ops else 0.0,
                  sorted(unscoped.items(), key=lambda kv: -kv[1])[:10], families)


def measure(root: Path, workload: str, seed: int, seconds: float, *,
            require_chip: bool = True, out=None, err=None) -> int:
    """One cell's set-up and traced window with the library's spans on;
    prints the stage split as one JSON line.  Returns the exit code."""
    import jax

    import repro.obs as obs

    out = sys.stdout if out is None else out
    err = sys.stderr if err is None else err
    _, _, ctx, entry = harness.prepare(root, workload, seed, err)
    device = jax.devices()[0]
    if require_chip and device.platform != "tpu":
        print(f"scopes: no TPU (jax.devices()[0].platform is {device.platform!r})", file=err)
        return 3
    state = entry.setup(ctx)
    trace_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        obs.enable()
        jax.profiler.start_trace(trace_dir)
        try:
            _, failed, iterations, elapsed = harness.window(entry, state, seconds, err)
        finally:
            jax.profiler.stop_trace()
            obs.disable()
        entry.release(state)
        path = trace.find_xplane(trace_dir)
        t0 = time.perf_counter()
        trace.summarize(path, harness.SPANS)
        t1 = time.perf_counter()
        result = summarize(path, harness.SPANS)
        t2 = time.perf_counter()
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    s = result.summary
    if not result.scoped:
        print("scopes: no op in the window carries a repro.exec scope (a program "
              "loaded from a compile cache filled before the scopes existed?)", file=err)
    per_iter = 1e3 / iterations
    short = {trace.short_name(n): f for n, f in result.families.items()}
    print(json.dumps({
        "workload": workload, "seed": int(seed), "device": device.device_kind,
        "iterations": iterations, "failed": failed, "window_s": elapsed,
        "traced_window_s": s.window_s, "busy_s": s.busy_s,
        "device_ops": s.n_ops / iterations,
        "stage_ms": stage_ms(result.scoped, iterations),
        "scoped_share": (100.0 * sum(result.scoped.values()) / result.op_s
                         if result.op_s else None),
        "scoped_busy_share": 100.0 * result.scoped_busy_s / s.busy_s if s.busy_s else None,
        "family_ms": {f: v * per_iter for f, v in sorted(result.scoped.items())},
        "spans_ms": {n: 1e3 * sum(d) / len(d) for n, d in sorted(s.spans.items())},
        "top_ops": [[n, v, short.get(n)] for n, v in s.top_ops],
        "unscoped_ops": [[n, v] for n, v in result.unscoped],
        "idle_gaps": [[n, v] for n, v in s.gaps],
        "reduce_s": {"bench": t1 - t0, "scopes": t2 - t1},
    }), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.configure_jax()
    return measure(ROOT, args.workload, args.seed, args.seconds)


if __name__ == "__main__":
    sys.exit(main())
