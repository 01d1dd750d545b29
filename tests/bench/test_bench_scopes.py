"""Device time by executor op family, and the library's host spans, from a trace."""

import io
import json
import shutil
import struct
from pathlib import Path

import pytest

from bench import scopes, trace
from bench.harness import SPANS

DATA = Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb"
ROOT = Path(__file__).resolve().parents[2]


# -- a hand-built XSpace -----------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        low, n = n & 0x7F, n >> 7
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A message from (field number, value): int -> varint, float -> fixed64,
    str or bytes -> length-delimited."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _event_meta(key, name, *stats):
    return (4, _msg((1, key), (2, _msg((1, key), (2, name), *[(5, _msg(*s)) for s in stats]))))


def _stat_meta(key, name):
    return (5, _msg((1, key), (2, _msg((1, key), (2, name)))))


def _space(tmp_path):
    device = _msg(
        (1, 3), (2, "/device:TPU:0"),
        _event_meta(1, "%fusion.1 = f32[8] fusion",
                    ((1, 10), (5, "jit(f)/repro.exec.potrf/vmap(jit(cholesky))/cholesky"))),
        _event_meta(2, "%copy.2 = f32[8] copy", ((1, 11), (2, 0.5)), ((1, 11), (5, "x"))),
        _event_meta(3, "%fusion.3 = f32[8] fusion", ((1, 11), (4, 7)), ((1, 10), (7, 12))),
        _event_meta(4, "%fusion.4 = f32[8] fusion", ((1, 10), (5, "jit(f)/broadcast_in_dim"))),
        _stat_meta(10, "tf_op"), _stat_meta(11, "flops"),
        _stat_meta(12, "jit(f)/repro.exec.gram/dot_general"),
    )
    host = _msg((2, "/host:CPU"),
                _event_meta(1, "%fusion.9", ((1, 10), (5, "repro.exec.trsm"))),
                _stat_meta(10, "tf_op"))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host), (4, "a-host")))
    return str(path)


def test_tf_ops_from_a_hand_built_space(tmp_path):
    path = _space(tmp_path)
    assert scopes.tf_ops(path) == {
        "%fusion.1 = f32[8] fusion": "jit(f)/repro.exec.potrf/vmap(jit(cholesky))/cholesky",
        "%fusion.3 = f32[8] fusion": "jit(f)/repro.exec.gram/dot_general",
        "%fusion.4 = f32[8] fusion": "jit(f)/broadcast_in_dim",
    }
    assert scopes.op_families(path) == {
        "%fusion.1 = f32[8] fusion": "potrf", "%fusion.3 = f32[8] fusion": "gram"}
    assert scopes.family("jit(f)/repro.exec.trsv_b/repro.exec.gemv_b/dot") == "gemv_b"
    assert scopes.family("jit(f)/dot_general") is None


def test_by_scope_and_stages_by_hand():
    ops = {"/device:TPU:0": [(-1.0, 0.5, "a"), (0.0, 1.0, "a"), (1.0, 3.0, "b"),
                             (2.5, 4.5, "c"), (3.0, 3.5, "free")],
           "/device:TPU:1": [(0.5, 1.5, "a")]}
    fams = {"a": "potrf", "b": "gram", "c": "vtrsv"}
    scoped = scopes.by_scope(ops, fams, 0.0, 4.0)
    assert scoped == {"potrf": 2.0, "gram": 2.0, "vtrsv": 1.5}
    assert scopes.stage_ms(scoped, 2) == {"cov": 0.0, "factor": 1000.0, "solve": 750.0,
                                          "gram": 1000.0}
    assert scopes.stage_ms({}, 2) == dict.fromkeys(scopes.STAGES)


def test_stages_cover_the_program_families():
    from repro.core import executor

    plan = executor.program_plan(4, 2, True, None)
    families = {bt.op for level in plan.levels for bt in level}
    staged = [f for fams in scopes.STAGES.values() for f in fams]
    assert sorted(staged) == sorted(families)


def test_small_trace_has_no_scope():
    # a plain jitted program: ops carry a tf_op but no executor scope
    assert scopes.tf_ops(str(SMALL))
    assert scopes.op_families(str(SMALL)) == {}
    result = scopes.summarize(str(SMALL), SPANS)
    assert result.scoped == {}
    assert scopes.stage_ms(result.scoped, 3) == dict.fromkeys(scopes.STAGES)
    # with no library span in it, the reduction is the benchmark's own
    assert result.summary == trace.summarize(str(SMALL), SPANS)


def test_scoped_chip_trace():
    # three cold 512-point posteriors with 256 test points, tiles of 128, on
    # one TPU v5e, with the library's spans on (tests/bench/record_scoped_trace.py)
    result = scopes.summarize(str(SCOPED), SPANS)
    s = result.summary
    assert s.n_devices == 1
    assert set(result.scoped) == {f for fams in scopes.STAGES.values() for f in fams}
    assert sum(result.scoped.values()) >= 0.9 * result.op_s
    assert 0.9 * s.busy_s <= result.scoped_busy_s <= s.busy_s
    assert result.unscoped and all(v < 1e-5 for _, v in result.unscoped)
    assert all(v is not None and v >= 0 for v in scopes.stage_ms(result.scoped, 3).values())
    for name in ("front_end_call", "repro.gp.predict", "repro.gp.lookup",
                 "repro.predict.pad", "repro.predict.fused", "repro.predict.untile",
                 "repro.gp.diag"):
        assert len(s.spans[name]) == 3, name
    # the library's spans label the gaps they hold, as named spans would
    library = sorted(n for n in s.spans if n.startswith(scopes.PREFIX))
    assert s == trace.summarize(str(SCOPED), list(SPANS) + library)
    assert {name for name, _ in s.gaps} & set(library)


# -- a traced run of the posterior cell on the CPU -----------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("scopes_root")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench")
    p = root / "bench" / "configs" / "msd_16k.json"
    cfg = json.loads(p.read_text())
    cfg.update(n_train=192, n_test=64, tile_size=32)
    p.write_text(json.dumps(cfg))
    return root


def test_traced_cpu_run_shows_library_spans(tiny_root):
    out, err = io.StringIO(), io.StringIO()
    rc = scopes.measure(tiny_root, "msd_16k.posterior", 4_100_000_003, 0.05,
                        require_chip=False, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["iterations"] >= 1 and line["failed"] == 0
    for name in ("front_end_call", "repro.gp.predict", "repro.predict.fused", "repro.gp.diag"):
        assert line["spans_ms"][name] > 0, name
    # the CPU's trace holds no device plane: no stage time, said on stderr
    assert line["stage_ms"] == dict.fromkeys(scopes.STAGES)
    assert "no op in the window carries a repro.exec scope" in err.getvalue()


def test_measure_needs_a_chip(tiny_root):
    err = io.StringIO()
    assert scopes.measure(tiny_root, "msd_16k.posterior", 1, 0.05, err=err) == 3
    assert "no TPU" in err.getvalue()
