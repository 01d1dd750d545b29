"""Every cell end to end at a tiny size on the CPU, through the harness's own path.

The configurations are cut to a few hundred rows in a copy of the benchmark
under a temporary directory; the harness's look for a chip is skipped.
Also: faults planted under the timed path turn ``correct`` false, the entry
point refuses to run without a TPU, and a cell, a configuration and a
per-layer metric added as files are found with no edit to any file.
"""

import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, peaks

ROOT = Path(__file__).resolve().parents[2]
TINY = {
    "msd_16k": {"n_train": 192, "n_test": 64, "tile_size": 32},
    "arbo_fleet": {"problems": 6, "size_min": 8, "size_max": 30, "tile_size": 16,
                   "candidates": 4},
}
CELLS = ["msd_16k.posterior", "arbo_fleet.refit"]
# arbo_fleet.refit is proved but not yet in BENCHMARK.json (its first run's
# set-up, 680-1160 s of compiling on the chip, nears the 1200 s allowed;
# PERF.md section 7); its entries as a later PR would add them
FLEET = {
    "configs": [{"name": "arbo_fleet", "source": "https://github.com/ekogl/ARBO",
                 "file": "bench/configs/arbo_fleet.json",
                 "reduced": ["problems", "ard_lengthscales"], "why": "fleet"}],
    "workloads": [{"name": "arbo_fleet.refit", "config": "arbo_fleet", "traffic": "refit",
                   "chips": 1, "why": "fleet"}],
    "end_to_end": [{"name": "fleet_refit_s", "unit": "s", "better": "lower", "bound": 0.25,
                    "source": "host_clock", "workloads": ["arbo_fleet.refit"]}],
    "per_layer": [{"name": f"{m}.fleet", "unit": "1", "better": "lower", "source": "device_trace",
                   "layer": "l", "moves": "fleet_refit_s", "workloads": ["arbo_fleet.refit"]}
                  for m in ("dispatch_ms", "device_ops", "roofline", "device_idle")],
}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _fake_peak(kind):
    return peaks.Peak(1e12, 1e11, 1e9)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in FLEET.items():
        spec[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(ROOT / "bench", root / "bench")
    for name, over in TINY.items():
        p = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg.update(over)
        p.write_text(json.dumps(cfg))
    p = root / "bench" / "traffic" / "posterior.json"
    t = json.loads(p.read_text())
    t["check_points"] = 16
    p.write_text(json.dumps(t))
    return root


def _run(root, workload, trace=False, seed=4_000_000_017):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(root, workload, seed, 0.05, trace, require_chip=False,
                          peaks=_fake_peak, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(tiny_root, workload):
    line, err = _run(tiny_root, workload)
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in harness.cell_metrics(spec, workload, None, "end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


def test_traced_run_prints_breakdown(tiny_root):
    line, _ = _run(tiny_root, "msd_16k.posterior", trace=True)
    assert list(line) == KEYS + ["breakdown", "checks"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["device"]["window_s"] > 0
    assert "dispatch_ms.posterior" in line["metrics"]


# -- faults planted under the timed path ---------------------------------------


FAULTS = [(w, kind) for w in CELLS for kind in ("unchanged", "half", "altered")]


@pytest.mark.parametrize("workload,kind", FAULTS)
def test_planted_fault_is_not_correct(tiny_root, workload, kind):
    from bench import faults

    with faults.planted(workload, kind):
        line, _ = _run(tiny_root, workload, seed=77)
    assert line["correct"] is False, line["checks"]


def test_faults_cover_every_cell():
    from bench import faults

    assert set(faults.FAULTS) == set(CELLS)


# -- the control and the faults on the chip: bench/control.py -----------------


def _control(tiny_root, monkeypatch, *args):
    from bench import control

    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    rc = control.main(["--root", str(tiny_root), "--workload", "msd_16k.posterior",
                       "--any-platform", *args])
    assert rc == 0
    return [json.loads(l) for l in out.getvalue().strip().splitlines()
            if l.startswith('{"correct"')]


def test_control_switches_the_program_precision(tiny_root, monkeypatch):
    from repro.core import precision, predict

    seen = []
    orig = predict.predict_fused

    def spy(*a, **k):
        seen.append(precision.MATMUL_PRECISION)
        return orig(*a, **k)

    monkeypatch.setattr(predict, "predict_fused", spy)
    lines = _control(tiny_root, monkeypatch, "--seeds", "5,6", "--precision", "high")
    assert seen and set(seen) == {"high"}
    assert precision.MATMUL_PRECISION == "highest"
    # one whole run per seed, judged by the harness's own comparison (on the
    # CPU HIGH computes as HIGHEST, so here it reads correct)
    assert len(lines) == 2
    assert all(list(l) == KEYS + ["checks"] for l in lines)
    assert all(set(l["checks"]) == {"mean_rms", "mean_rel", "var_err"} for l in lines)


def test_control_plants_a_fault(tiny_root, monkeypatch):
    from repro.core import gp

    before = gp.GaussianProcess.predict_with_uncertainty
    lines = _control(tiny_root, monkeypatch, "--seeds", "8", "--fault", "unchanged")
    assert [l["correct"] for l in lines] == [False]
    assert gp.GaussianProcess.predict_with_uncertainty is before


# -- the entry point and the data-driven lookup ---------------------------------


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "msd_16k.posterior", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_without_a_tpu():
    r = _entry(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_entry_refuses_in_a_bare_benchmark_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    r = _entry(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_added_files_are_found_by_name(tiny_root, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    cfg = json.loads((root / "bench/configs/msd_16k.json").read_text())
    cfg.update(name="msd_tiny", n_train=96, n_test=32)
    (root / "bench/configs/msd_tiny.json").write_text(json.dumps(cfg))
    (root / "bench/limits/msd_tiny.posterior.json").write_text(
        (root / "bench/limits/msd_16k.posterior.json").read_text())
    (root / "bench/metrics/iterations_seen.posterior.py").write_text(
        "def read(r):\n    return float(r.iterations)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "msd_tiny", "source": "https://arxiv.org/abs/2602.19683",
                            "file": "bench/configs/msd_tiny.json", "reduced": ["n_train"],
                            "why": "test"})
    spec["workloads"].append({"name": "msd_tiny.posterior", "config": "msd_tiny",
                              "traffic": "posterior", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("msd_tiny.posterior")
    spec["per_layer"].append({"name": "iterations_seen.posterior", "unit": "1",
                              "better": "higher", "source": "host_clock", "layer": "test",
                              "moves": "posterior_s", "workloads": ["msd_tiny.posterior"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    line, _ = _run(root, "msd_tiny.posterior", trace=True)
    assert line["metrics"]["iterations_seen.posterior"]["value"] == line["attempted"]
    assert line["correct"] is True
