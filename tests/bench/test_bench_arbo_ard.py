"""The ARBO fleet cell, ``arbo_ard_1k.fleet_refit``, at a tiny size on the CPU.

The configuration is cut to a few problems of a few dozen rows in a copy of
the benchmark under a temporary directory and run through the harness's own
``run_cell``; the fleet faults of ``bench/faults.py`` (its ``arbo_fleet.refit``
wrappers, which patch ``GPFleet``) must turn ``correct`` false.  The plain
reference is pinned to the direct inverse.
"""

import io
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import faults, harness, peaks
from bench.reference import gp_dense_matern_ard as ref

ROOT = Path(__file__).resolve().parents[2]
CELL = "arbo_ard_1k.fleet_refit"
TINY = {"problems": 8, "size_min": 8, "size_max": 60, "tile_size": 16, "candidates": 5}
FLEET_METRICS = [f"{m}.fleet" for m in ("dispatch_ms", "device_ops", "roofline", "device_idle")]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("arbo_root")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench")
    p = root / "bench" / "configs" / "arbo_ard_1k.json"
    cfg = json.loads(p.read_text())
    cfg.update(TINY)
    p.write_text(json.dumps(cfg))
    return root


def _run(root, trace=False, seed=4_000_000_017):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(root, CELL, seed, 0.05, trace, require_chip=False,
                          peaks=lambda kind: peaks.Peak(1e12, 1e11, 1e9), out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[0]), json.loads(lines[-1])


def test_declared_in_the_benchmark():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "fleet_refit"
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert CELL in e2e["posterior_s"]["workloads"]
    layer = {m["name"]: m for m in spec["per_layer"]}
    for name in FLEET_METRICS:
        assert layer[name]["moves"] == "posterior_s" and layer[name]["workloads"] == [CELL]
    cfg = json.loads((ROOT / "bench/configs/arbo_ard_1k.json").read_text())
    assert cfg["problems"] == 1024 and cfg["candidates"] == cfg["s_max"] == 64
    assert cfg["reduced"] == [] and cfg["kernel"] == "matern52_ard"


@pytest.mark.parametrize("seed", [4_000_000_017, 2_600_000_003])
def test_cell_is_correct_end_to_end(tiny_root, seed):
    counts, line = _run(tiny_root, seed=seed)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"posterior_s", "setup_s"}
    assert set(line["checks"]) == {"mean_err", "cov_err"}
    # every shape the window runs was compiled (or loaded) in set-up
    assert counts["window_compiles"] == 0 and counts["window_cache_loads"] == 0


def test_traced_run_reads_the_fleet_metrics(tiny_root):
    _, line = _run(tiny_root, trace=True)
    assert "dispatch_ms.fleet" in line["metrics"]
    assert set(line["metrics"]) <= set(FLEET_METRICS)
    assert line["correct"] is True


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_planted_fleet_fault_is_not_correct(tiny_root, monkeypatch, kind):
    from repro.core.gp import GPFleet

    cls_name, method, wrap = faults.FAULTS["arbo_fleet.refit"][kind]
    assert cls_name == "GPFleet"
    monkeypatch.setattr(GPFleet, method, wrap(getattr(GPFleet, method)))
    _, line = _run(tiny_root, seed=77)
    assert line["correct"] is False, line["checks"]


def _data(n=60, seed=0):
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(1, 65, n), rng.uniform(0.5, 4.0, n),
                  rng.uniform(0.0, 100.0, n)], -1)
    return x, np.sin(x[:, 0] / 8.0) + 0.1 * rng.standard_normal(n)


LS, V, S2 = np.asarray([100.0, 1.0, 100.0]), 1.3, 0.03


def test_reference_by_direct_inverse():
    x, y = _data()
    xt = _data(7, seed=1)[0]
    mean, cov = ref.posterior(x, y, xt, LS, V, S2)
    k = ref.kfree(x, x, LS, V) + S2 * np.eye(len(x))
    kst = ref.kfree(xt, x, LS, V)
    kinv = np.linalg.inv(k)
    np.testing.assert_allclose(mean, kst @ kinv @ y, atol=1e-8)
    np.testing.assert_allclose(cov, ref.kfree(xt, xt, LS, V) - kst @ kinv @ kst.T, atol=1e-8)
    _, var = ref.posterior(x, y, xt, LS, V, S2, full_cov=False)
    np.testing.assert_allclose(var, np.diag(cov), atol=1e-12)


def test_reference_kernel_is_matern52_ard():
    a, b = _data(5)[0], _data(4, seed=2)[0]
    r = np.sqrt((((a[:, None] - b[None]) ** 2) / LS).sum(-1))
    want = V * (1 + np.sqrt(5) * r + 5 * r * r / 3) * np.exp(-np.sqrt(5) * r)
    np.testing.assert_allclose(ref.kfree(a, b, LS, V), want, rtol=1e-12)
