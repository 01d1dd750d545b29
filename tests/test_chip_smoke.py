"""chip_smoke.py's logic on the CPU: its phases at tiny sizes, its reference
checks tripping on perturbed results, its refusal to run without a TPU,
the four-device mesh phase on forced host devices, and the compile-cache
placement helper."""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _subproc import SRC, run_with_devices
from repro.core import GaussianProcess, GPFleet, mll
from repro.data.msd import make_dataset

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TILE = 32


@pytest.fixture(scope="module")
def data():
    return make_dataset(256, 256, seed=0)


def _failed(checks):
    return [c.name for c in checks if not c.ok]


@pytest.mark.parametrize("perturb", [False, True], ids=["clean", "perturbed"])
def test_exact_phase(data, monkeypatch, perturb):
    x, y, xt, _ = data
    if perturb:
        orig = GaussianProcess.predict_with_uncertainty

        def off(self, x_test):
            mean, var = orig(self, x_test)
            return mean, var + 1e-2

        monkeypatch.setattr(GaussianProcess, "predict_with_uncertainty", off)
    checks, info = chip_smoke.exact_phase(x, y, xt, TILE)
    assert info["n_train"] == 256 and info["tile"] == TILE
    if perturb:
        assert _failed(checks) == ["cold_var", "warm_var"]
    else:
        assert _failed(checks) == [], checks


@pytest.mark.parametrize("perturb", [False, True], ids=["clean", "perturbed"])
def test_train_phase(data, monkeypatch, perturb):
    x, y, _, _ = data
    if perturb:
        orig = mll.nlml_tiled
        monkeypatch.setattr(mll, "nlml_tiled", lambda *a, **k: 1.01 * orig(*a, **k))
    checks, info = chip_smoke.train_phase(x, y, TILE, steps=2)
    assert np.all(np.isfinite(info["params_fitted"]))
    assert _failed(checks) == (["grad"] if perturb else []), checks


@pytest.mark.parametrize("perturb", [False, True], ids=["clean", "perturbed"])
def test_serve_phase(data, monkeypatch, perturb):
    x, y, xt, _ = data
    if perturb:
        orig = GPFleet.predict_each

        def off(self, tests, *, full_cov=False):
            return [(m + 1e-2, c) for m, c in orig(self, tests, full_cov=full_cov)]

        monkeypatch.setattr(GPFleet, "predict_each", off)
    checks, info = chip_smoke.serve_phase(
        x, y, xt, b=4, n_lo=16, n_hi=128, tile=TILE, waves=2, arrive=8,
        per_request=4, n_checked=8,
    )
    assert info["predict_requests"] == 8 and info["checked"] == 8
    assert sum(info["sizes_end"]) == sum(info["sizes_start"]) + 2 * 8
    assert _failed(checks) == (["sample_mean"] if perturb else []), checks


def test_references_match_plain_float64_algebra():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((300, 4)) / 3.0
    y = rng.standard_normal(300)
    xt = rng.standard_normal((50, 4)) / 3.0
    theta = np.array([0.8, 1.3, 0.2])

    def se(a, b, l, v):
        return v * np.exp(-0.5 * ((a[:, None] - b[None]) ** 2).sum(-1) / l)

    def nlml(t):
        k = se(x, x, t[0], t[1]) + t[2] * np.eye(len(x))
        return 0.5 * (y @ np.linalg.solve(k, y) + np.linalg.slogdet(k)[1]
                      + len(x) * np.log(2 * np.pi))

    k = se(x, x, *theta[:2]) + theta[2] * np.eye(len(x))
    ks = se(xt, x, *theta[:2])
    mean, var = chip_smoke.reference_posterior(x, y, xt, *theta)
    np.testing.assert_allclose(mean, ks @ np.linalg.solve(k, y), atol=1e-10)
    np.testing.assert_allclose(
        var, theta[1] - np.einsum("ij,ji->i", ks, np.linalg.solve(k, ks.T)), atol=1e-10
    )
    h = 1e-6
    fd = [(nlml(theta + h * e) - nlml(theta - h * e)) / (2 * h) for e in np.eye(3)]
    np.testing.assert_allclose(chip_smoke.reference_nlml_grad(x, y, *theta), fd, rtol=1e-6)


def test_one_chip_phases_run_side_by_side(data, monkeypatch, capsys):
    x, y, xt, _ = data
    monkeypatch.setattr(chip_smoke, "serve_phase", functools.partial(
        chip_smoke.serve_phase, b=4, n_lo=16, n_hi=128, tile=TILE, waves=2,
        arrive=8, per_request=4,
    ))
    clock = chip_smoke._CompileClock()
    with clock.listening():
        oks = chip_smoke.one_chip_phases(x, y, xt, TILE, clock)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert oks == [True, True, True]
    assert sorted(r["phase"] for r in lines) == ["exact", "serve", "train"]
    assert all(r["wall_s"] >= r["compile_s"] >= 0 for r in lines)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert captured.out == ""


def test_fleet_mesh_phase_on_four_host_devices():
    code = f"""
import json, sys
sys.path.insert(0, {ROOT!r})
import jax
import chip_smoke
assert len(jax.devices()) == 4
checks, info = chip_smoke.fleet_mesh_phase(b=8, n=64, n_test=16, tile=32, chips=4)
print(json.dumps({{"failed": [c.name for c in checks if not c.ok], **info}}))
"""
    out = json.loads(run_with_devices(code, n_devices=4).strip().splitlines()[-1])
    assert out["failed"] == []
    assert len(out["shard_devices"]) == 4 and out["rows_per_shard"] == [2]


def _run_cache_probe(code, env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_honours_env_var(tmp_path):
    code = """
import json
import jax, jax.numpy as jnp
from repro.compile_cache import CHECKOUT_CACHE_DIR, use_persistent_cache
before = set(CHECKOUT_CACHE_DIR.glob("*")) if CHECKOUT_CACHE_DIR.exists() else set()
placed = use_persistent_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda a: jnp.sin(a) @ a)(jnp.ones((8, 8))).block_until_ready()
after = set(CHECKOUT_CACHE_DIR.glob("*")) if CHECKOUT_CACHE_DIR.exists() else set()
print(json.dumps({"placed": placed, "config": jax.config.jax_compilation_cache_dir,
                  "checkout_new": len(after - before)}))
"""
    out = _run_cache_probe(code, str(tmp_path))
    assert out["placed"] == out["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written where the env var says"
    assert out["checkout_new"] == 0


def test_compile_cache_falls_back_to_fixed_checkout_path():
    code = """
import json
import jax
from repro.compile_cache import use_persistent_cache
first = use_persistent_cache()
second = use_persistent_cache()
print(json.dumps({"first": first, "second": second,
                  "config": jax.config.jax_compilation_cache_dir}))
"""
    out = _run_cache_probe(code, None)
    assert out["first"] == out["second"] == out["config"] == os.path.join(ROOT, ".jax_cache")


def test_library_import_sets_no_cache():
    code = """
import json
import jax
import repro.core, repro.serve, repro.compile_cache
print(json.dumps({"config": jax.config.jax_compilation_cache_dir}))
"""
    assert _run_cache_probe(code, None)["config"] is None
