"""Smoke run of the tiled GP pipeline on a TPU, through the user front-ends.

    python chip_smoke.py              # one chip: exact, train, serve
    python chip_smoke.py --chips 4    # four chips: a sharded GPBatch only

Everything runs in this one process, with the default ``op_backend="jnp"``.
Each phase prints one JSON line: wall, compile and run seconds, the
device's ``peak_bytes_in_use`` so far, and every error against its
reference beside its tolerance.  A failed check makes the exit code
non-zero; the last line is ``{"ok": true, "device": {...}}`` only when
every check passed.  Without a TPU the script stops before any phase.

One chip:

* exact — the paper's 16k cell (``configs.gp_msd.GP_PAPER_16K``:
  n_train = n_test = 16384, tile 512, D = 16, MSD NFIR data).  A cold
  ``GaussianProcess.predict_with_uncertainty`` runs the fused program,
  which factors and caches the posterior; a warm call runs off the cached
  factor.  Both are held to a dense f32 reference on the same chip, and
  their distance from a float64 posterior is printed beside.
* train — the NLML gradient at the initial parameters through the tiled
  custom VJP against the dense float64 gradient, and
  ``GaussianProcess.optimize(steps=3)``.
* serve — a ragged ``GPFleet`` of 16 problems (sizes log-uniform in
  64..2048, tile 128) under ``ContinuousBatcher`` for 8 waves of predict
  requests and observation arrivals.  Every request is answered, and a
  sample of answers matches a float64 reference fit on the data the
  problem held when its wave ran.

train and serve run side by side, on two threads: compiling is most of
either, and the chip holds both.  Every program compiles at
``COMPILE_EFFORT``.

Four chips (``--chips 4``): a ``GPBatch`` of 16 problems of n = 2048, tile
256, sharded over ``make_fleet_mesh(4)``, against the same batch on one
device; the cached factor's shards must lie on 4 distinct devices.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import contextlib
import dataclasses
import json
import math
import os
import sys
import threading
import time

import numpy as np
import scipy.linalg

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Tolerances, fixed before any chip run.  Mean and variance: the CPU
# suite's tiled-vs-monolithic tolerance (tests/test_predict.py).  Gradient:
# the f32 tolerance of tests/test_mll_grad.py.  Sharded vs one device: the
# equivalence tolerance of tests/test_sharded_fleet.py.
PREDICT_ATOL = 1e-3
GRAD_RTOL = 1e-3
MESH_ATOL = 1e-5

# XLA's exec-time optimization effort for every compile of this run.  At
# the default (0) the 16k programs compile for minutes each at up to 14 GB
# of host memory, and a cold run neared both the 1200 s and the 40 GiB
# limits; at -0.5 the fused predict compiles about 4x faster in 5 GB
# (rehearsal).  The programs and their results are the same; their run
# time may not be.
COMPILE_EFFORT = -0.5

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass
class Check:
    """One comparison against a reference: passes when error <= tol."""

    name: str
    error: float
    tol: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.error) and self.error <= self.tol


def _max_abs(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


# ---------------------------------------------------------------------------
# Plain dense references, independent of the tiled pipeline.
#
# The exact phase is held to a dense f32 reference on the same chip: dense
# K, jnp.linalg.cholesky and cho_solve at highest precision.  Compiled for
# a v5e as one program it needs 66 GiB of HBM (the triangular solve against
# the whole 16384 x 16384 cross-covariance), so the variance solves run in
# chunks of test points, and the reference compiles on a worker thread
# while the tiled programs compile.  The gradient and the serve answers
# are held to float64 LAPACK on the host: jax.grad through a 16k dense
# Cholesky compiles for 11 minutes with 38 GB of host memory.  The exact
# phase also reports its distance from the float64 posterior.
# ---------------------------------------------------------------------------

DENSE_CHUNK = 1024  # test points per on-chip variance solve


def _se(a, b, lengthscale, vertical):
    """The repo's SE kernel, v exp(-|a-b|^2 / (2 l)), written out densely."""
    d2 = jnp.sum(a * a, -1)[:, None] + jnp.sum(b * b, -1)[None, :] - 2.0 * a @ b.T
    return vertical * jnp.exp(-0.5 * jnp.maximum(d2, 0.0) / lengthscale)


def _dense_factor(x, y, lengthscale, vertical, noise):
    with jax.default_matmul_precision("highest"):
        k = _se(x, x, lengthscale, vertical)
        idx = jnp.arange(x.shape[0])
        chol = jnp.linalg.cholesky(k.at[idx, idx].set(vertical + noise))
        return chol, jax.scipy.linalg.cho_solve((chol, True), y)


def _dense_chunk(chol, alpha, x, xt, lengthscale, vertical):
    with jax.default_matmul_precision("highest"):
        ks = _se(xt, x, lengthscale, vertical)
        w = jax.scipy.linalg.solve_triangular(chol, ks.T, lower=True)
        return ks @ alpha, vertical - jnp.sum(w * w, axis=0)


def compile_dense_posterior(x, y, xt, lengthscale, vertical, noise):
    """Compile the dense f32 reference for these shapes; returns ``run()``."""
    c = min(DENSE_CHUNK, xt.shape[0])
    args = (lengthscale, vertical, noise)
    factor = jax.jit(_dense_factor).lower(x, y, *args).compile()
    chunk = jax.jit(_dense_chunk).lower(
        jax.ShapeDtypeStruct((x.shape[0], x.shape[0]), np.float32),
        jax.ShapeDtypeStruct((x.shape[0],), np.float32), x, xt[:c],
        lengthscale, vertical,
    ).compile()

    def run():
        chol, alpha = factor(x, y, *args)
        pad = -xt.shape[0] % c
        xtp = np.concatenate([xt, np.zeros((pad, xt.shape[1]), xt.dtype)])
        parts = [chunk(chol, alpha, x, xtp[s:s + c], lengthscale, vertical)
                 for s in range(0, xtp.shape[0], c)]
        n_test = xt.shape[0]
        return (np.concatenate([np.asarray(m) for m, _ in parts])[:n_test],
                np.concatenate([np.asarray(v) for _, v in parts])[:n_test])

    return run


REF_CHUNK = 2048  # test points or K^-1 columns per host solve


def _se64(a, b, lengthscale, vertical):
    """The repo's SE kernel v exp(-|a-b|^2 / (2 l)) and |a-b|^2, in float64."""
    d2 = np.maximum(
        np.sum(a * a, -1)[:, None] + np.sum(b * b, -1)[None, :] - 2.0 * a @ b.T, 0.0
    )
    return vertical * np.exp(-0.5 * d2 / lengthscale), d2


def reference_factor(x, y, lengthscale, vertical, noise):
    """Dense Cholesky factor of K = K_se + noise I and alpha = K^-1 y."""
    x = np.asarray(x, np.float64)
    k = np.empty((x.shape[0], x.shape[0]))
    for s in range(0, x.shape[0], REF_CHUNK):
        k[s:s + REF_CHUNK], _ = _se64(x[s:s + REF_CHUNK], x, lengthscale, vertical)
    k[np.diag_indices_from(k)] = vertical + noise
    chol = scipy.linalg.cholesky(k, lower=True, overwrite_a=True, check_finite=False)
    alpha = scipy.linalg.cho_solve((chol, True), np.asarray(y, np.float64), check_finite=False)
    return chol, alpha


def reference_posterior(x, y, xt, lengthscale, vertical, noise):
    """Posterior mean and variance at ``xt``."""
    x, xt = np.asarray(x, np.float64), np.asarray(xt, np.float64)
    chol, alpha = reference_factor(x, y, lengthscale, vertical, noise)
    mean, var = [], []
    for s in range(0, xt.shape[0], REF_CHUNK):
        ks, _ = _se64(xt[s:s + REF_CHUNK], x, lengthscale, vertical)
        w = scipy.linalg.solve_triangular(chol, ks.T, lower=True, check_finite=False)
        mean.append(ks @ alpha)
        var.append(vertical - np.sum(w * w, axis=0))
    return np.concatenate(mean), np.concatenate(var)


def reference_nlml_grad(x, y, lengthscale, vertical, noise):
    """d NLML / d (lengthscale, vertical, noise) = 0.5 tr((K^-1 - a a^T) dK)."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    chol, alpha = reference_factor(x, y, lengthscale, vertical, noise)
    grad = np.zeros(3)
    for s in range(0, n, REF_CHUNK):
        cols = np.arange(s, min(s + REF_CHUNK, n))
        eye = np.zeros((n, cols.size))
        eye[cols, np.arange(cols.size)] = 1.0
        kinv = scipy.linalg.cho_solve((chol, True), eye, check_finite=False)
        sm = 0.5 * (kinv - np.outer(alpha, alpha[cols]))
        kse, d2 = _se64(x, x[cols], lengthscale, vertical)
        grad += (
            np.sum(sm * kse * d2) / (2.0 * lengthscale**2),
            np.sum(sm * kse) / vertical,
            np.sum(sm[cols, np.arange(cols.size)]),
        )
    return grad


def _param_floats(params):
    return tuple(float(p) for p in (params.lengthscale, params.vertical, params.noise))


# ---------------------------------------------------------------------------
# Phases.  Each returns (checks, info); info is printed beside the checks.
# ---------------------------------------------------------------------------


def exact_phase(x, y, xt, tile):
    """Cold fused predict-with-uncertainty, then warm off the cached factor."""
    from repro.core import GaussianProcess

    params = _param_floats(_default_params())
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        compiling = pool.submit(compile_dense_posterior, x, y, xt, *params)
        truth = pool.submit(reference_posterior, x, y, xt, *params)
        gp = GaussianProcess(x, y, tile_size=tile)
        t0 = time.perf_counter()
        cold = [np.asarray(a) for a in gp.predict_with_uncertainty(xt)]
        t1 = time.perf_counter()
        warm = [np.asarray(a) for a in gp.predict_with_uncertainty(xt)]
        t2 = time.perf_counter()
        del gp
        dense = compiling.result()
        t3 = time.perf_counter()
        ref_mean, ref_var = dense()
        t4 = time.perf_counter()
        f64_mean, f64_var = truth.result()
    checks = [
        Check("cold_mean", _max_abs(cold[0], ref_mean), PREDICT_ATOL),
        Check("cold_var", _max_abs(cold[1], ref_var), PREDICT_ATOL),
        Check("warm_mean", _max_abs(warm[0], ref_mean), PREDICT_ATOL),
        Check("warm_var", _max_abs(warm[1], ref_var), PREDICT_ATOL),
    ]
    info = {
        "n_train": int(x.shape[0]), "n_test": int(xt.shape[0]), "tile": tile,
        "cold_s": t1 - t0, "warm_s": t2 - t1, "dense_run_s": t4 - t3,
        "compile_wait_s": t3 - t2,
        "vs_float64": {
            "cold_mean": _max_abs(cold[0], f64_mean), "cold_var": _max_abs(cold[1], f64_var),
            "warm_mean": _max_abs(warm[0], f64_mean), "warm_var": _max_abs(warm[1], f64_var),
            "dense_f32_mean": _max_abs(ref_mean, f64_mean),
            "dense_f32_var": _max_abs(ref_var, f64_var),
        },
    }
    return checks, info


def _default_params():
    from repro.core import SEKernelParams

    return SEKernelParams.paper_defaults()


def train_phase(x, y, tile, steps=3):
    """Tiled-VJP gradient vs the dense gradient, and a few Adam steps."""
    from repro.core import GaussianProcess, mll

    p0 = _default_params()
    grad_tiled = jax.jit(
        jax.grad(lambda xx, yy, p: mll.nlml_tiled(xx, yy, p, tile_size=tile), argnums=2)
    )
    gp = GaussianProcess(x, y, tile_size=tile)
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        reference = pool.submit(reference_nlml_grad, x, y, *_param_floats(p0))
        t0 = time.perf_counter()
        gp.optimize(steps=steps)
        fitted = np.asarray(_param_floats(gp.params))
        t1 = time.perf_counter()
        g_t = np.asarray(_param_floats(grad_tiled(x, y, p0)))
        t2 = time.perf_counter()
        g_d = reference.result()
    # assert_allclose(g_t, g_d, rtol, atol=rtol * max|g_d|) as a ratio: <= 1 passes
    bound = GRAD_RTOL * np.abs(g_d) + GRAD_RTOL * np.abs(g_d).max()
    checks = [
        Check("grad", float(np.max(np.abs(g_t - g_d) / bound)), 1.0),
        Check("params_finite", 0.0 if np.all(np.isfinite(fitted)) else math.inf, 0.0),
    ]
    info = {
        "n_train": int(x.shape[0]), "tile": tile, "steps": steps,
        "grad_tiled": g_t.tolist(), "grad_reference": g_d.tolist(),
        "params_fitted": fitted.tolist(), "optimize_s": t1 - t0, "grad_s": t2 - t1,
    }
    return checks, info


def serve_phase(
    x_pool, y_pool, xt_pool, *, b=16, n_lo=64, n_hi=2048, tile=128, waves=8,
    arrive=32, per_request=16, n_checked=16, seed=0,
):
    """A ragged fleet under continuous batching, checked against float64.

    Problem i owns a contiguous window of the simulated series: it starts
    with ``n_i`` rows and grows forward in time as observations arrive.
    """
    from repro.core import GPFleet
    from repro.serve import ContinuousBatcher

    rng = np.random.default_rng(seed)
    ns = np.exp(rng.uniform(np.log(n_lo), np.log(n_hi), b)).astype(int)
    room = arrive * waves
    starts = [int(rng.integers(0, x_pool.shape[0] - n - room + 1)) for n in ns]
    held = [int(n) for n in ns]  # rows each problem holds so far
    fleet = GPFleet(
        [x_pool[s:s + n] for s, n in zip(starts, ns)],
        [y_pool[s:s + n] for s, n in zip(starts, ns)],
        tile_size=tile,
    )
    srv = ContinuousBatcher(fleet)
    asked = []  # (rid, problem, rows held at its wave, test rows)
    migrations = 0
    t0 = time.perf_counter()
    for _ in range(waves):
        for i in rng.choice(b, size=max(b // 4, 1), replace=False):
            lo = starts[i] + held[i]
            srv.submit_observe(int(i), x_pool[lo:lo + arrive], y_pool[lo:lo + arrive])
            held[i] += arrive
        for i in range(b):
            r0 = int(rng.integers(0, xt_pool.shape[0] - per_request + 1))
            xt = xt_pool[r0:r0 + per_request]
            asked.append((srv.submit_predict(i, xt, uncertainty=True), i, held[i], xt))
        migrations += srv.step().migrations
    srv.flush()
    t1 = time.perf_counter()

    answers = {}
    for rid, *_ in asked:
        try:
            answers[rid] = srv.result(rid)
        except KeyError:  # never finished
            continue
    unanswered = len(asked) - len(answers)
    finite = all(
        np.all(np.isfinite(m)) and np.all(np.isfinite(v)) and m.shape == (per_request,)
        for m, v in answers.values()
    )
    params = _param_floats(_default_params())
    err_mean = err_var = 0.0
    for k in rng.choice(len(asked), size=min(n_checked, len(asked)), replace=False):
        rid, i, n_held, xt = asked[k]
        if rid not in answers:
            continue
        s = starts[i]
        ref_m, ref_v = reference_posterior(
            x_pool[s:s + n_held], y_pool[s:s + n_held], xt, *params
        )
        err_mean = max(err_mean, _max_abs(answers[rid][0], ref_m))
        err_var = max(err_var, _max_abs(answers[rid][1], ref_v))
    summary = srv.summary()
    checks = [
        Check("unanswered", float(unanswered), 0.0),
        Check("answers_finite", 0.0 if finite else math.inf, 0.0),
        Check("sample_mean", err_mean, PREDICT_ATOL),
        Check("sample_var", err_var, PREDICT_ATOL),
    ]
    info = {
        "b": b, "tile": tile, "waves": waves, "sizes_start": [int(n) for n in ns],
        "sizes_end": list(fleet.sizes), "predict_requests": len(asked),
        "checked": min(n_checked, len(asked)), "migrations": migrations,
        "buckets": {str(c): len(i) for c, i in fleet.bucket_assignment().items()},
        "loop_s": t1 - t0, "p50_ms": summary["p50_ms"], "p99_ms": summary["p99_ms"],
    }
    return checks, info


def fleet_mesh_phase(*, b=16, n=2048, n_test=256, d=16, tile=256, chips=4, seed=0):
    """A GPBatch sharded over ``chips`` devices against the same batch on one."""
    from repro.core import GPBatch
    from repro.launch.mesh import make_fleet_mesh

    rng = np.random.default_rng(seed)
    # features at the NFIR scale (|x - x'|^2 of order 1), a smooth target
    x = (rng.standard_normal((b, n, d)) / np.sqrt(2.0 * d)).astype(np.float32)
    y = (np.sin(3.0 * x.sum(-1)) + 0.1 * rng.standard_normal((b, n))).astype(np.float32)
    xt = (rng.standard_normal((n_test, d)) / np.sqrt(2.0 * d)).astype(np.float32)

    mesh = make_fleet_mesh(chips)
    sharded = GPBatch(x, y, tile_size=tile, mesh=mesh)
    plain = GPBatch(x, y, tile_size=tile)
    t0 = time.perf_counter()
    got = [np.asarray(a) for a in sharded.predict_with_uncertainty(xt)]
    t1 = time.perf_counter()
    want = [np.asarray(a) for a in plain.predict_with_uncertainty(xt)]
    shards = sharded.posterior().lpacked.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted({int(s.data.shape[0]) for s in shards})
    checks = [
        Check("mean_vs_one_device", _max_abs(got[0], want[0]), MESH_ATOL),
        Check("var_vs_one_device", _max_abs(got[1], want[1]), MESH_ATOL),
        Check("shard_devices_missing", float(chips - len(devices)), 0.0),
        Check("shard_rows_off", 0.0 if rows == [b // chips] else math.inf, 0.0),
    ]
    info = {
        "b": b, "n": n, "tile": tile, "chips": chips, "sharded_s": t1 - t0,
        "shard_devices": sorted(str(dv) for dv in devices), "rows_per_shard": rows,
    }
    return checks, info


# ---------------------------------------------------------------------------
# Driver.
# ---------------------------------------------------------------------------


class _CompileClock:
    """Per-thread sums of JAX's lowering and backend-compile durations while
    registered.  A compile on a worker thread a phase starts is not counted
    in the phase's thread; the phase reports the time it waited for one as
    ``compile_wait_s``."""

    def __init__(self):
        self._seconds = collections.defaultdict(float)

    def __call__(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self._seconds[threading.get_ident()] += duration

    @property
    def seconds(self) -> float:
        """Compile seconds counted so far on the calling thread."""
        return self._seconds[threading.get_ident()]

    @contextlib.contextmanager
    def listening(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self)


def _peak_bytes():
    stats = [dv.memory_stats() or {} for dv in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return peaks[0] if len(peaks) == 1 else peaks


def run_phase(name, fn, clock, *args, **kwargs) -> bool:
    """Run one phase, print its JSON line, and return whether it passed."""
    before = clock.seconds
    t0 = time.perf_counter()
    checks, info = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - before + info.get("compile_wait_s", 0.0)
    ok = all(c.ok for c in checks)
    print(json.dumps({
        "phase": name, "ok": ok, "wall_s": wall, "compile_s": compile_s,
        "run_s": wall - compile_s, "peak_bytes_in_use": _peak_bytes(),
        "checks": [dict(dataclasses.asdict(c), ok=c.ok) for c in checks],
        **info,
    }), flush=True)
    return ok


def one_chip_phases(x, y, xt, tile, clock, *, seed=0):
    """exact, then train and serve side by side; returns each phase's pass.

    Compiling is most of both train (two large programs) and serve (many
    small ones), and the chip has room for both, so they overlap: their
    wall and run seconds include each other's contention.
    """
    oks = [run_phase("exact", exact_phase, clock, x, y, xt, tile)]
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        training = pool.submit(run_phase, "train", train_phase, clock, x, y, tile)
        oks.append(run_phase("serve", serve_phase, clock, x, y, xt, seed=seed))
        oks.append(training.result())
    return oks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (jax.devices()[0].platform is "
            f"{devices[0].platform!r})", file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 2

    from repro.compile_cache import use_persistent_cache
    from repro.configs.gp_msd import GP_PAPER_16K
    from repro.data.msd import make_dataset

    jax.config.update("jax_exec_time_optimization_effort", COMPILE_EFFORT)
    print(json.dumps({
        "compile_cache": use_persistent_cache(), "compile_effort": COMPILE_EFFORT,
    }), flush=True)
    clock = _CompileClock()
    results = []
    with clock.listening():
        if args.chips == 4:
            results.append(run_phase("fleet_mesh", fleet_mesh_phase, clock, seed=args.seed))
        else:
            cfg = GP_PAPER_16K
            t0 = time.perf_counter()
            x, y, xt, _ = make_dataset(cfg.n_train, cfg.n_test, seed=args.seed)
            print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0}),
                  flush=True)
            results += one_chip_phases(x, y, xt, cfg.tile_size, clock, seed=args.seed)
    if not all(results):
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
