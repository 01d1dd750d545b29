# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: one module per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--n N] [--json PATH]

Emits ``name,us_per_call,derived`` CSV rows on stdout AND writes a
machine-readable ``BENCH_pipeline.json`` (per-figure timings, executor
batch counts, fused-vs-staged pipeline timings) so the perf trajectory is
tracked across PRs.
"""

from __future__ import annotations

import argparse
import json


def _env_header() -> dict:
    """Execution environment stamped into every figure's row header."""
    import jax

    nd = jax.device_count()
    return {
        "devices": nd,
        "backend": jax.default_backend(),
        "mesh_shape": [nd],
        "mesh_axes": ["data"],
    }


class _Collector:
    """Print benchmark rows and keep them for the JSON artifact.

    The execution environment (device count, backend, fleet mesh shape) is
    stamped once at the payload's top level; figure groups carry only their
    ``rows`` — one run means one environment, so per-figure copies would be
    pure duplication."""

    def __init__(self) -> None:
        self.figures: dict = {}
        self._env: dict = None

    @property
    def env(self) -> dict:
        if self._env is None:
            self._env = _env_header()
        return self._env

    def out(self, figure: str):
        group = self.figures.setdefault(figure, {"rows": []})
        rows = group["rows"]

        def _out(line: str) -> None:
            print(line)
            name, us, derived = (line.split(",", 2) + ["", ""])[:3]
            rows.append({"name": name, "us_per_call": float(us), "derived": derived})

        return _out

    def run_fig(self, figure: str, fn, /, *args, **kwargs):
        """Run one figure module, stamping its wall-clock duration and the
        lru-cache tallies (cumulative across the run — per-figure deltas are
        derivable by diffing consecutive groups) into its JSON group."""
        import time

        import repro.obs as obs

        t0 = time.perf_counter()
        result = fn(*args, out=self.out(figure), **kwargs)
        group = self.figures[figure]
        group["duration_s"] = round(time.perf_counter() - t0, 3)
        group["cache_stats"] = obs.cache_stats()
        return result


def _executor_counts(tile_counts=(4, 8, 16), streams=(None, 4, 16)) -> list:
    """Fused-program vs staged batched-launch counts (plan-level, no exec)."""
    from repro.core import executor

    rows = []
    for m_tiles in tile_counts:
        q_tiles = max(m_tiles // 4, 1)
        for unc in (False, True):
            for ns in streams:
                plan = executor.program_plan(m_tiles, q_tiles, unc, ns)
                rows.append({
                    "m_tiles": m_tiles,
                    "q_tiles": q_tiles,
                    "uncertainty": unc,
                    "n_streams": ns,
                    "fused_batches": plan.n_batches,
                    "fused_waves": len(plan.levels),
                    "staged_batches": executor.staged_launch_count(
                        m_tiles, head=unc, n_streams=ns
                    ),
                })
    return rows


def _fused_vs_staged(n: int, out) -> list:
    """Wall-clock of the fused program vs the staged pipeline vs monolithic."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    from benchmarks.common import bench, row
    from repro.core import predict as pred
    from repro.core.kernels_math import SEKernelParams

    rng = np.random.default_rng(0)
    d = 16
    params = SEKernelParams.paper_defaults()
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    xt = jnp.asarray(rng.standard_normal((max(n // 4, 8), d)).astype(np.float32))
    m = max(n // 8, 16)
    results = []
    for full_cov in (False, True):
        timings = {}
        for label, impl in (("fused", pred.predict), ("staged", pred.predict_staged)):
            fn = jax.jit(
                lambda a, b, c, impl=impl, full_cov=full_cov: impl(
                    a, b, c, params, m, full_cov=full_cov
                )
            )
            t, _ = bench(fn, x, y, xt)
            timings[label] = t
            out(row(f"pipeline/{label}/n{n}/m{m}/cov{int(full_cov)}", t))
        mono = jax.jit(
            lambda a, b, c, full_cov=full_cov: pred.predict_monolithic(
                a, b, c, params, full_cov=full_cov
            )
        )
        t, _ = bench(mono, x, y, xt)
        timings["monolithic"] = t
        out(row(
            f"pipeline/monolithic/n{n}/cov{int(full_cov)}", t,
            f"fused_speedup_vs_staged={timings['staged'] / timings['fused']:.3f}",
        ))
        results.append({
            "n": n,
            "m": m,
            "full_cov": full_cov,
            "us_fused": timings["fused"] * 1e6,
            "us_staged": timings["staged"] * 1e6,
            "us_monolithic": timings["monolithic"] * 1e6,
        })
    return results


def main() -> None:
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024, help="problem size for fig3/fig4")
    ap.add_argument("--quick", action="store_true", help="smaller sweeps")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="minimal CI smoke run: tiny sizes, every figure module imported",
    )
    ap.add_argument(
        "--json",
        default="BENCH_pipeline.json",
        help="machine-readable output path ('' disables)",
    )
    args = ap.parse_args()

    from benchmarks import (
        fig3_streams_tiles,
        fig4_breakdown,
        fig5_schedule_trace,
        fig6_cholesky_scaling,
        fig7_predict_scaling,
        fig8_train_scaling,
        fig9_batched_fleet,
        fig10_online_update,
        fig11_ragged_fleet,
        fig12_sharded_fleet,
        fig13_kernel_zoo,
        fig14_lowrank_tradeoff,
        fig15_obs_overhead,
        mem_tiles,
    )

    col = _Collector()
    print("name,us_per_call,derived")
    if args.smoke:
        col.run_fig("fig3", fig3_streams_tiles.run, n=128, tile_counts=(4,), streams=(2, None))
        col.run_fig("fig5", fig5_schedule_trace.run, m_tiles=8)
        col.run_fig("fig6", fig6_cholesky_scaling.run, sizes=(128,))
        col.run_fig("fig8", fig8_train_scaling.run, sizes=(64,))
        fleet = col.run_fig("fig9", fig9_batched_fleet.run, n=128, bs=(1, 4))
        online = col.run_fig("fig10", fig10_online_update.run, ns=(128,), bs=(1, 8))
        ragged = col.run_fig(
            "fig11", fig11_ragged_fleet.run,
            b=8, n_max=96, tile=16, bucket_counts=(1, 2), waves=1, batch=8,
        )
        sharded = col.run_fig(
            "fig12", fig12_sharded_fleet.run, n_total=128, tile=16, bs=(1, 4), n_test=16
        )
        kernel_zoo = col.run_fig(
            "fig13", fig13_kernel_zoo.run, n=96, n_test=16, tile=32, d=4
        )
        lowrank = col.run_fig(
            "fig14", fig14_lowrank_tradeoff.run,
            sizes=(96,), ms=(16, 32), n_test=24, tile=32, d=3,
        )
        obs_overhead = col.run_fig(
            "fig15", fig15_obs_overhead.run,
            n=96, tile=32, d=4, b=4, n_max=64, batch=8, reps=3,
        )
        col.run_fig("mem", mem_tiles.run, n=256)
        pipeline = col.run_fig(
            "pipeline", lambda n, out: _fused_vs_staged(n, out), 128
        )
        counts = _executor_counts(tile_counts=(8,))
    else:
        n = min(args.n, 512) if args.quick else args.n
        col.run_fig("fig3", fig3_streams_tiles.run, n=n)
        col.run_fig("fig4", fig4_breakdown.run, n=n, n_test=n)
        col.run_fig("fig5", fig5_schedule_trace.run, m_tiles=32)
        sizes = (128, 256, 512) if args.quick else (128, 256, 512, 1024, 2048)
        col.run_fig("fig6", fig6_cholesky_scaling.run, sizes=sizes)
        psizes = (128, 256) if args.quick else (128, 256, 512, 1024)
        col.run_fig("fig7", fig7_predict_scaling.run, sizes=psizes)
        tsizes = (128, 256) if args.quick else (128, 256, 512, 1024, 2048)
        col.run_fig("fig8", fig8_train_scaling.run, sizes=tsizes)
        fbs = (1, 2, 4) if args.quick else (1, 2, 4, 8, 16)
        fleet = col.run_fig("fig9", fig9_batched_fleet.run, n=min(n, 256), bs=fbs)
        osizes = (256, 512) if args.quick else (256, 512, 1024)
        online = col.run_fig("fig10", fig10_online_update.run, ns=osizes, bs=(1, 16, 64))
        rb, rn = ((8, 256) if args.quick else (16, 512))
        ragged = col.run_fig("fig11", fig11_ragged_fleet.run, b=rb, n_max=rn, tile=32)
        sharded = col.run_fig(
            "fig12", fig12_sharded_fleet.run,
            n_total=(256 if args.quick else 512),
            bs=(1, 4) if args.quick else (1, 4, 16),
        )
        kernel_zoo = col.run_fig(
            "fig13", fig13_kernel_zoo.run,
            n=(256 if args.quick else 512),
            tile=(32 if args.quick else 64),
        )
        lowrank = col.run_fig(
            "fig14", fig14_lowrank_tradeoff.run,
            sizes=((1024,) if args.quick else (4096, 16384)),
            ms=((64, 128) if args.quick else (64, 128, 256, 512)),
            n_test=(128 if args.quick else 512),
            tile=(64 if args.quick else 256),
        )
        obs_overhead = col.run_fig(
            "fig15", fig15_obs_overhead.run,
            n=(256 if args.quick else 512),
            tile=(32 if args.quick else 64),
            b=6, n_max=(96 if args.quick else 128),
            reps=(5 if args.quick else 10),
        )
        col.run_fig("mem", mem_tiles.run, n=n)
        pipeline = col.run_fig(
            "pipeline", lambda n, out: _fused_vs_staged(n, out), min(n, 512)
        )
        counts = _executor_counts()

    if args.json:
        payload = {
            "env": col.env,
            "figures": col.figures,
            "executor_batches": counts,
            "fused_vs_staged": pipeline,
            "batched_fleet": fleet,
            "online_update": online,
            "ragged_fleet": ragged,
            "sharded_fleet": sharded,
            "kernel_zoo": kernel_zoo,
            "lowrank": lowrank,
            "obs_overhead": obs_overhead,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
