"""GP prediction serving: factor once, serve batched prediction requests.

The paper's workload is inference (predict + uncertainty); the serving shape
is: a trained GP (assembled + factored covariance, device-resident) answering
batches of prediction requests at low latency.

Built on the fused-program `GaussianProcess` API (DESIGN.md §7): the offline
phase is one cold fused predict (ONE multi-stage program that also populates
the posterior cache), and the online loop is a jitted warm tail
(`predict_from_state` — cross covariance + mean off the cached factor).

``--fleet B`` serves B independent GPs through `GPBatch` (DESIGN.md §9):
one problem-batched program factors the whole fleet, and each online batch
answers B × batch requests in a single launch sequence — compare its
req/s against the single-GP numbers to see the wavefront-width win.

``--ragged B`` serves B GPs of *different* sizes through `GPFleet` + the
continuous-batching loop (DESIGN.md §11): problems are bucketed by tile
geometry, each wave drains a mixed queue of prediction and observation
requests (one ragged fused launch per occupied bucket), and buckets are
re-formed between waves as problems grow and migrate.

``--online`` turns the server into a *streaming* one (DESIGN.md §10):
prediction requests interleave with observation arrivals, absorbed by
`GaussianProcess.update` — the O(n^2 b) block Cholesky append — under a
`sliding_window` cap that evicts the oldest tile when the window overflows.
It reports the served latency alongside update-vs-full-refactorization
latency, the number the streaming subsystem exists to shrink.

    PYTHONPATH=src python examples/serve_gp.py [--n 4096] [--batches 32]
    PYTHONPATH=src python examples/serve_gp.py --fleet 8 --n 512
    PYTHONPATH=src python examples/serve_gp.py --online --n 1024 --arrive 32
    PYTHONPATH=src python examples/serve_gp.py --ragged 12 --n 512 --tile 64

``--metrics out.jsonl`` enables `repro.obs` telemetry (DESIGN.md §15) for
the run and streams every event — executor wave dispatches, `serve.wave`
records, factorization-health incidents, the predict-path counters (warm
tails, ``predict.head.variance`` / ``.covariance`` dispatches), a final
lru-cache snapshot — to a JSON-lines file:

    PYTHONPATH=src python examples/serve_gp.py --ragged 8 --metrics metrics.jsonl
"""

import argparse
import time

import jax
import numpy as np

import repro.obs as obs
from repro.compile_cache import use_persistent_cache
from repro.core import GaussianProcess, GPBatch, GPFleet
from repro.core import predict as pred
from repro.data.msd import MSDConfig, make_dataset, nfir_features, simulate
from repro.serve import ContinuousBatcher


def request_batches(cfg, batch, batches, seed0=100):
    """Fresh NFIR feature batches simulating online prediction requests."""
    for i in range(batches):
        u, y = simulate(batch + cfg.n_regressors - 1, cfg, seed=seed0 + i)
        xt, _ = nfir_features(u, y, cfg.n_regressors)
        yield xt.astype(np.float32)


def report(label, lat, requests):
    lat = np.asarray(lat[1:]) * 1e3  # drop the jit-compile batch
    print(
        f"{label}: p50={np.percentile(lat, 50):.2f}ms "
        f"p99={np.percentile(lat, 99):.2f}ms "
        f"({requests / np.median(lat) * 1e3:.0f} req/s)"
    )


def serve_single(args, cfg):
    x_tr, y_tr, _, _ = make_dataset(args.n, 1, cfg, seed=0)

    # ---- offline: ONE cold fused predict factors + caches the posterior ---
    t0 = time.perf_counter()
    gp = GaussianProcess(x_tr, y_tr, tile_size=args.tile)
    warm_probe = next(request_batches(cfg, args.batch, 1))
    jax.block_until_ready(gp.predict(warm_probe))
    print(f"fused factor+cache (offline): {time.perf_counter() - t0:.2f}s for n={args.n}")

    # ---- online: jitted warm tail off the cached PosteriorState -----------
    state = gp.posterior()
    serve = jax.jit(lambda xt: pred.predict_from_state(state, xt))
    lat = []
    for xt in request_batches(cfg, args.batch, args.batches):
        t0 = time.perf_counter()
        jax.block_until_ready(serve(xt))
        lat.append(time.perf_counter() - t0)
    report(f"served {args.batches} batches x {args.batch} requests", lat, args.batch)


def serve_fleet(args, cfg):
    b = args.fleet
    xs, ys = [], []
    for i in range(b):
        x_tr, y_tr, _, _ = make_dataset(args.n, 1, cfg, seed=i)
        xs.append(x_tr)
        ys.append(y_tr)
    x_stack = np.stack(xs)
    y_stack = np.stack(ys)

    # ---- offline: ONE problem-batched program factors the whole fleet -----
    t0 = time.perf_counter()
    fleet = GPBatch(x_stack, y_stack, tile_size=args.tile)
    warm_probe = next(request_batches(cfg, args.batch, 1))
    jax.block_until_ready(fleet.predict(warm_probe))  # shared block broadcast
    print(
        f"fleet fused factor+cache (offline): {time.perf_counter() - t0:.2f}s "
        f"for B={b} x n={args.n}"
    )

    # ---- online: every request batch is answered for ALL B GPs at once ----
    state = fleet.posterior()
    serve = jax.jit(lambda xt: pred.predict_from_state_batched(state, xt))
    lat = []
    for xt in request_batches(cfg, args.batch, args.batches):
        stacked = np.broadcast_to(xt, (b,) + xt.shape)
        t0 = time.perf_counter()
        jax.block_until_ready(serve(stacked))
        lat.append(time.perf_counter() - t0)
    report(
        f"served {args.batches} batches x {args.batch} requests x B={b} GPs",
        lat,
        args.batch * b,
    )


def serve_ragged(args, cfg):
    """Continuous batching over a ragged fleet (DESIGN.md §11).

    B problems with a skewed size mix (most small, a heavy tail up to --n)
    share bucketed fused programs; every wave mixes prediction requests with
    observation arrivals, so problems grow — and migrate buckets — live."""
    rng = np.random.default_rng(7)
    b = args.ragged
    # skewed mix: sizes log-uniform in [tile/2, n] — many small, few large
    lo, hi = max(args.tile // 2, 8), max(args.n, args.tile)
    ns = np.exp(rng.uniform(np.log(lo), np.log(hi), b)).astype(int)
    xs, ys = [], []
    for i, n in enumerate(ns):
        x_tr, y_tr, _, _ = make_dataset(int(n), 1, cfg, seed=i)
        xs.append(x_tr)
        ys.append(y_tr)

    t0 = time.perf_counter()
    fleet = GPFleet(xs, ys, tile_size=args.tile)
    srv = ContinuousBatcher(fleet)
    warm_probe = next(request_batches(cfg, args.batch, 1))
    jax.block_until_ready(fleet.predict(warm_probe))  # factor every bucket
    caps = {c: len(i) for c, i in fleet.bucket_assignment().items()}
    print(
        f"ragged fleet factor+cache (offline): {time.perf_counter() - t0:.2f}s "
        f"for B={b}, sizes {int(ns.min())}..{int(ns.max())}, buckets {caps}"
    )

    migrations = 0
    for w, xt in enumerate(request_batches(cfg, args.batch, args.batches)):
        # every wave: each problem gets a slice of the request batch ...
        splits = np.array_split(np.arange(xt.shape[0]), b)
        for i, rows in enumerate(splits):
            if rows.size:
                srv.submit_predict(i, xt[rows])
        # ... and a few problems receive labelled arrivals
        for i in rng.choice(b, size=max(b // 4, 1), replace=False):
            u, yv = simulate(args.arrive + cfg.n_regressors - 1, cfg, seed=5000 + 97 * w + i)
            x_new, y_new = nfir_features(u, yv, cfg.n_regressors)
            srv.submit_observe(int(i), x_new.astype(np.float32), y_new.astype(np.float32))
        stats = srv.step()
        migrations += stats.migrations
    srv.flush()  # fetch the last wave's one-wave-late dispatched results
    s = srv.summary()
    print(
        f"ragged: served {int(s['requests'])} requests in {int(s['waves'])} waves "
        f"(p50={s['p50_ms']:.2f}ms p99={s['p99_ms']:.2f}ms, {s['req_per_s']:.0f} req/s)"
    )
    print(
        f"ragged: {migrations} bucket migrations, final sizes "
        f"{min(fleet.sizes)}..{max(fleet.sizes)}, buckets "
        f"{ {c: len(i) for c, i in fleet.bucket_assignment().items()} }"
    )


def serve_online(args, cfg):
    """Streaming serving: requests interleave with observation arrivals."""
    x_tr, y_tr, _, _ = make_dataset(args.n, 1, cfg, seed=0)

    gp = GaussianProcess(
        x_tr, y_tr, tile_size=args.tile, sliding_window=args.n
    )
    warm_probe = next(request_batches(cfg, args.batch, 1))
    t0 = time.perf_counter()
    jax.block_until_ready(gp.predict(warm_probe))
    print(f"fused factor+cache (offline): {time.perf_counter() - t0:.2f}s for n={args.n}")

    # one full refit of the same window (the jitted fused q_tiles=0
    # program — the honest O(n^3) baseline), warmed before timing
    def refit():
        env, _ = pred.nlml_program_env(gp.x_train, gp.y_train, gp.params, args.tile)
        return env["alpha"]

    jax.block_until_ready(refit())
    t0 = time.perf_counter()
    jax.block_until_ready(refit())
    t_refit = time.perf_counter() - t0

    serve_lat, upd_lat = [], []
    for i, xt in enumerate(request_batches(cfg, args.batch, args.batches)):
        t0 = time.perf_counter()
        jax.block_until_ready(gp.predict(xt))
        serve_lat.append(time.perf_counter() - t0)
        # observation arrivals: the request batch's first rows come back
        # labelled; absorb them under the sliding window
        u, yv = simulate(args.arrive + cfg.n_regressors - 1, cfg, seed=1000 + i)
        x_new, y_new = nfir_features(u, yv, cfg.n_regressors)
        t0 = time.perf_counter()
        gp.update(x_new.astype(np.float32), y_new.astype(np.float32))
        jax.block_until_ready(gp.posterior().alpha)
        upd_lat.append(time.perf_counter() - t0)
    report(f"online: served {args.batches} batches x {args.batch}", serve_lat, args.batch)
    upd = np.asarray(upd_lat[1:]) * 1e3
    print(
        f"online: absorbed {args.arrive} obs/batch in p50={np.percentile(upd, 50):.2f}ms "
        f"p99={np.percentile(upd, 99):.2f}ms vs full refactorize {t_refit * 1e3:.2f}ms "
        f"({t_refit * 1e3 / np.percentile(upd, 50):.1f}x)"
    )
    assert gp.y_train.shape[0] <= args.n, "sliding window must cap the set"


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--tile", type=int, default=512)
    ap.add_argument("--batch", type=int, default=256, help="requests per batch")
    ap.add_argument("--batches", type=int, default=32)
    ap.add_argument(
        "--fleet",
        type=int,
        default=0,
        metavar="B",
        help="serve B independent GPs through one GPBatch program",
    )
    ap.add_argument(
        "--ragged",
        type=int,
        default=0,
        metavar="B",
        help="serve B differently-sized GPs through GPFleet + continuous batching",
    )
    ap.add_argument(
        "--online",
        action="store_true",
        help="interleave observation arrivals with requests (streaming updates)",
    )
    ap.add_argument(
        "--arrive", type=int, default=32, help="observations arriving per batch (--online/--ragged)"
    )
    ap.add_argument(
        "--metrics",
        metavar="OUT.jsonl",
        default=None,
        help="enable repro.obs telemetry and stream events to a JSONL file",
    )
    args = ap.parse_args()

    if args.metrics:
        obs.enable(args.metrics)
    cfg = MSDConfig()
    try:
        if args.ragged > 0:
            serve_ragged(args, cfg)
        elif args.online:
            serve_online(args, cfg)
        elif args.fleet > 0:
            serve_fleet(args, cfg)
        else:
            serve_single(args, cfg)
        if args.metrics:
            # health + cache tallies ride along as final events so the JSONL
            # is self-contained (no second file for the snapshot)
            snap = obs.snapshot()
            obs.event(
                "serve.health",
                counters={
                    k: v for k, v in snap["counters"].items()
                    if k.startswith("health.")
                },
            )
            # how often each predict path ran: warm tails, uncertainty heads
            obs.event(
                "serve.predict",
                counters={
                    k: v for k, v in snap["counters"].items()
                    if k.startswith("predict.")
                },
            )
            obs.event("obs.cache_stats", caches=obs.cache_stats())
            print(f"metrics: wrote {len(obs.registry().events)}+ events to {args.metrics}")
    finally:
        if args.metrics:
            obs.disable()


if __name__ == "__main__":
    main()
