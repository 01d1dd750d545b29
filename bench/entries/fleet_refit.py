"""One cold refit round of an ARD fleet per iteration: ``GPFleet.predict_each``.

Each round draws new per-problem hyperparameters from the seed (each of the
D lengthscales, the vertical scale and the noise near the configuration's),
sets them on one ``GPFleet`` with the configuration's ARD kernel, and asks
every problem for the mean and joint covariance at its own candidates
(``full_cov=True``).  The new hyperparameters make every bucket stale, so a
round is every job's cold posterior: the fused ragged program per bucket,
in launches of a bounded width.

The comparison takes ``check_rounds`` rounds of the window, drawn from the
seed, and holds every problem's mean and covariance to the plain float64
reference, so every bucket, ragged frontier and padded launch is covered.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.harness import Check, span

UNITS = 1


def draw_params(ctx, i):
    """(lengthscales (B, D), vertical (B,), noise (B,)), float32, of round ``i``."""
    c, t = ctx.cfg, ctx.traffic
    rng = ctx.rng(1, i + 1)
    b, d = c["problems"], len(c["params"]["lengthscales"])
    ls = np.asarray(c["params"]["lengthscales"]) * np.exp(
        t["lengthscale_spread"] * rng.uniform(-1.0, 1.0, (b, d)))
    v = c["params"]["vertical"] * np.exp(t["vertical_spread"] * rng.uniform(-1.0, 1.0, b))
    s2 = c["params"]["noise"] * np.exp(t["noise_spread"] * rng.uniform(-1.0, 1.0, b))
    return tuple(a.astype(np.float32) for a in (ls, v, s2))


def draw_candidates(ctx, i):
    return ctx.load("data", ctx.cfg["data"]).candidates(ctx.cfg, ctx.rng(3, i + 1))


@jax.jit
def _all_finite(outs):
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(a)) for pair in outs for a in pair]))


def setup(ctx):
    from repro.core import GPFleet, get_kernel
    from repro.core.kernels_math import ARDKernelParams

    cfg = ctx.cfg
    xs, ys = ctx.load("data", cfg["data"]).make_fleet(cfg, ctx.seed)
    kernel = get_kernel(cfg["kernel"], ndim=len(cfg["params"]["lengthscales"]))
    fleet = GPFleet(xs, ys, params=ARDKernelParams(*draw_params(ctx, -1)),
                    tile_size=cfg["tile_size"], kernel=kernel)
    state = {"ctx": ctx, "fleet": fleet, "xs": xs, "ys": ys, "params_cls": ARDKernelParams}
    finite(iterate(state, -1))  # compiles or loads every program the window runs
    return state


def iterate(state, i):
    ctx, fleet = state["ctx"], state["fleet"]
    with span("make_inputs"):
        params = state["params_cls"](*draw_params(ctx, i))
        cands = draw_candidates(ctx, i)
    with span("set_params"):
        fleet.params = params
    with span("front_end_call"):
        out = fleet.predict_each(cands, full_cov=True)
    with span("block"):
        jax.block_until_ready(out)
    return out


def finite(output) -> bool:
    return bool(_all_finite(output))


def fetch(ctx, state, outputs):
    done = sorted(outputs)
    rng = ctx.rng(2)
    rounds = rng.choice(done, size=min(ctx.traffic["check_rounds"], len(done)), replace=False)
    picked = [(int(r), jax.device_get(outputs[r])) for r in rounds]
    return {"xs": state["xs"], "ys": state["ys"], "picked": picked}


def release(state):
    state.clear()


def check(ctx, fetched):
    ref = ctx.load("reference", ctx.cfg["reference"])
    err_mean = err_cov = 0.0
    for r, answers in fetched["picked"]:
        ls, vs, ns = draw_params(ctx, r)
        cands = draw_candidates(ctx, r)
        for k, (mean, cov) in enumerate(answers):
            ref_mean, ref_cov = ref.posterior(fetched["xs"][k], fetched["ys"][k], cands[k],
                                              ls[k], float(vs[k]), float(ns[k]))
            err_mean = max(err_mean, float(np.max(np.abs(mean - ref_mean))))
            err_cov = max(err_cov, float(np.max(np.abs(cov - ref_cov))))
    return [Check("mean_err", err_mean, ctx.limits["mean_err"]),
            Check("cov_err", err_cov, ctx.limits["cov_err"])]


def work(ctx):
    c = ctx.cfg
    flops = nbytes = 0.0
    for n in ctx.load("data", c["data"]).sizes(c):
        f, b = counts.posterior(int(n), c["candidates"], len(c["input_scales"]), full_cov=True)
        flops += f
        nbytes += b
    return flops, nbytes
