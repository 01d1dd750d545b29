"""One refit round of a ragged fleet per iteration: ``GPFleet.predict_each``.

Each round sets new per-problem hyperparameters, drawn near the
configuration's from the seed, on one ``GPFleet`` and asks every problem for
the mean and joint covariance at its own candidates (``full_cov=True``).  The
new hyperparameters invalidate every bucket, so each round refactors all
problems: stacking, the per-problem-params assembly, the bucket programs and
the warm heads.

The comparison takes ``check_rounds`` rounds of the window, drawn from the
seed, and holds every problem's mean and covariance to the float64
reference, so every bucket and every ragged ``n_valid`` frontier is covered.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.harness import Check, span

UNITS = 1


def draw_params(ctx, i):
    """(lengthscale, vertical, noise), each (B,) float32, of round ``i``."""
    p, t = ctx.cfg["params"], ctx.traffic
    rng = ctx.rng(1, i + 1)
    b = ctx.cfg["problems"]
    out = []
    for name, spread in (("lengthscale", t["param_spread"]), ("vertical", t["param_spread"]),
                         ("noise", t["noise_spread"])):
        out.append((p[name] * np.exp(spread * rng.uniform(-1.0, 1.0, b))).astype(np.float32))
    return tuple(out)


def draw_candidates(ctx, i):
    return ctx.load("data", ctx.cfg["data"]).candidates(ctx.cfg, ctx.rng(3, i + 1))


@jax.jit
def _all_finite(outs):
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(a)) for pair in outs for a in pair]))


def setup(ctx):
    from repro.core import GPFleet
    from repro.core.kernels_math import SEKernelParams

    cfg = ctx.cfg
    xs, ys = ctx.load("data", cfg["data"]).make_fleet(cfg, ctx.seed)
    fleet = GPFleet(xs, ys, params=SEKernelParams(*draw_params(ctx, -1)),
                    tile_size=cfg["tile_size"], kernel=cfg["kernel"])
    state = {"ctx": ctx, "fleet": fleet, "xs": xs, "ys": ys, "params_cls": SEKernelParams}
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
    picked = [(int(r), [(np.asarray(m), np.asarray(c)) for m, c in outputs[r]])
              for r in rounds]
    return {"xs": state["xs"], "ys": state["ys"], "picked": picked}


def release(state):
    state.clear()


def check(ctx, fetched):
    ref = ctx.load("reference", ctx.cfg["reference"])
    kernel = ctx.cfg["kernel"]
    err_mean = err_cov = 0.0
    for r, answers in fetched["picked"]:
        ls, vs, ns = draw_params(ctx, r)
        cands = draw_candidates(ctx, r)
        for k, (mean, cov) in enumerate(answers):
            ref_mean, ref_cov = ref.posterior(
                kernel, fetched["xs"][k], fetched["ys"][k], cands[k],
                float(ls[k]), float(vs[k]), float(ns[k]), full_cov=True)
            err_mean = max(err_mean, float(np.max(np.abs(mean - ref_mean))))
            err_cov = max(err_cov, float(np.max(np.abs(cov - ref_cov))))
    return [Check("mean_err", err_mean, ctx.limits["mean_err"]),
            Check("cov_err", err_cov, ctx.limits["cov_err"])]


def work(ctx):
    c = ctx.cfg
    data = ctx.load("data", c["data"])
    flops = nbytes = 0.0
    for n in data.sizes(c):
        f, b = counts.posterior(int(n), c["candidates"], len(c["input_scales"]), full_cov=True)
        flops += f
        nbytes += b
    return flops, nbytes
