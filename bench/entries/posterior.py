"""A cold exact posterior per iteration: ``GaussianProcess.predict_with_uncertainty``.

Each iteration sets new hyperparameters, drawn near the configuration's
from the seed, on one ``GaussianProcess`` and asks for the mean and variance
at all ``n_test`` points, so the whole pipeline runs: covariance assembly,
factorization, both substitutions, the mean and the variance heads.

The comparison takes ``check_iterations`` iterations of the window and
``check_points`` of their test points, drawn from the seed, and holds their
mean and variance to the reference the configuration names: the dense
float32 posterior on the chip at HIGHEST (``gp_dense_f32``).  At 16k the
chip's f32 arithmetic puts the program and that reference alike about 1e-3 from a
float64 posterior, while the program at HIGH precision lands another 1e-3
away from both; only a reference with the same arithmetic tells the two
apart (PERF.md).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.harness import Check, span

UNITS = 1


def draw_params(ctx, i):
    """Hyperparameters of iteration ``i``: the configuration's, each scaled
    by exp(u), u uniform in +-``param_spread``."""
    p = ctx.cfg["params"]
    u = ctx.rng(1, i + 1).uniform(-1.0, 1.0, 3) * ctx.traffic["param_spread"]
    return tuple(float(v * np.exp(e)) for v, e in zip(
        (p["lengthscale"], p["vertical"], p["noise"]), u))


def setup(ctx):
    from repro.core import GaussianProcess
    from repro.core.kernels_math import SEKernelParams

    cfg = ctx.cfg
    data = ctx.load("data", cfg["data"])
    x, y, xt, _ = data.make_dataset(cfg["n_train"], cfg["n_test"], cfg["n_regressors"],
                                    ctx.seed % (1 << 32))
    gp = GaussianProcess(jnp.asarray(x), jnp.asarray(y), params=SEKernelParams(*draw_params(ctx, -1)),
                         tile_size=cfg["tile_size"], kernel=cfg["kernel"])
    state = {"ctx": ctx, "gp": gp, "x": x, "y": y, "xt_host": xt, "xt": jnp.asarray(xt),
             "params_cls": SEKernelParams}
    iterate(state, -1)  # compiles or loads every program the window runs
    return state


def iterate(state, i):
    ctx, gp = state["ctx"], state["gp"]
    with span("set_params"):
        gp.params = state["params_cls"](*draw_params(ctx, i))
    with span("front_end_call"):
        mean, var = gp.predict_with_uncertainty(state["xt"])
    with span("block"):
        jax.block_until_ready((mean, var))
    return mean, var


def finite(output) -> bool:
    return bool(jnp.all(jnp.isfinite(output[0])) & jnp.all(jnp.isfinite(output[1])))


def fetch(ctx, state, outputs):
    """The sampled iterations' answers at the sampled points, on the host."""
    done = sorted(outputs)
    rng = ctx.rng(2)
    its = rng.choice(done, size=min(ctx.traffic["check_iterations"], len(done)), replace=False)
    pts = np.sort(rng.choice(ctx.cfg["n_test"], size=ctx.traffic["check_points"], replace=False))
    picked = [(int(i), np.asarray(outputs[i][0])[pts], np.asarray(outputs[i][1])[pts])
              for i in its]
    return {"x": state["x"], "y": state["y"], "xt": state["xt_host"][pts], "picked": picked}


def release(state):
    state.clear()


def check(ctx, fetched):
    """``mean_rms``, ``mean_rel`` and ``var_err`` of the sampled answers.

    ``mean_rel`` is ``mean_rms`` over the f32 noise of this very problem:
    the RMS gap between the reference and the reference on the training
    rows permuted, the same posterior in another order of rounding.  The
    conditioning, which moves with the data and the hyperparameters, scales
    the program's error and that noise alike (PERF.md section 2).
    """
    ref = ctx.load("reference", ctx.cfg["reference"])
    x, y = fetched["x"], fetched["y"]
    perm = ctx.rng(3).permutation(len(y))
    rms_mean = rel_mean = err_var = 0.0
    for i, mean, var in fetched["picked"]:
        l, v, s2 = draw_params(ctx, i)
        ref_mean, ref_var = ref.posterior(ctx.cfg["kernel"], x, y, fetched["xt"], l, v, s2)
        alt_mean, _ = ref.posterior(ctx.cfg["kernel"], x[perm], y[perm], fetched["xt"], l, v, s2)
        gap = np.asarray(mean, np.float64) - ref_mean
        rms = float(np.sqrt(np.mean(gap * gap)))
        noise = float(np.sqrt(np.mean((np.asarray(alt_mean, np.float64) - ref_mean) ** 2)))
        rms_mean = max(rms_mean, rms)
        rel_mean = max(rel_mean, rms / noise if noise > 0 else math.inf)
        err_var = max(err_var, float(np.max(np.abs(np.asarray(var, np.float64) - ref_var))))
    return [Check("mean_rms", rms_mean, ctx.limits["mean_rms"]),
            Check("mean_rel", rel_mean, ctx.limits["mean_rel"]),
            Check("var_err", err_var, ctx.limits["var_err"])]


def work(ctx):
    c = ctx.cfg
    return counts.posterior(c["n_train"], c["n_test"], c["n_regressors"], full_cov=False)
