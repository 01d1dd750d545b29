"""Synthetic histories for a fleet of ARBO residual models.

ARBO (github.com/ekogl/ARBO, ``arbo_lib/core/residual.py``) fits one GP per
tuned job to the residual of Amdahl's law over three inputs: parallelism
``s``, input scale ``gamma`` and cluster load.  Its kernel is
C * Matern(nu = 2.5, length_scale = [10, 1, 10]) + White, with
``normalize_y``.  The repository has no Matern-ARD kernel, so the fixed
per-dimension scales are folded into the inputs (x / [10, 1, 10]) and the
isotropic Matern 5/2 runs on them; targets are z-scored per problem.

The histories are synthetic (ARBO's are not public): ``s`` an integer in
1..``s_max``, ``gamma`` uniform in [0.5, 4], load uniform in [0, 100] %,
and a smooth residual of all three with Gaussian noise, its coefficients
drawn per problem.  Problem sizes come from the configuration's own seed,
so a run's seed changes values and never shapes.
"""

from __future__ import annotations

import numpy as np

GAMMA = (0.5, 4.0)
LOAD = (0.0, 100.0)


def sizes(cfg) -> np.ndarray:
    """Per-problem history lengths, log-uniform, fixed by ``size_seed``."""
    rng = np.random.default_rng(cfg["size_seed"])
    lo, hi = np.log(cfg["size_min"]), np.log(cfg["size_max"])
    return np.exp(rng.uniform(lo, hi, cfg["problems"])).astype(int).clip(
        cfg["size_min"], cfg["size_max"])


def _fold(s, gamma, load, scales):
    return (np.stack([s, gamma, load], -1) / np.asarray(scales)).astype(np.float32)


def make_fleet(cfg, seed: int):
    """Lists of per-problem inputs (n_i, 3) and z-scored targets (n_i,)."""
    rng = np.random.default_rng([seed % (1 << 63), 7])
    xs, ys = [], []
    for n in sizes(cfg):
        s = rng.integers(1, cfg["s_max"] + 1, n).astype(np.float64)
        gamma = rng.uniform(*GAMMA, n)
        load = rng.uniform(*LOAD, n)
        a, b, c = rng.normal(0.0, 1.0, 3)
        y = (a * np.sin(s / 8.0) * gamma + b * np.log(s) * load / 50.0
             + c * np.cos(gamma) + 0.1 * rng.standard_normal(n))
        y = (y - y.mean()) / (y.std() + 1e-12)
        xs.append(_fold(s, gamma, load, cfg["input_scales"]))
        ys.append(y.astype(np.float32))
    return xs, ys


def candidates(cfg, rng: np.random.Generator):
    """Per-problem candidate sets (q, 3): ``q`` parallelism levels over
    1..``s_max`` at one drawn input scale and cluster load, as ARBO asks."""
    q = cfg["candidates"]
    s = np.linspace(1.0, cfg["s_max"], q)
    out = []
    for _ in range(cfg["problems"]):
        gamma = np.full(q, rng.uniform(*GAMMA))
        load = np.full(q, rng.uniform(*LOAD))
        out.append(_fold(s, gamma, load, cfg["input_scales"]))
    return out
