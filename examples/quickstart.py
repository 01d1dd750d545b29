"""Quickstart: tiled GP regression in five lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import use_persistent_cache
from repro.core import GaussianProcess, SEKernelParams

use_persistent_cache()
rng = np.random.default_rng(0)
x_train = rng.uniform(-3, 3, (256, 1)).astype(np.float32)
y_train = np.sin(x_train[:, 0]) + 0.1 * rng.standard_normal(256).astype(np.float32)
x_test = np.linspace(-3, 3, 100)[:, None].astype(np.float32)

# The paper's pipeline: tiled covariance assembly -> tiled Cholesky ->
# triangular solves -> predictive mean + uncertainty, one device program.
gp = GaussianProcess(x_train, y_train, tile_size=64)
mean, var = gp.predict_with_uncertainty(x_test)

err = np.abs(np.asarray(mean) - np.sin(x_test[:, 0]))
print(f"mean abs error vs ground truth: {err.mean():.4f}")
print(f"avg predictive std:             {np.sqrt(np.asarray(var)).mean():.4f}")

# hyperparameter optimization (beyond the paper's fixed values)
gp.optimize(steps=50, lr=0.1)
mean2, _ = gp.predict_with_uncertainty(x_test)
err2 = np.abs(np.asarray(mean2) - np.sin(x_test[:, 0]))
print(f"after NLML optimization:        {err2.mean():.4f}  params={gp.params}")

# Large n: method="lowrank" swaps the O(n^3) exact solver for the O(n m^2)
# Nystrom tier (DESIGN.md §14) — same API, m_inducing controls the
# accuracy/speed trade-off (benchmarks/fig14_lowrank_tradeoff.py charts it).
n_big = 8192
x_big = rng.uniform(-3, 3, (n_big, 1)).astype(np.float32)
y_big = np.sin(x_big[:, 0]) + 0.1 * rng.standard_normal(n_big).astype(np.float32)
gp_lr = GaussianProcess(x_big, y_big, tile_size=256, method="lowrank", m_inducing=256)
mean_lr = gp_lr.predict(x_test)
err_lr = np.abs(np.asarray(mean_lr) - np.sin(x_test[:, 0]))
print(f"lowrank (n=8192, m=256):        {err_lr.mean():.4f}")
