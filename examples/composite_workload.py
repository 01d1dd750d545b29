"""Composite kernels through the tiled pipeline: C * Matern52 + White.

The ARBO-style surrogate — an output-scaled Matérn 5/2 plus an explicit
white-noise term — built from the kernel zoo's composition algebra
(DESIGN.md §13), trained via the tiled NLML (autodiff VJP fallback), and
served through a predict-observe-update loop where each round's new
observations are absorbed online by the block Cholesky append (no
re-factorization).  `repro.obs` telemetry (DESIGN.md §15) is on for the
whole run; the tail prints what the loop actually did — warm vs cold
posterior builds, executor dispatches, factorization-health incidents,
and the plan/jit lru-cache tallies.

    PYTHONPATH=src python examples/composite_workload.py
"""

import numpy as np

import repro.obs as obs
from repro.compile_cache import use_persistent_cache
from repro.core import GaussianProcess, Matern52, Scaled, Sum, White

use_persistent_cache()
obs.enable()

rng = np.random.default_rng(0)


def f(x):  # the function being surrogate-modeled
    return np.sin(3.0 * x[:, 0]) * np.cos(2.0 * x[:, 1])


x_train = rng.uniform(-1, 1, (192, 2)).astype(np.float32)
y_train = (f(x_train) + 0.05 * rng.standard_normal(192)).astype(np.float32)
x_test = rng.uniform(-1, 1, (128, 2)).astype(np.float32)

# kernel algebra: Sum / Product / Scaled compose over nested params pytrees;
# the composite is hashable, so it keys the jit and posterior caches directly
kernel = Sum(Scaled(Matern52()), White())
gp = GaussianProcess(x_train, y_train, tile_size=64, kernel=kernel)

mean, var = gp.predict_with_uncertainty(x_test)
err = np.abs(np.asarray(mean) - f(x_test))
print(f"untrained composite:  mae={err.mean():.4f}  "
      f"avg std={np.sqrt(np.asarray(var)).mean():.4f}")

# tiled NLML + Adam over the full params pytree (scale, Matérn, noise leaves)
gp.optimize(steps=60, lr=0.1)
mean, var = gp.predict_with_uncertainty(x_test)
err = np.abs(np.asarray(mean) - f(x_test))
print(f"after NLML training:  mae={err.mean():.4f}  "
      f"avg std={np.sqrt(np.asarray(var)).mean():.4f}")

# predict-observe-update: each round streams fresh observations into the
# cached factor via the tiled block Cholesky append
for round_idx in range(3):
    x_new = rng.uniform(-1, 1, (32, 2)).astype(np.float32)
    y_new = (f(x_new) + 0.05 * rng.standard_normal(32)).astype(np.float32)
    gp.update(x_new, y_new)
    mean, _ = gp.predict_with_uncertainty(x_test)
    err = np.abs(np.asarray(mean) - f(x_test))
    print(f"round {round_idx}: n={gp.y_train.shape[0]}  mae={err.mean():.4f}")

# what the loop did, from the telemetry registry (DESIGN.md §15)
snap = obs.snapshot()
c = snap["counters"]
print(
    f"obs: posterior cache warm={c.get('cache.posterior.warm', 0):.0f} "
    f"cold={c.get('cache.posterior.cold', 0):.0f}, executor dispatches="
    f"{sum(v for k, v in c.items() if k.startswith('executor.dispatch.')):.0f}, "
    f"health incidents={sum(v for k, v in c.items() if k.startswith('health.')):.0f}"
)
print("obs: cache stats:")
for name, st in obs.cache_stats().items():
    if st["hits"] or st["misses"]:
        print(f"  {name}: hits={st['hits']} misses={st['misses']} size={st['size']}")
obs.disable()
