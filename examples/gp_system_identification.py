"""The paper's end-to-end use case: system identification of a coupled
mass-spring-damper chain with a tiled, device-resident GP.

    PYTHONPATH=src python examples/gp_system_identification.py [--n 2048]
"""

import argparse
import time

import numpy as np

from repro.compile_cache import use_persistent_cache
from repro.core import GaussianProcess
from repro.data.msd import MSDConfig, make_dataset


def main():
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048, help="training samples")
    ap.add_argument("--n-test", type=int, default=512)
    ap.add_argument("--tile", type=int, default=256)
    ap.add_argument("--backend", choices=["jnp", "pallas"], default="jnp")
    args = ap.parse_args()

    cfg = MSDConfig()
    print(f"simulating MSD chain: {cfg.n_masses} masses, D={cfg.n_regressors} regressors")
    x_tr, y_tr, x_te, y_te = make_dataset(args.n, args.n_test, cfg, seed=0)

    gp = GaussianProcess(x_tr, y_tr, tile_size=args.tile, op_backend=args.backend)

    t0 = time.perf_counter()
    mean, var = gp.predict_with_uncertainty(x_te)
    mean = np.asarray(mean)
    t1 = time.perf_counter()

    mse = float(np.mean((mean - y_te) ** 2))
    r2 = 1 - mse / float(np.var(y_te))
    sd = np.sqrt(np.asarray(var) + float(gp.params.noise))
    cover = float(np.mean(np.abs(mean - y_te) < 2 * sd))
    print(f"n={args.n} tiles/dim={args.n // args.tile}  predict+uncertainty "
          f"wall: {t1 - t0:.2f}s (includes jit)")
    print(f"r2 = {r2:.3f}   2-sigma coverage = {cover:.2%}")

    # monolithic (cuSOLVER-analogue) cross-check
    gp_m = GaussianProcess(x_tr, y_tr, pipeline="monolithic")
    mu_m = np.asarray(gp_m.predict(x_te))
    print(f"max |tiled - monolithic| = {np.abs(mean - mu_m).max():.2e}")


if __name__ == "__main__":
    main()
