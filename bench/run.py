"""Run one benchmark cell once on the chip.

    python bench/run.py --workload msd_16k.posterior --seed 7 --seconds 40 --trace 0

Prints progress on standard error, one line of set-up and window counts,
and as its last line on standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``busy_s`` and
``window_s`` too with ``--trace 1``), ``breakdown`` with ``--trace 1``, and
``checks`` (each number compared, beside its limit).  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.  Exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    harness.configure_jax()
    return harness.run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START
    )


if __name__ == "__main__":
    sys.exit(main())
