"""Runs a cell with its control or a planted fault, through the harness's own run.

    python bench/control.py --workload msd_16k.posterior --seeds 1,2,3 --precision high
    python bench/control.py --workload msd_16k.posterior --seeds 1,2,3 --fault unchanged

Each seed is one whole run of the cell, as ``bench/run.py`` makes it, in
this one process: set-up, a window of one iteration, and the cell's
comparison, which prints each number beside its limit and the
result line with ``correct``.  ``--precision high`` is the control: the
program's matmuls at three bf16 passes
(``repro.core.precision.MATMUL_PRECISION``) where the configuration states
HIGHEST.  ``--fault`` plants one fault of ``bench/faults.py`` under the
timed call.  Both must come out with ``correct`` false.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--precision", choices=("highest", "high"), default="highest")
    ap.add_argument("--fault", help="a fault of bench/faults.py for this workload")
    ap.add_argument("--any-platform", action="store_true",
                    help="run without a TPU (a rehearsal; on the CPU HIGH computes as HIGHEST)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import faults, harness
    from repro.core import precision

    if not args.any_platform:
        harness.configure_jax()
    saved = precision.MATMUL_PRECISION
    precision.MATMUL_PRECISION = args.precision
    planted = (faults.planted(args.workload, args.fault) if args.fault
               else contextlib.nullcontext())
    worst = 0
    try:
        with planted:
            for seed in [int(s) for s in args.seeds.split(",")]:
                rc = harness.run_cell(args.root, args.workload, seed, 0.0, False,
                                      require_chip=not args.any_platform)
                worst = max(worst, rc)
    finally:
        precision.MATMUL_PRECISION = saved
    return worst


if __name__ == "__main__":
    sys.exit(main())
