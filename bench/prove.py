"""Runs one cell several times, one process per run, as the benchmark's check does.

    python bench/prove.py --workload msd_16k.posterior --seeds 11,12,13 \
        --seconds 40 --traces 0,0,1 --out chiprun_out/posterior

Each run is ``python3 bench/run.py ...`` in a child process (this process
never touches JAX, so the child owns the chip).  Its standard output and
error go to ``<out>/<workload>.<seed>.<trace>.{out,err}``; one summary line
per run is printed: exit code, wall seconds, the set-up and window counts
line, and the result line.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traces", default="0", help="comma-separated 0/1, cycled over the seeds")
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1500.0)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    traces = [int(t) for t in args.traces.split(",")]
    worst = 0
    for k, seed in enumerate(seeds):
        trace = traces[k % len(traces)]
        stem = out / f"{args.workload}.{seed}.{trace}"
        cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", args.workload,
               "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(trace)]
        t0 = time.perf_counter()
        with open(f"{stem}.out", "w") as fo, open(f"{stem}.err", "w") as fe:
            try:
                rc = subprocess.run(cmd, stdout=fo, stderr=fe, cwd=ROOT,
                                    timeout=args.timeout).returncode
            except subprocess.TimeoutExpired:
                rc = 124
        wall = time.perf_counter() - t0
        lines = Path(f"{stem}.out").read_text().strip().splitlines()
        info = [json.loads(l) for l in lines if l.startswith("{")]
        print(json.dumps({"seed": seed, "trace": trace, "rc": rc, "wall_s": wall,
                          "lines": info[-2:]}), flush=True)
        if rc:
            print(Path(f"{stem}.err").read_text()[-3000:], file=sys.stderr, flush=True)
        worst = max(worst, rc)
    return worst


if __name__ == "__main__":
    sys.exit(main())
