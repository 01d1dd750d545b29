"""Records the small chip trace that ``test_bench_trace.py`` reduces.

    python tests/bench/record_trace.py tests/bench/data

Three iterations of a small jitted program inside the benchmark's spans
(``window``, ``iteration``, ``front_end_call``, ``block``), with a host
sleep between dispatch and wait so that the device shows an idle gap
labelled ``front_end_call``.  Copies the ``.xplane.pb`` to the given
directory as ``small.xplane.pb`` and prints the planes and lines it holds.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


def main(dest: str) -> int:
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    a = jnp.ones((2048, 2048), jnp.float32)
    f(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("iteration"):
                with TraceAnnotation("front_end_call"):
                    r = f(a)
                    time.sleep(0.02)
                with TraceAnnotation("block"):
                    r.block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(dest, exist_ok=True)
    shutil.copy(path, os.path.join(dest, "small.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        print(plane.name, [(l.name, sum(1 for _ in l.events)) for l in plane.lines])
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in list(line.events)[:5]:
                    print(plane.name, "|", line.name, "|", ev.name, ev.start_ns, ev.duration_ns)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
