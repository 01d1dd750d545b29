"""Records the scoped chip trace that ``test_bench_scopes.py`` reduces.

    python tests/bench/record_scoped_trace.py tests/bench/data

Three cold ``GaussianProcess.predict_with_uncertainty`` calls (512 training
and 256 test points, tiles of 128: 4 training and 2 test tiles) under new
hyperparameters each, inside the benchmark's spans (``window``,
``iteration``, ``set_params``, ``front_end_call``, ``block``) and with the
library's spans on (``repro.obs.enable()``).  So the device ops carry the
executor's ``repro.exec.<family>`` scopes and the host plane the library's
``repro.gp.*`` and ``repro.predict.*`` spans.  Copies the ``.xplane.pb`` to
the given directory as ``scoped.xplane.pb`` and prints the device time by
family it holds.
"""

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

import repro.obs as obs  # noqa: E402
from bench import harness, scopes  # noqa: E402
from repro.core import GaussianProcess  # noqa: E402
from repro.core.kernels_math import SEKernelParams  # noqa: E402


def main(dest: str) -> int:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(512, 3)), jnp.float32)
    y = jnp.asarray(np.sin(rng.normal(size=512)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(256, 3)), jnp.float32)
    gp = GaussianProcess(x, y, params=SEKernelParams(1.0, 1.0, 0.1), tile_size=128)
    jax.block_until_ready(gp.predict_with_uncertainty(xt))  # compiles the cold call
    tmp = tempfile.mkdtemp()
    obs.enable()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("window"):
        for i in range(3):
            with TraceAnnotation("iteration"):
                with TraceAnnotation("set_params"):
                    gp.params = SEKernelParams(1.1 + 0.1 * i, 1.0, 0.1)
                with TraceAnnotation("front_end_call"):
                    out = gp.predict_with_uncertainty(xt)
                with TraceAnnotation("block"):
                    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    obs.disable()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(dest, exist_ok=True)
    shutil.copy(path, os.path.join(dest, "scoped.xplane.pb"))
    result = scopes.summarize(path, harness.SPANS)
    print("ops", result.summary.n_ops, "op_s", result.op_s, "busy_s", result.summary.busy_s)
    print("by family", sorted(result.scoped.items()))
    print("spans", {n: len(d) for n, d in result.summary.spans.items()})
    print("gaps", result.summary.gaps)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
