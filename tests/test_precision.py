"""Every matmul on the main path runs at ``precision.MATMUL_PRECISION``.

A TPU runs an f32 dot at DEFAULT precision as one bf16 pass; the CPU never
shows it.  So this test records the precision of every ``dot_general`` the
front-ends bind while they trace and run — cold and warm predict, the
tiled NLML gradient, training, streaming updates and a serving wave — and
requires all of them to be at ``precision.MATMUL_PRECISION``.
"""

import jax
import numpy as np

from repro.core import GaussianProcess, GPFleet, mll, precision
from repro.serve import ContinuousBatcher


def test_main_path_matmuls_run_at_the_set_precision(monkeypatch):
    seen = []
    prim = jax.lax.dot_general_p
    bind = prim.bind

    def recording_bind(*args, **params):
        seen.append(params.get("precision"))
        return bind(*args, **params)

    jax.clear_caches()  # every program below must trace afresh
    monkeypatch.setattr(prim, "bind", recording_bind)

    rng = np.random.default_rng(0)
    x = rng.standard_normal((100, 4)).astype(np.float32)
    y = rng.standard_normal(100).astype(np.float32)
    xt = rng.standard_normal((37, 4)).astype(np.float32)
    gp = GaussianProcess(x, y, tile_size=32)
    gp.predict_with_uncertainty(xt)  # cold: the fused program
    gp.predict_with_uncertainty(xt)  # warm: the op-by-op tail
    loss = lambda p: mll.nlml_tiled(x, y, p, tile_size=32)  # noqa: E731
    jax.grad(loss)(gp.params)
    jax.jit(jax.grad(loss))(gp.params)
    gp.optimize(steps=1)
    gp.predict(xt)
    gp.update(x[:10], y[:10])
    gp.predict(xt)

    xs = [rng.standard_normal((n, 4)).astype(np.float32) for n in (20, 50, 90)]
    ys = [rng.standard_normal(len(a)).astype(np.float32) for a in xs]
    srv = ContinuousBatcher(GPFleet(xs, ys, tile_size=32))
    for wave in range(2):
        for i in range(3):
            srv.submit_predict(i, xt[:5], uncertainty=True)
        srv.submit_observe(wave, xs[2][:40], ys[2][:40])
        srv.step()
    srv.flush()

    want = jax.lax.Precision[precision.MATMUL_PRECISION.upper()]
    assert len(seen) > 50
    assert [p for p in seen if p != (want, want)] == []
