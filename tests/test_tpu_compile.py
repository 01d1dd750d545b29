"""Compile rehearsals of the main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a v5e that is described and not attached.  That finds
what interpret mode cannot — a kernel Mosaic refuses to lower, a program
that does not fit the chip's 16 GiB — at no chip time.  The topology is
described inside a module-scoped fixture, never at import, because only
one process may hold the TPU library at a time.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import executor
from repro.core import kernels_math as km
from repro.core import mll
from repro.core import predict as pred
from repro.core.scheduler import Head
from repro.kernels.trailing_update import trailing_update

V5E_HBM_BYTES = 16 * 2**30
N, TILE, D = 1024, 256, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache while this file runs
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(sharding):
    return km.SEKernelParams(*(_spec(sharding, ()) for _ in range(3)))


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
    return 0 < used < V5E_HBM_BYTES


def test_fused_predict_compiles(one_chip):
    fn = pred._fused_program_fn(Head.COVARIANCE, None, "jnp", None, N, N,
                                kernel=km.resolve_kernel(None))
    m_tiles = N // TILE
    compiled = fn.lower(
        _spec(one_chip, (m_tiles, TILE, D)),
        _spec(one_chip, (m_tiles, TILE)),
        _spec(one_chip, (m_tiles, TILE, D)),
        _params(one_chip),
    ).compile()
    assert _fits(compiled)


def test_fused_predict_ops_carry_their_family(one_chip):
    # every op the chip runs that came from the executor's plan names its op
    # family (``repro.exec.<op>``): the profiler trace reads it as ``tf_op``
    fn = pred._fused_program_fn(Head.COVARIANCE, None, "jnp", None, N, N,
                                kernel=km.resolve_kernel(None))
    m_tiles = N // TILE
    text = fn.lower(
        _spec(one_chip, (m_tiles, TILE, D)),
        _spec(one_chip, (m_tiles, TILE)),
        _spec(one_chip, (m_tiles, TILE, D)),
        _params(one_chip),
    ).compile().as_text()
    plan = executor.program_plan(m_tiles, m_tiles, Head.COVARIANCE, None)
    families = {bt.op for level in plan.levels for bt in level}
    assert set(re.findall(r"repro\.exec\.(\w+)", text)) == families
    work = [line for line in text.splitlines()
            if re.search(r" (convolution|dot|custom-call|cholesky|triangular-solve)\(", line)
            and "op_name=" in line]
    assert len(work) > 20
    assert all("repro.exec." in line for line in work)


def test_fused_variance_predict_compiles_smaller(one_chip):
    # the variance head holds no n̂ x n̂ buffer, so it needs less device
    # memory than the covariance head of the same size; it carries every
    # op family of its plan
    m_tiles = N // TILE
    args = (_spec(one_chip, (m_tiles, TILE, D)), _spec(one_chip, (m_tiles, TILE)),
            _spec(one_chip, (m_tiles, TILE, D)), _params(one_chip))
    used = {}
    for head in (Head.VARIANCE, Head.COVARIANCE):
        fn = pred._fused_program_fn(head, None, "jnp", None, N, N,
                                    kernel=km.resolve_kernel(None))
        compiled = fn.lower(*args).compile()
        assert _fits(compiled)
        ma = compiled.memory_analysis()
        used[head] = ma.temp_size_in_bytes + ma.output_size_in_bytes
        if head == Head.VARIANCE:
            plan = executor.program_plan(m_tiles, m_tiles, head, None)
            families = {bt.op for level in plan.levels for bt in level}
            assert set(re.findall(r"repro\.exec\.(\w+)", compiled.as_text())) == families
    # at least the n̂ x n̂ f32 covariance less the n̂ variances
    assert used[Head.COVARIANCE] - used[Head.VARIANCE] >= (N * N - N) * 4


def test_tiled_nlml_grad_compiles(one_chip):
    grad = jax.jit(
        jax.grad(lambda x, y, p: mll.nlml_tiled(x, y, p, tile_size=TILE), argnums=2)
    )
    compiled = grad.lower(
        _spec(one_chip, (N, D)), _spec(one_chip, (N,)), _params(one_chip)
    ).compile()
    assert _fits(compiled)


@pytest.mark.parametrize("tile", [256, 512])
def test_trailing_update_kernel_lowers(one_chip, tile):
    stack = _spec(one_chip, (8, tile, tile))
    fn = jax.jit(lambda c, a, b: trailing_update(c, a, b, interpret=False))
    compiled = fn.lower(stack, stack, stack).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)
