"""Regression: the softplus re-parameterization must round-trip extreme values.

Pre-fix, ``mll._pack`` used ``log(expm1(p))`` directly: ``expm1`` overflows
float32 at p ≈ 90 (inf -> inf raw values, NaN gradients), and a hard 1e-6
floor silently distorted any hyperparameter below it.  The fixed inverse
softplus branches at p = 20 — ``log(expm1(p))`` below, the asymptotically
exact ``p + log1p(-exp(-p))`` above — so the whole f32 range [1e-8, 1e6]
round-trips through pack -> unpack.

The sweep is a seeded log-uniform property (the ``hypothesis`` package is
optional in this environment; the explicit grid + random sweep below covers
the same space deterministically).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import kernels_math as km
from repro.core import mll


# endpoints, the old overflow knee (~90), the branch point (20), and a
# seeded log-uniform sweep across the full range
def _values(n=64, seed=5):
    rng = np.random.default_rng(seed)
    sweep = 10.0 ** rng.uniform(-8, 6, size=n)
    return np.concatenate(
        [[1e-8, 1e-6, 1.0, 19.5, 20.0, 20.5, 89.0, 95.0, 1e3, 1e6], sweep]
    )


def test_roundtrip_float32():
    v = jnp.asarray(_values(), jnp.float32)
    back = mll.unpack_params(mll.pack_params(v))
    assert back.dtype == jnp.float32
    np.testing.assert_allclose(back, v, rtol=3e-6, atol=0)


def test_roundtrip_float64():
    with compat.enable_x64():
        v = jnp.asarray(_values(), jnp.float64)
        back = mll.unpack_params(mll.pack_params(v))
        assert back.dtype == jnp.float64
        np.testing.assert_allclose(back, v, rtol=1e-12, atol=0)


def test_no_overflow_above_old_knee():
    """p >~ 90 used to produce inf raw values (expm1 overflow in f32)."""
    v = jnp.asarray([95.0, 1e3, 1e6], jnp.float32)
    raw = mll.pack_params(v)
    assert np.isfinite(np.asarray(raw)).all()
    # large p: softplus^-1(p) ~= p; the raw value must track it, not clamp
    np.testing.assert_allclose(raw, v, rtol=1e-5)


def test_tiny_values_not_floored():
    """Values below the old 1e-6 floor must survive (no silent distortion)."""
    v = jnp.asarray([1e-8, 5e-8, 1e-7], jnp.float32)
    back = np.asarray(mll.unpack_params(mll.pack_params(v)))
    assert np.isfinite(back).all()
    np.testing.assert_allclose(back, v, rtol=3e-6)


def test_gradients_finite_across_range():
    g = jax.vmap(jax.grad(lambda r: mll.unpack_params(r)))(
        mll.pack_params(jnp.asarray(_values(), jnp.float32))
    )
    assert np.isfinite(np.asarray(g)).all()


def test_pack_roundtrip_over_params_pytree():
    """pack/unpack are tree_maps: composite kernel params round-trip whole."""
    kern = km.Sum(km.Scaled(km.Matern52()), km.White())
    p = kern.default_params()
    back = mll.unpack_params(mll.pack_params(p))
    for a, b in zip(jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(back)):
        np.testing.assert_allclose(b, a, rtol=3e-6)


def test_stacked_se_pack_api_unchanged():
    """The legacy stacked (…, 3) SE raw layout still round-trips bit-for-bit
    with the generic path on each column."""
    p = km.SEKernelParams(lengthscale=2.0, vertical=0.5, noise=1e-4)
    raw = mll._pack(p)
    assert raw.shape == (3,)
    back = mll._unpack(raw)
    np.testing.assert_allclose(back.lengthscale, 2.0, rtol=3e-6)
    np.testing.assert_allclose(back.vertical, 0.5, rtol=3e-6)
    np.testing.assert_allclose(back.noise, 1e-4, rtol=3e-6)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=30, deadline=None)
    @given(v=st.floats(1e-8, 1e6))
    def test_property_roundtrip(v):
        x = jnp.asarray(v, jnp.float32)
        back = mll.unpack_params(mll.pack_params(x))
        np.testing.assert_allclose(back, x, rtol=3e-6)
except ImportError:  # pragma: no cover - the explicit sweep above stands in
    pass
