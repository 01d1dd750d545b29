"""Matérn-5/2 with one lengthscale per input (``matern52_ard``).

Pinned against its formula, against the isotropic Matérn 5/2 at equal
lengthscales, under a joint permutation of inputs and lengthscales, on the
Pallas body's host-baked route, and through the tiled program; the SE-ARD
kernel that shares its distance helper keeps its numbers.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kernels_math as km
from repro.core import predict as pred
from repro.core.gp import GaussianProcess

LS = np.asarray([100.0, 1.0, 100.0])  # ARBO's length_scale [10, 1, 10], squared


def _x(rng, n, scale=(20.0, 1.5, 30.0)):
    return (rng.standard_normal((n, 3)) * np.asarray(scale)).astype(np.float32)


def _formula(a, b, ls, v):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    r2 = (((a[:, None, :] - b[None, :, :]) ** 2) / np.asarray(ls, np.float64)).sum(-1)
    s = np.sqrt(5.0 * r2)
    return v * (1.0 + s + s * s / 3.0) * np.exp(-s)


def test_registered_with_ard_params():
    k = km.get_kernel("matern52_ard", ndim=3)
    assert isinstance(k, km.ARDMatern52) and k.kernel_id() == "matern52_ard3"
    assert k.default_params().lengthscales.shape == (3,)
    assert k != km.ARDSquaredExponential(ndim=3)
    p = km.ARDKernelParams(jnp.ones((5, 3)), jnp.ones(5), jnp.ones(5))
    assert km.params_per_problem(p, k)


def test_matches_its_formula(rng):
    a, b = _x(rng, 9), _x(rng, 7)
    p = km.ARDKernelParams(jnp.asarray(LS, jnp.float32), 1.7, 0.03)
    got = km.ARDMatern52(ndim=3).kfree(p, jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), _formula(a, b, LS, 1.7), rtol=2e-5, atol=2e-6)


def test_equal_lengthscales_give_isotropic_matern52(rng):
    a, b = _x(rng, 8, (1.0, 1.0, 1.0)), _x(rng, 6, (1.0, 1.0, 1.0))
    ard = km.ARDMatern52(ndim=3).kfree(
        km.ARDKernelParams(jnp.full((3,), 2.5), 0.8, 0.1), a, b)
    iso = km.Matern52().kfree(km.SEKernelParams(2.5, 0.8, 0.1), a, b)
    np.testing.assert_allclose(np.asarray(ard), np.asarray(iso), rtol=1e-5, atol=1e-6)


def test_permuting_inputs_with_lengthscales_is_invariant(rng):
    a, b = _x(rng, 8), _x(rng, 5)
    perm = [2, 0, 1]
    k = km.ARDMatern52(ndim=3)
    one = k.kfree(km.ARDKernelParams(jnp.asarray(LS), 1.0, 0.1), a, b)
    two = k.kfree(km.ARDKernelParams(jnp.asarray(LS[perm]), 1.0, 0.1), a[:, perm], b[:, perm])
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), rtol=1e-5, atol=1e-6)


def test_host_baked_route_agrees(rng):
    # the Pallas body sees lengthscales as a float tuple (concrete_params)
    a, b = _x(rng, 8), _x(rng, 8)
    p = km.ARDKernelParams(jnp.asarray(LS, jnp.float32), 1.2, 0.05)
    k = km.ARDMatern52(ndim=3)
    np.testing.assert_allclose(np.asarray(k.kfree(km.concrete_params(p), a, b)),
                               np.asarray(k.kfree(p, a, b)), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("route", ["array", "tuple"])
def test_se_ard_numbers_unchanged(rng, route):
    # the expressions SE-ARD had before it shared the distance helper
    a, b = _x(rng, 7, (1.0, 2.0, 3.0)), _x(rng, 6, (1.0, 2.0, 3.0))
    ls = jnp.asarray([0.7, 1.6, 2.5], jnp.float32)
    p = km.ARDKernelParams(ls, 1.3, 0.1)
    if route == "array":
        inv = 1.0 / jnp.sqrt(ls)
        want = 1.3 * jnp.exp(-0.5 * km.sq_dists(a * inv, b * inv))
    else:
        p = km.concrete_params(p)
        d2 = sum((a[:, d:d + 1] - b[:, d:d + 1].T) ** 2 * (1.0 / l)
                 for d, l in enumerate(p.lengthscales))
        want = 1.3 * jnp.exp(-0.5 * d2)
    got = km.ARDSquaredExponential(ndim=3).kfree(p, a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiled_program_matches_monolithic(rng):
    x = _x(rng, 45)
    y = np.sin(x[:, 1]).astype(np.float32)
    xt = _x(rng, 9)
    k = km.ARDMatern52(ndim=3)
    p = km.ARDKernelParams(jnp.asarray(LS, jnp.float32), 1.0, 0.03)
    mean, cov = GaussianProcess(x, y, params=p, tile_size=16, kernel=k).predict_full_cov(xt)
    ref_m, ref_c = pred.predict_monolithic(x, y, xt, p, full_cov=True, kernel=k)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(ref_m), atol=2e-4)
    np.testing.assert_allclose(np.asarray(cov), np.asarray(ref_c), atol=2e-4)
