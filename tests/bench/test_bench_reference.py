"""The plain references the comparisons hold the program to."""

import numpy as np
import pytest

from bench.reference import gp_dense, gp_dense_f32


def _data(n=120, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)) / 3.0
    return x, np.sin(x.sum(1)) + 0.1 * rng.standard_normal(n)


@pytest.mark.parametrize("kernel", ["se", "matern52"])
def test_posterior_by_direct_inverse(kernel):
    x, y = _data(80)
    xt = _data(7, seed=1)[0]
    l, v, s2 = 0.8, 1.3, 0.05
    mean, cov = gp_dense.posterior(kernel, x, y, xt, l, v, s2, full_cov=True)
    k = gp_dense.train_cov(kernel, x, l, v, s2)
    kst = gp_dense.kfree(kernel, gp_dense.sq_dists(xt, x), l, v)
    kss = gp_dense.kfree(kernel, gp_dense.sq_dists(xt, xt), l, v)
    kinv = np.linalg.inv(k)
    np.testing.assert_allclose(mean, kst @ kinv @ y, atol=1e-9)
    np.testing.assert_allclose(cov, kss - kst @ kinv @ kst.T, atol=1e-9)
    _, var = gp_dense.posterior(kernel, x, y, xt, l, v, s2)
    np.testing.assert_allclose(var, np.diag(cov), atol=1e-12)


@pytest.mark.parametrize("kernel", ["se", "matern52"])
def test_float32_reference_agrees_with_float64(kernel):
    x, y = _data(100)
    xt = _data(9, seed=2)[0]
    want = gp_dense.posterior(kernel, x, y, xt, 0.8, 1.3, 0.05)
    got = gp_dense_f32.posterior(kernel, x, y, xt, 0.8, 1.3, 0.05)
    np.testing.assert_allclose(got[0], want[0], atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)


def test_float32_reference_ignores_row_order():
    # mean_rel's noise floor: the same posterior on permuted training rows
    x, y = _data(100)
    xt = _data(9, seed=2)[0]
    perm = np.random.default_rng(3).permutation(len(y))
    want = gp_dense_f32.posterior("se", x, y, xt, 0.8, 1.3, 0.05)
    got = gp_dense_f32.posterior("se", x[perm], y[perm], xt, 0.8, 1.3, 0.05)
    np.testing.assert_allclose(got[0], want[0], atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)
