"""Plain dense Gaussian-process reference in float32 on the device, at HIGHEST.

The same equations as ``gp_dense`` (float64, host), in straightforward
``jax.numpy``: the dense training covariance, ``jnp.linalg.cholesky`` and
triangular solves, every matmul at ``precision="highest"``.  The variance
solve runs in blocks of test points so that n = 16384 fits one chip.  No
import from the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1024  # test points per triangular solve


def _d2(a, b):
    cross = jnp.matmul(a, b.T, precision="highest")
    return jnp.maximum(jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :] - 2.0 * cross, 0.0)


def _kfree(kernel, d2, l, v):
    if kernel == "se":
        return v * jnp.exp(-0.5 * d2 / l)
    if kernel == "matern52":
        s = jnp.sqrt(5.0 * d2 / l)
        return v * (1.0 + s + s * s / 3.0) * jnp.exp(-s)
    raise KeyError(f"no reference for kernel {kernel!r}")


@functools.partial(jax.jit, static_argnums=0)
def _factor(kernel, x, y, l, v, s2):
    with jax.default_matmul_precision("highest"):
        return _factor_body(kernel, x, y, l, v, s2)


def _factor_body(kernel, x, y, l, v, s2):
    n = x.shape[0]
    k = _kfree(kernel, _d2(x, x), l, v)
    k = k.at[jnp.arange(n), jnp.arange(n)].set(v + s2)
    chol = jnp.linalg.cholesky(k)
    z = jax.scipy.linalg.solve_triangular(chol, y, lower=True)
    return chol, jax.scipy.linalg.solve_triangular(chol.T, z, lower=False)


@functools.partial(jax.jit, static_argnums=(0, 7))
def _block(kernel, chol, alpha, x, xt, l, v, full_cov):
    with jax.default_matmul_precision("highest"):
        return _block_body(kernel, chol, alpha, x, xt, l, v, full_cov)


def _block_body(kernel, chol, alpha, x, xt, l, v, full_cov):
    kst = _kfree(kernel, _d2(xt, x), l, v)
    mean = jnp.matmul(kst, alpha, precision="highest")
    w = jax.scipy.linalg.solve_triangular(chol, kst.T, lower=True)
    if full_cov:
        prior = _kfree(kernel, _d2(xt, xt), l, v)
        return mean, prior - jnp.matmul(w.T, w, precision="highest")
    return mean, v - jnp.sum(w * w, axis=0)


def posterior(kernel, x, y, xt, lengthscale, vertical, noise, *, full_cov=False):
    """Posterior mean and variance (or covariance) at ``xt``, as numpy float32."""
    args = tuple(jnp.float32(a) for a in (lengthscale, vertical, noise))
    chol, alpha = _factor(kernel, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), *args)
    x = jnp.asarray(x, jnp.float32)
    if full_cov:
        m, c = _block(kernel, chol, alpha, x, jnp.asarray(xt, jnp.float32), args[0], args[1], True)
        return np.asarray(m), np.asarray(c)
    means, variances = [], []
    for s in range(0, xt.shape[0], BLOCK):
        m, var = _block(kernel, chol, alpha, x, jnp.asarray(xt[s:s + BLOCK], jnp.float32),
                        args[0], args[1], False)
        means.append(np.asarray(m))
        variances.append(np.asarray(var))
    return np.concatenate(means), np.concatenate(variances)
