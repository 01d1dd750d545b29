"""Plain dense Gaussian-process reference in float64, on the host.

Written from the published equations, with no import from the program under
test and nothing it made.  The covariance families and their
parameterisation are the configurations':

* ``se``:       k = v exp(-d2 / (2 l))                     (GPRat, Eq. 1)
* ``matern52``: k = v (1 + s + s^2 / 3) exp(-s),  s = sqrt(5 d2 / l)

where d2 is the squared Euclidean distance, ``l`` scales squared distances,
and the noise variance is added on the diagonal of the training covariance.
Every dense matrix is built in row blocks so that n = 16384 fits the host.
"""

from __future__ import annotations

import numpy as np
import scipy.linalg
from scipy.linalg import lapack

BLOCK = 2048  # rows per block of a dense covariance


def sq_dists(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    d2 = np.sum(a * a, 1)[:, None] + np.sum(b * b, 1)[None, :] - 2.0 * a @ b.T
    return np.maximum(d2, 0.0)


def kfree(kernel: str, d2: np.ndarray, lengthscale: float, vertical: float) -> np.ndarray:
    """The noise-free covariance at squared distances ``d2``."""
    if kernel == "se":
        return vertical * np.exp(-0.5 * d2 / lengthscale)
    if kernel == "matern52":
        s = np.sqrt(5.0 * d2 / lengthscale)
        return vertical * (1.0 + s + s * s / 3.0) * np.exp(-s)
    raise KeyError(f"no reference for kernel {kernel!r}")


def train_cov(kernel, x, lengthscale, vertical, noise) -> np.ndarray:
    """K(X, X) + noise I, built in row blocks, diagonal pinned to v + noise."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    k = np.empty((n, n))
    for s in range(0, n, BLOCK):
        k[s:s + BLOCK] = kfree(kernel, sq_dists(x[s:s + BLOCK], x), lengthscale, vertical)
    k[np.diag_indices(n)] = vertical + noise
    return k


def factor(kernel, x, y, lengthscale, vertical, noise):
    """Lower Cholesky factor of the training covariance and alpha = K^-1 y."""
    k = train_cov(kernel, x, lengthscale, vertical, noise)
    chol, info = lapack.dpotrf(k, lower=1, overwrite_a=1, clean=1)
    if info != 0:
        raise np.linalg.LinAlgError(f"reference covariance is not positive definite ({info})")
    alpha = scipy.linalg.cho_solve((chol, True), np.asarray(y, np.float64), check_finite=False)
    return chol, alpha


def posterior(kernel, x, y, xt, lengthscale, vertical, noise, *, full_cov=False):
    """Posterior mean at ``xt`` and its variance (or full covariance)."""
    x = np.asarray(x, np.float64)
    xt = np.asarray(xt, np.float64)
    chol, alpha = factor(kernel, x, y, lengthscale, vertical, noise)
    kst = kfree(kernel, sq_dists(xt, x), lengthscale, vertical)
    mean = kst @ alpha
    w = scipy.linalg.solve_triangular(chol, kst.T, lower=True, check_finite=False)
    if full_cov:
        prior = kfree(kernel, sq_dists(xt, xt), lengthscale, vertical)
        return mean, prior - w.T @ w
    return mean, vertical - np.sum(w * w, axis=0)
