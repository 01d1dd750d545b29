"""Plain dense posterior of v * Matern-5/2-ARD + noise, on the host.

Written from the published equations, with no import from the program under
test and nothing it made.  The covariance is ARBO's residual model
(github.com/ekogl/ARBO, ``arbo_lib/core/residual.py``:
C * Matern(nu = 2.5, length_scale = [l_1 .. l_D]) + White):

    k(x, x') = v (1 + sqrt(5) r + 5 r^2 / 3) exp(-sqrt(5) r),
    r^2      = sum_d (x_d - x'_d)^2 / l_d,

where ``l_d`` scales *squared* distances (the configurations' convention:
sklearn's length_scale 10 is l = 100), ``v`` is C and the noise variance, the
White term, is added on the diagonal of the training covariance.  Distances
are taken as differences, not expanded, so the reference is free of the
cancellation the program's MXU form has.  Float64 throughout.
"""

from __future__ import annotations

import numpy as np
import scipy.linalg
from scipy.linalg import lapack

SQRT5 = np.sqrt(5.0)


def kfree(a, b, lengthscales, vertical) -> np.ndarray:
    """The noise-free covariance between the rows of ``a`` and of ``b``."""
    inv = 1.0 / np.sqrt(np.asarray(lengthscales, np.float64))
    a = np.asarray(a, np.float64) * inv
    b = np.asarray(b, np.float64) * inv
    r2 = np.zeros((a.shape[0], b.shape[0]))
    for d in range(a.shape[1]):
        diff = a[:, d, None] - b[None, :, d]
        r2 += diff * diff
    s = SQRT5 * np.sqrt(r2)
    return vertical * (1.0 + s + s * s / 3.0) * np.exp(-s)


def posterior(x, y, xt, lengthscales, vertical, noise, *, full_cov=True):
    """Posterior mean at ``xt`` and its covariance (or variance)."""
    k = kfree(x, x, lengthscales, vertical)
    k[np.diag_indices_from(k)] = vertical + noise
    chol, info = lapack.dpotrf(k, lower=1, overwrite_a=1, clean=1)
    if info != 0:
        raise np.linalg.LinAlgError(f"reference covariance is not positive definite ({info})")
    alpha = scipy.linalg.cho_solve((chol, True), np.asarray(y, np.float64), check_finite=False)
    kst = kfree(xt, x, lengthscales, vertical)
    mean = kst @ alpha
    w = scipy.linalg.solve_triangular(chol, kst.T, lower=True, check_finite=False)
    if full_cov:
        return mean, kfree(xt, xt, lengthscales, vertical) - w.T @ w
    return mean, vertical - np.sum(w * w, axis=0)
