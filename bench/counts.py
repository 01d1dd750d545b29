"""Operations and bytes that the dense GP algorithms need, from shapes alone.

Each count is of the dense algorithm for the output an entry returns,
independent of tiling, padding and any work the implementation adds on top:
a later change to the kernels or the scheduler is read against the same
work.  A FLOP is one multiply or one add; a Cholesky factor of an n x n
matrix is n^3 / 3, a triangular solve with r right-hand sides n^2 r, a
product of an (a x k) and a (k x b) matrix 2 a k b.  Element-wise kernel
evaluations are not counted: they are O(n^2) beside O(n^3).

Bytes are the least traffic the algorithm needs with float32 values: its
inputs and outputs once, and the training covariance's lower triangle
written once and read once.
"""

from __future__ import annotations

F32 = 4


def posterior(n: int, nt: int, d: int, *, full_cov: bool) -> tuple[float, float]:
    """Exact posterior: mean and variance (or full covariance) at nt points."""
    flops = (
        2.0 * n * n * d          # training covariance, distance cross term
        + n**3 / 3.0             # Cholesky factor
        + 2.0 * n * n            # two triangular solves for alpha
        + 2.0 * n * nt * d       # cross covariance
        + 2.0 * n * nt           # mean
        + float(n) * n * nt      # V = L^-1 K(X, X*)
    )
    if full_cov:
        flops += 2.0 * nt * nt * d + 2.0 * n * nt * nt   # prior block and V^T V
        out = nt + nt * nt
    else:
        flops += 2.0 * n * nt                            # diag(V^T V)
        out = 2 * nt
    nbytes = F32 * ((n + nt) * d + n + out) + F32 * n * n
    return flops, float(nbytes)
