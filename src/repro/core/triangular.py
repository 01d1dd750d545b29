"""Tiled triangular solves and tiled matmul helpers for the GP pipeline.

Forward substitution   L b = y        (paper: L beta = y)
Backward substitution  L^T a = b      (paper: L^T alpha = beta)
Matrix forward solve   L V = B        (paper: L V = K_{X,X̂}, for uncertainty)

All operate on the packed symmetric-lower tile store for L (see tiling.py)
and tile stacks for vectors/matrices.  The solves are driven by the same
schedule/executor machinery as the factorization: the scheduler emits the
solve DAG (TRSV diagonal solves, GEMV row propagations), the executor walks
its ASAP levels issuing one batched gather/einsum/scatter per level chunk
(see DESIGN.md §4).  The outer recurrence over tile-rows is inherently
sequential (2M - 1 levels); the inner propagation per level is one batched
matmul — no per-row Python restacking of previously solved chunks.

These standalone entry points are the *staged* path.  In the fused
prediction program (DESIGN.md §7) the same TRSV/GEMV task DAGs are embedded
into the whole-pipeline schedule with cross-stage edges, so solve rows start
the moment their factor tiles resolve instead of waiting for the full
factorization.

All helpers accept an optional leading problem-batch axis B (DESIGN.md §9):
a packed factor (B, T, m, m) with rhs (B, M, m) / (B, M, Q, m, mq) solves B
independent systems through the same lru-cached executor plan.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor, tiling


@functools.lru_cache(maxsize=None)
def _diag_slots(m_tiles: int) -> np.ndarray:
    """Packed slots of the diagonal tiles (i, i)."""
    return np.array(
        [tiling.packed_index(i, i, m_tiles) for i in range(m_tiles)], np.int32
    )


def _solve_lower(lii: jax.Array, rhs: jax.Array, *, transpose: bool = False) -> jax.Array:
    """Solve L x = rhs (or L^T x = rhs) for one diagonal tile; rhs (m,) or (m, k)."""
    vec = rhs.ndim == 1
    b = rhs[:, None] if vec else rhs
    x = jax.lax.linalg.triangular_solve(
        lii, b, left_side=True, lower=True, transpose_a=transpose
    )
    return x[:, 0] if vec else x


def _check_shapes(lpacked: jax.Array, chunks: jax.Array) -> None:
    m_tiles = chunks.shape[1] if lpacked.ndim == 4 else chunks.shape[0]
    assert tiling.num_packed_tiles(m_tiles) == lpacked.shape[-3]


def forward_substitution(
    lpacked: jax.Array, y_chunks: jax.Array, *, n_streams: Optional[int] = None
) -> jax.Array:
    """Solve L b = y.  lpacked: (T, m, m); y_chunks: (M, m) -> b chunks (M, m)."""
    _check_shapes(lpacked, y_chunks)
    return executor.run_solve(lpacked, y_chunks, lower=True, n_streams=n_streams)


def backward_substitution(
    lpacked: jax.Array, b_chunks: jax.Array, *, n_streams: Optional[int] = None
) -> jax.Array:
    """Solve L^T a = b.  Uses tiles (k, i) for k > i: (L^T)_{i,k} = L_{k,i}^T."""
    _check_shapes(lpacked, b_chunks)
    return executor.run_solve(lpacked, b_chunks, lower=False, n_streams=n_streams)


def forward_substitution_matrix(
    lpacked: jax.Array, b_tiles: jax.Array, *, n_streams: Optional[int] = None
) -> jax.Array:
    """Solve L V = B for a tiled matrix RHS.

    b_tiles: (M, Q, m, mq) tile grid of B (n × q).  Returns V tiles (M, Q, m, mq).
    """
    _check_shapes(lpacked, b_tiles)
    return executor.run_solve(lpacked, b_tiles, lower=True, n_streams=n_streams)


def backward_substitution_matrix(
    lpacked: jax.Array, b_tiles: jax.Array, *, n_streams: Optional[int] = None
) -> jax.Array:
    """Solve L^T X = B for a tiled matrix RHS (used by full posterior solve)."""
    _check_shapes(lpacked, b_tiles)
    return executor.run_solve(lpacked, b_tiles, lower=False, n_streams=n_streams)


def tiled_matvec(a_tiles: jax.Array, x_chunks: jax.Array) -> jax.Array:
    """(P, Q, m, mq) tile grid times (Q, mq) chunked vector -> (P, m).

    Batched: (B, P, Q, m, mq) x (B, Q, mq) -> (B, P, m).
    """
    if a_tiles.ndim == 5:
        return jnp.einsum("zpqab,zqb->zpa", a_tiles, x_chunks)
    return jnp.einsum("pqab,qb->pa", a_tiles, x_chunks)


def tiled_gram(v_tiles: jax.Array) -> jax.Array:
    """W = V^T V for V tiles (M, Q, m, mq) -> W tiles (Q, Q, mq, mq).

    Batched: (B, M, Q, m, mq) -> (B, Q, Q, mq, mq).
    """
    if v_tiles.ndim == 5:
        return jnp.einsum("zipab,ziqac->zpqbc", v_tiles, v_tiles)
    return jnp.einsum("ipab,iqac->pqbc", v_tiles, v_tiles)


def tiled_gram_diag(v_tiles: jax.Array) -> jax.Array:
    """diag(V^T V) for V tiles (M, Q, m, mq) -> chunks (Q, mq): the column
    sums of V squared, element-wise — the variance head's O(n n̂) gram.

    Batched: (B, M, Q, m, mq) -> (B, Q, mq).
    """
    return jnp.sum(jnp.square(v_tiles), axis=(-4, -2))


def packed_matvec(
    lpacked: jax.Array, chunks: jax.Array, *, transpose: bool = False
) -> jax.Array:
    """y = L x (or L^T x) against the packed lower factor; chunks (M, m).

    Used by the streaming-update path (DESIGN.md §10) to reconstruct the
    forward-solve chunks beta = L^T alpha (and y = L beta) from posterior
    states that predate the live-state fields.  Batched: (B, T, m, m) x
    (B, M, m) -> (B, M, m).
    """
    batched = lpacked.ndim == 4
    m_tiles = chunks.shape[-2]
    if tiling.num_packed_tiles(m_tiles) != lpacked.shape[-3]:
        raise ValueError(
            f"chunk rows {m_tiles} inconsistent with packed store {lpacked.shape}"
        )
    rows, cols = tiling._packed_coords(m_tiles)
    m = lpacked.shape[-1]
    dense = jnp.zeros(
        lpacked.shape[:-3] + (m_tiles, m_tiles, m, m), lpacked.dtype
    )
    if batched:
        dense = dense.at[:, rows, cols].set(lpacked)
        ein = "zjiba,zjb->zia" if transpose else "zijab,zjb->zia"
    else:
        dense = dense.at[rows, cols].set(lpacked)
        ein = "jiba,jb->ia" if transpose else "ijab,jb->ia"
    return jnp.einsum(ein, dense, chunks.astype(lpacked.dtype))


def identity_tiles(m_tiles: int, m: int, dtype=jnp.float32) -> jax.Array:
    """Identity matrix as an (M, M, m, m) tile grid (matrix-solve RHS layout)."""
    eye = jnp.eye(m, dtype=dtype)
    block_diag = jnp.eye(m_tiles, dtype=dtype)[:, :, None, None]
    return block_diag * eye[None, None]


def kinv_tiles_from_factor(
    lpacked: jax.Array, *, n_streams: Optional[int] = None
) -> jax.Array:
    """K^{-1} tile grid (M, M, m, m) from the packed Cholesky factor.

    Blocked reverse-mode building block (DESIGN.md §8): solve ``L Z = I``
    through the tiled matrix-solve executor (Z = L^{-1} as tile rows), then
    ``K^{-1} = Z^T Z`` via the tiled gram.  O(n^3) like the factorization
    itself — one triangular matrix solve + one gram, instead of autodiff
    back through every wavefront launch.  Identity padding makes the padded
    diagonal block of K^{-1} identity, which callers slice away.
    """
    m_tiles = executor.m_tiles_of_packed(lpacked)
    m = lpacked.shape[-1]
    eye = identity_tiles(m_tiles, m, lpacked.dtype)
    if lpacked.ndim == 4:  # problem-batched factor: one RHS per problem
        eye = jnp.broadcast_to(eye, (lpacked.shape[0],) + eye.shape)
    z = forward_substitution_matrix(lpacked, eye, n_streams=n_streams)
    return tiled_gram(z)


def logdet_from_factor(lpacked: jax.Array, m_tiles: int, n_valid=None) -> jax.Array:
    """log det K = 2 sum_i log diag(L)_i from the packed factor.

    When the factor came out of the masked assembly path its padding is
    exactly identity and contributes log(1) = 0 with no masking.  But a
    factor whose padded diagonal is anything else — a raw ``pack_lower`` of
    a dense matrix with junk past ``n_valid``, or a ragged-batch factor
    where each problem's frontier differs — would silently corrupt the
    log-determinant, so when ``n_valid`` is given the diagonal entries at
    global index >= n_valid are masked to 1 before the log.  ``n_valid``
    may be a scalar or, for batched factors (B, T, m, m), a (B,) array of
    per-problem frontiers.  Batched factors return per-problem
    log-determinants (B,).
    """
    slots = _diag_slots(m_tiles)
    tiles = lpacked[:, slots] if lpacked.ndim == 4 else lpacked[slots]
    diags = jnp.diagonal(tiles, axis1=-2, axis2=-1)  # (..., M, m)
    if n_valid is not None:
        m = lpacked.shape[-1]
        gi = jnp.arange(m_tiles, dtype=jnp.int32)[:, None] * m + jnp.arange(
            m, dtype=jnp.int32
        )[None, :]                                    # (M, m) global indices
        nv = jnp.asarray(n_valid)
        if nv.ndim > 0:                               # per-problem (B,)
            nv = nv[:, None, None]
        diags = jnp.where(gi < nv, diags, jnp.ones((), diags.dtype))
    return 2.0 * jnp.sum(jnp.log(diags), axis=(-2, -1))
