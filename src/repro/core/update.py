"""Streaming posterior maintenance: block Cholesky append / evict (DESIGN.md §10).

The serving story of the paper (and GPRat's) assumes a fixed training set:
absorbing one new observation forces a full O(n^3) re-factorization.  This
module turns the cached :class:`repro.core.predict.PosteriorState` into a
*live* artifact:

* :func:`extend_state` absorbs b new observations in O(n^2 b) by growing
  the packed factor one tile-row at a time (the append DAG of
  ``scheduler.append_tasks``, executed by ``executor.run_append``).  A
  partially padded trailing tile is refilled in place first — padding
  always stays at the very end, which is what keeps the scalar ``n_valid``
  masking of the assembly kernels exact.
* :func:`shrink_state` evicts the k *oldest* observations (sliding-window
  semantics) in O(n^2 k) — dropping the leading tile-column of a factor is
  a *positive* rank-m update of the trailing block
  (K22 = L21 L21^T + L22 L22^T), run as the blocked cholupdate sweep of
  ``executor.run_rank_update``.  ``sign=-1`` of the same sweep is the true
  hyperbolic downdate; both share the positivity guardrail below.

Posterior maintenance rides along: the forward-solve chunks beta are
extended incrementally (prefix rows of a grown triangular system never
change), and alpha is re-solved with ONE O(n^2) backward substitution —
``predict`` after an update never re-runs the O(n^3) program.

Numerical stability: every public entry point validates the refreshed
factor/weights for NaNs (a failed Cholesky head — e.g. a non-PD downdate —
surfaces as NaN) and raises :class:`CholeskyUpdateError`; callers
(``GaussianProcess.update`` / ``forget``) catch it and fall back to a full
refactorization.  The f64 path flows through unchanged via the state dtype.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import executor, precision, tiling, triangular


def _record_step(kind: str, plan, backend: str, batched: bool, operand) -> None:
    """Dispatch-boundary record for the jitted step fns (DESIGN.md §15).

    The jnp backend jits each step, so executor.run_append/run_rank_update
    only execute at trace time there — the per-dispatch record happens
    here, where ``operand`` is concrete.  The Pallas backend runs the steps
    unjitted and records inside the executor entry points instead.
    """
    if obs.enabled() and backend == "jnp" \
            and not isinstance(operand, jax.core.Tracer):
        executor.record_dispatch(kind, plan, backend=backend, batched=batched)


class CholeskyUpdateError(RuntimeError):
    """The incremental factor update went numerically bad (NaN heads).

    Raised after the fact — the returned state would be poisoned — so
    callers can fall back to a full refactorization of the grown/shrunk
    dataset (the established O(n^3) path)."""


# ---------------------------------------------------------------------------
# jitted step functions (lru-cached per static geometry/config, like
# predict._fused_program_fn; the Pallas backend runs unjitted since its
# assembly bakes hyperparameters and n_valid in as compile-time constants).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _append_step_fn(
    r_tiles: int,
    m_store: int,
    grow: bool,
    n_streams: Optional[int],
    backend: str,
    update_dtype,
    batched: bool,
    batch_dispatch: str,
    mesh=None,
    kernel=None,
):
    """One tile-row append: solve the row, repack the store, extend beta.

    Returns ``fn(lpacked, xc, yc, beta, x_row, y_row, params, n_valid_new)
    -> (lpacked', xc', yc', beta')`` where the primed buffers hold the
    grown (or refilled-in-place) factor and chunk stacks.  ``kernel`` is
    the state's covariance family (hashable — part of the lru key).
    """

    def fn(lpacked, xc, yc, beta, x_row, y_row, params, n_valid_new):
        row = executor.run_append(
            lpacked,
            xc,
            x_row,
            params,
            r_tiles,
            n_valid_new,
            n_streams=n_streams,
            backend=backend,
            update_dtype=update_dtype,
            batch_dispatch=batch_dispatch,
            mesh=mesh,
            kernel=kernel,
        )
        # beta_R = corner^{-1} (y_row - sum_{j<R} row_j beta_j): the prefix
        # of a grown forward-triangular system never changes.
        z = "z" if batched else ""
        off = (slice(None),) if batched else ()
        s = jnp.einsum(
            f"{z}jab,{z}jb->{z}a", row[off + (slice(0, r_tiles),)],
            beta[off + (slice(0, r_tiles),)],
        )
        corner = row[off + (r_tiles,)]
        rhs = (y_row - s).astype(corner.dtype)[..., None]
        beta_new = jax.lax.linalg.triangular_solve(
            corner, rhs, left_side=True, lower=True
        )[..., 0]
        if grow:
            idx = tiling.grow_packed_indices(m_store)
            store = jnp.concatenate([lpacked, row], axis=-3)
            lpacked = store[:, idx] if batched else store[idx]
            xc = jnp.concatenate(
                [xc, x_row[:, None] if batched else x_row[None]], axis=-3
            )
            yc = jnp.concatenate(
                [yc, y_row[:, None] if batched else y_row[None]], axis=-2
            )
            beta = jnp.concatenate(
                [beta, beta_new[:, None] if batched else beta_new[None]], axis=-2
            )
        else:
            slots = tiling.replace_row_indices(r_tiles, m_store)
            lpacked = (
                lpacked.at[:, slots].set(row) if batched
                else lpacked.at[slots].set(row)
            )
            xc = xc.at[off + (r_tiles,)].set(x_row)
            yc = yc.at[off + (r_tiles,)].set(y_row)
            beta = beta.at[off + (r_tiles,)].set(beta_new)
        return lpacked, xc, yc, beta

    fn = precision.f32_matmuls(fn)
    return jax.jit(fn) if backend == "jnp" else fn


@functools.lru_cache(maxsize=None)
def _evict_step_fn(
    m_tiles: int, n_streams: Optional[int], backend: str, batch_dispatch: str,
    mesh=None,
):
    """Drop the leading tile-column: positive rank-m update of the trailing
    factor (K22 = L21 L21^T + L22 L22^T)."""
    trailing, evicted = tiling.shrink_packed_indices(m_tiles)

    def fn(lpacked):
        batched = lpacked.ndim == 4
        w = lpacked[:, evicted] if batched else lpacked[evicted]
        sub = lpacked[:, trailing] if batched else lpacked[trailing]
        new_packed, _ = executor.run_rank_update(
            sub,
            w,
            sign=1.0,
            n_streams=n_streams,
            backend=backend,
            batch_dispatch=batch_dispatch,
            mesh=mesh,
        )
        return new_packed

    fn = precision.f32_matmuls(fn)
    return jax.jit(fn) if backend == "jnp" else fn


@functools.lru_cache(maxsize=None)
def _resolve_fn(n_streams: Optional[int], forward: bool):
    """Jitted O(n^2) re-solve of the weight chunks off a fresh factor.

    ``forward=False``: beta is given, return alpha only (append path).
    ``forward=True``: solve beta from y chunks too (evict path)."""

    def fn(lpacked, chunks):
        beta = (
            triangular.forward_substitution(lpacked, chunks, n_streams=n_streams)
            if forward
            else chunks
        )
        alpha = triangular.backward_substitution(lpacked, beta, n_streams=n_streams)
        return beta, alpha

    return jax.jit(precision.f32_matmuls(fn))


def _check(state_arrays, what: str) -> None:
    flat = jnp.concatenate([jnp.ravel(a) for a in state_arrays])
    if bool(jnp.any(jnp.isnan(flat))):
        obs.health_event("nan_guard_trip", what=what)
        raise CholeskyUpdateError(
            f"incremental {what} produced NaNs (non-positive-definite head); "
            "fall back to a full refactorization"
        )


def _live_chunks(state) -> Tuple[jax.Array, jax.Array]:
    """(beta, y_chunks), reconstructing pre-§10 states from the factor:
    beta = L^T alpha and y = L beta are two O(n^2) packed matvecs."""
    beta = state.beta
    if beta is None:
        beta = triangular.packed_matvec(state.lpacked, state.alpha, transpose=True)
    yc = state.y_chunks
    if yc is None:
        yc = triangular.packed_matvec(state.lpacked, beta, transpose=False)
    return beta, yc


# ---------------------------------------------------------------------------
# Public entry points.
# ---------------------------------------------------------------------------


def extend_state(
    state,
    x_new: jax.Array,
    y_new: jax.Array,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    batch_dispatch: str = "flat",
    check_finite: bool = True,
    mesh=None,
):
    """Absorb new observations into a cached posterior in O(n^2 b).

    x_new (b, D) / y_new (b,) — or stacked (B, b, D) / (B, b) for a fleet
    state (every problem absorbs the same count b, keeping the shared tile
    geometry that makes the fleet one program).  Returns a new
    :class:`~repro.core.predict.PosteriorState`; the input state is
    unchanged (jax arrays are immutable — states are cheap snapshots).

    The append walks tile-row by tile-row: a partially padded trailing tile
    is refilled first (recomputing only that row), then whole new rows are
    appended, each one O(n^2 m) — never a full refactorization.  beta grows
    incrementally; alpha is re-solved with one O(n^2) backward substitution
    at the end.
    """
    from repro.core import predict as pred  # cycle: predict imports update

    batched = state.x_chunks.ndim == 4
    m = state.m
    dtype = state.x_chunks.dtype
    x_new = jnp.asarray(x_new, dtype)
    y_new = jnp.asarray(y_new, dtype)
    if x_new.ndim == (2 if batched else 1):  # 1-D problem convenience
        x_new = x_new[..., None]
    want = 3 if batched else 2
    d = state.x_chunks.shape[-1]
    if (
        x_new.ndim != want
        or x_new.shape[-1] != d
        or y_new.shape != x_new.shape[:-1]
    ):
        raise ValueError(
            f"x_new must be {'(B, b, D)' if batched else '(b, D)'} with "
            f"D == {d} and matching y_new; got x {tuple(x_new.shape)}, "
            f"y {tuple(y_new.shape)}"
        )
    b_total = x_new.shape[-2]
    if b_total == 0:
        return state

    lpacked, xc, yc = state.lpacked, state.x_chunks, state.y_chunks
    beta, yc_live = _live_chunks(state)
    yc = yc_live
    n = state.n
    consumed = 0
    off = (slice(None),) if batched else ()
    while consumed < b_total:
        r = n % m
        grow = r == 0
        r_tiles = n // m  # row index R being appended / refilled
        m_store = xc.shape[-3]
        take = min(m - r, b_total - consumed)
        xs = x_new[off + (slice(consumed, consumed + take),)]
        ys = y_new[off + (slice(consumed, consumed + take),)]
        if grow:
            x_row = jnp.zeros(xs.shape[:-2] + (m, xs.shape[-1]), dtype)
            y_row = jnp.zeros(ys.shape[:-1] + (m,), dtype)
        else:
            x_row = xc[off + (r_tiles,)]
            y_row = yc[off + (r_tiles,)]
        x_row = x_row.at[off + (slice(r, r + take),)].set(xs)
        y_row = y_row.at[off + (slice(r, r + take),)].set(ys)
        n_valid_new = n + take
        step = _append_step_fn(
            r_tiles, m_store, grow, n_streams, backend, update_dtype,
            batched, batch_dispatch, mesh if batched else None,
            getattr(state, "kernel", None),
        )
        _record_step(
            "run_append", executor.update_append_plan(r_tiles, m_store, n_streams),
            backend, batched, lpacked,
        )
        lpacked, xc, yc, beta = step(
            lpacked, xc, yc, beta, x_row, y_row, state.params,
            n_valid_new if backend == "pallas" else jnp.asarray(n_valid_new),
        )
        n = n_valid_new
        consumed += take

    _, alpha = _resolve_fn(n_streams, False)(lpacked, beta)
    if check_finite:
        _check((alpha,), "append")
    return pred.PosteriorState(
        lpacked=lpacked, alpha=alpha, x_chunks=xc, n=n, m=m,
        params=state.params, beta=beta, y_chunks=yc, kernel=state.kernel,
    )


def extend_state_ragged(
    state,
    x_new: jax.Array,
    y_new: jax.Array,
    counts,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    batch_dispatch: str = "flat",
    check_finite: bool = True,
    mesh=None,
):
    """Absorb per-problem arrival counts b_i into a ragged fleet state.

    ``state`` is a stacked bucket state (B problems sharing one tile
    geometry, per-problem frontiers in ``state.n_valid``); ``x_new`` is
    (B, b_max, D) with each problem's arrivals in its leading ``counts[i]``
    rows (rows past the count are ignored), ``y_new`` (B, b_max), and
    ``counts`` a host-side (B,) int vector.  Every problem must stay within
    the bucket capacity — crossing a boundary is a *migration*, handled one
    level up by ``gp.GPFleet`` (re-embed via ``tiling.embed_packed``, then
    extend in the destination bucket).

    The sweep (DESIGN.md §11): first scatter all arrivals into the feature /
    target chunks at each problem's own frontier, then refill tile-rows
    R = min_i floor(n_i/m) .. max_i ceil(n_i'/m)-1 in increasing order for
    the WHOLE batch with the final per-problem ``n_valid`` masking both
    axes.  Row refill is idempotent: problems untouched at row R reproduce
    their row (same masked assembly, same frozen prefix) and problems whose
    frontier lies below R reproduce identity padding — so one shared
    B-invariant append plan per row serves every ragged arrival mix.
    """
    from repro.core import predict as pred  # cycle: predict imports update

    if state.x_chunks.ndim != 4:
        raise ValueError("extend_state_ragged needs a stacked (B, ...) state")
    if getattr(state, "n_valid", None) is None:
        raise ValueError("extend_state_ragged needs a state with n_valid")
    m = state.m
    dtype = state.x_chunks.dtype
    bsz, m_store, _, d = state.x_chunks.shape
    capacity = m_store * m
    x_new = jnp.asarray(x_new, dtype)
    y_new = jnp.asarray(y_new, dtype)
    if x_new.ndim == 2:  # 1-D problem convenience
        x_new = x_new[..., None]
    counts = np.asarray(counts, np.int64).reshape(-1)
    if (
        x_new.ndim != 3
        or x_new.shape[0] != bsz
        or x_new.shape[-1] != d
        or y_new.shape != x_new.shape[:-1]
        or counts.shape != (bsz,)
    ):
        raise ValueError(
            f"need x_new (B, b_max, D={d}), matching y_new and counts (B,); "
            f"got x {tuple(x_new.shape)}, y {tuple(y_new.shape)}, "
            f"counts {counts.shape}"
        )
    b_max = x_new.shape[1]
    if np.any(counts < 0) or np.any(counts > b_max):
        raise ValueError(f"counts must lie in [0, b_max={b_max}]: {counts}")
    n_old = np.asarray(state.n_valid, np.int64)
    n_new = n_old + counts
    if np.any(n_new > capacity):
        over = np.nonzero(n_new > capacity)[0].tolist()
        raise ValueError(
            f"problems {over} would outgrow the bucket capacity {capacity}; "
            "migrate them to a larger geometry first (gp.GPFleet does)"
        )
    if not np.any(counts > 0):
        return state

    beta, yc = _live_chunks(state)
    lpacked, xc = state.lpacked, state.x_chunks

    # 1) scatter arrivals at each problem's frontier (out-of-bounds rows —
    #    the per-problem tail past counts[i] — drop).
    nv_dev = jnp.asarray(n_old, jnp.int32)
    cnt_dev = jnp.asarray(counts, jnp.int32)

    def scatter_one(xf, yf, xs, ys, n0, cnt):
        ar = jnp.arange(b_max, dtype=jnp.int32)
        pos = jnp.where(ar < cnt, n0 + ar, capacity)
        return (
            xf.at[pos].set(xs, mode="drop"),
            yf.at[pos].set(ys, mode="drop"),
        )

    xc_flat, yc_flat = jax.vmap(scatter_one)(
        xc.reshape(bsz, capacity, d), yc.reshape(bsz, capacity),
        x_new, y_new, nv_dev, cnt_dev,
    )
    xc = xc_flat.reshape(bsz, m_store, m, d)
    yc = yc_flat.reshape(bsz, m_store, m)

    # 2) refill the affected tile-rows, lowest first, whole batch at once.
    growing = counts > 0
    r_lo = int(np.min(n_old[growing]) // m)
    r_hi = int(np.max(n_new[growing] - 1) // m)
    nv_new_dev = jnp.asarray(n_new, jnp.int32)
    for r in range(r_lo, r_hi + 1):
        step = _append_step_fn(
            r, m_store, False, n_streams, backend, update_dtype,
            True, batch_dispatch, mesh, getattr(state, "kernel", None),
        )
        _record_step(
            "run_append", executor.update_append_plan(r, m_store, n_streams),
            backend, True, lpacked,
        )
        lpacked, xc, yc, beta = step(
            lpacked, xc, yc, beta, xc[:, r], yc[:, r], state.params, nv_new_dev
        )

    _, alpha = _resolve_fn(n_streams, False)(lpacked, beta)
    if check_finite:
        _check((alpha,), "ragged append")
    return pred.PosteriorState(
        lpacked=lpacked, alpha=alpha, x_chunks=xc, n=state.n, m=m,
        params=state.params, beta=beta, y_chunks=yc, n_valid=nv_new_dev,
        kernel=state.kernel,
    )


def shrink_state(
    state,
    k: int,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    batch_dispatch: str = "flat",
    check_finite: bool = True,
    mesh=None,
):
    """Evict the k oldest observations from a cached posterior in O(n^2 k).

    ``k`` must be a multiple of the tile size (whole leading tile-columns —
    the sliding-window serving shape; ``GaussianProcess.forget`` falls back
    to refactorization for unaligned k) and must leave at least one valid
    observation.  Each evicted column is a positive rank-m update of the
    trailing factor; beta/alpha are re-solved with one O(n^2) forward +
    backward substitution pass at the end.
    """
    from repro.core import predict as pred

    m = state.m
    if k == 0:
        return state
    if k % m != 0:
        raise ValueError(
            f"shrink_state evicts whole leading tiles: k={k} is not a "
            f"multiple of the tile size {m} (refactorize instead)"
        )
    t = k // m
    m_tiles = state.x_chunks.shape[-3]
    if t >= m_tiles or k >= state.n:
        raise ValueError(
            f"cannot evict {k} of {state.n} observations ({m_tiles} tiles)"
        )
    batched = state.x_chunks.ndim == 4
    off = (slice(None),) if batched else ()
    _, yc = _live_chunks(state)
    lpacked = state.lpacked
    for step in range(t):
        _record_step(
            "run_rank_update",
            executor.update_rank_plan(m_tiles - step - 1, n_streams),
            backend, batched, lpacked,
        )
        lpacked = _evict_step_fn(
            m_tiles - step, n_streams, backend, batch_dispatch,
            mesh if batched else None,
        )(lpacked)
    xc = state.x_chunks[off + (slice(t, None),)]
    yc = yc[off + (slice(t, None),)]
    beta, alpha = _resolve_fn(n_streams, True)(lpacked, yc)
    if check_finite:
        _check((alpha,), "evict")
    return pred.PosteriorState(
        lpacked=lpacked, alpha=alpha, x_chunks=xc, n=state.n - k, m=m,
        params=state.params, beta=beta, y_chunks=yc, kernel=state.kernel,
    )


def downdate_factor(
    lpacked: jax.Array,
    w: jax.Array,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    check_finite: bool = True,
) -> jax.Array:
    """True rank-b downdate: chol(L L^T - W W^T) via hyperbolic rotations.

    w: (M, m, m) carry blocks (zero-padded beyond the rank).  Raises
    :class:`CholeskyUpdateError` when L L^T - W W^T is not positive
    definite (the Cholesky heads go NaN) — the guardrail the sliding-window
    path shares.  The inverse of :func:`update_factor`.
    """
    new_packed, _ = executor.run_rank_update(
        lpacked, w, sign=-1.0, n_streams=n_streams, backend=backend
    )
    if check_finite:
        _check((new_packed,), "downdate")
    return new_packed


def update_factor(
    lpacked: jax.Array,
    w: jax.Array,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    check_finite: bool = True,
) -> jax.Array:
    """Positive rank-b update: chol(L L^T + W W^T) (always PD in exact
    arithmetic; NaN-checked for numerical failures)."""
    new_packed, _ = executor.run_rank_update(
        lpacked, w, sign=1.0, n_streams=n_streams, backend=backend
    )
    if check_finite:
        _check((new_packed,), "update")
    return new_packed


obs.register_cache("update.append_step_fn", _append_step_fn)
obs.register_cache("update.evict_step_fn", _evict_step_fn)
obs.register_cache("update.resolve_fn", _resolve_fn)
