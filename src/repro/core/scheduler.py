"""Tile-task DAG scheduler — the JAX-side analogue of HPX ``hpx::dataflow``.

The paper expresses the tiled Cholesky as a dataflow graph: each tile is
wrapped in an ``hpx::shared_future`` and POTRF/TRSM/SYRK/GEMM tasks fire as
their inputs become ready, spread round-robin over a pool of CUDA streams.

On TPU there is no runtime task graph — the graph must be *static*.  This
module builds the same DAG at trace time and derives:

* ``levels`` — an ASAP (as-soon-as-possible) level schedule: level k holds all
  tasks whose longest dependency chain has length k.  All tasks inside one
  level are independent, which is exactly the set HPX would have in flight
  concurrently with unlimited streams.
* ``chunk(level, n_streams)`` — splits a level into round-robin chunks of at
  most ``n_streams`` tasks; the executor issues one *batched* kernel per chunk.
  ``n_streams=1`` reproduces fully sequential per-task execution (the paper's
  single-stream case); ``n_streams=None`` batches the entire level (the
  TPU-native limit).

The schedule is compiled into gather/compute/scatter batches by
:mod:`repro.core.executor` and consumed through :mod:`repro.core.cholesky`
(``tiled_cholesky``) and :mod:`repro.core.triangular`
(the solve DAGs below); it is also unit-tested directly (task counts,
dependency sanity, critical path length).  See DESIGN.md §3.

Besides the factorization DAG this module also builds the dataflow graphs of
the triangular solves (forward substitution ``L b = y``, backward
substitution ``L^T a = b`` and their tiled-matrix variants): ``TRSV`` tasks
solve one diagonal tile, ``GEMV`` tasks propagate a solved tile-row into a
pending one.  They level-schedule the same way the factorization does.

Finally, :func:`build_program_schedule` fuses the *entire* prediction
pipeline — covariance assembly, Cholesky, both substitutions, cross
covariance, predictive mean and (optionally) an uncertainty head — into
one DAG with cross-stage edges, so e.g. ``TRSV(0)`` depends only on
``POTRF@col0`` and cross-covariance tiles are ready at level 0.  The
wavefront scheduler then co-batches solve rows and cross-assembly into the
tail of Cholesky columns exactly like the paper's Fig. 5 timeline (DESIGN.md
§7).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Task encodings: (op, i, j, k).  k is only used by GEMM.
POTRF = "potrf"
TRSM = "trsm"
SYRK = "syrk"
GEMM = "gemm"

# Triangular-solve ops: TRSV solves the diagonal tile of row i; GEMV updates
# pending row i with solved row j (tile (i, j) for forward, (j, i)^T backward).
TRSV = "trsv"
GEMV = "gemv"

# Whole-pipeline program ops (build_program): covariance assembly feeds the
# factorization, solves/cross-covariance feed the prediction heads.  Forward
# substitution reuses TRSV/GEMV; the backward pass gets distinct ops because
# both stages coexist in one DAG (and write a different buffer).
ASSEMBLE = "assemble"    # packed training-covariance tile (i, j)
CROSS = "cross"          # cross-covariance tile K_*[p, q] (test row p, train col q)
PRIOR = "prior"          # prior test-covariance tile K_{X̂,X̂}[p, q]
TRSV_B = "trsv_b"        # backward diagonal solve of row i (alpha buffer)
GEMV_B = "gemv_b"        # backward propagation a_i -= L_ji^T a_j
XGEMV = "xgemv"          # predictive-mean row p: mean_p = sum_q K_*[p,q] alpha_q
VINIT = "vinit"          # uncertainty workspace row i: V_i,q <- K_*[q,i]^T
VTRSV = "vtrsv"          # matrix forward solve, diagonal tile of row i
VGEMV = "vgemv"          # matrix forward propagation V_i -= L_ij V_j
GRAM = "gram"            # prior - V^T V, or its diagonal (single closing task)


class Head(enum.IntEnum):
    """The fused program's closing uncertainty head (DESIGN.md §7).

    ``NONE``: the mean alone (and the NLML prefix, ``q_tiles=0``).
    ``COVARIANCE``: Sigma = prior - V^T V over all Q^2 test tiles.
    ``VARIANCE``: diag(Sigma) alone — the Q diagonal prior tiles' diagonals
    less the column sums of V squared; no Q x Q buffer exists.  The values
    keep the old ``uncertainty`` flag's meaning: False is ``NONE``, True is
    ``COVARIANCE``.
    """

    NONE = 0
    COVARIANCE = 1
    VARIANCE = 2


# Streaming-update ops (DESIGN.md §10).  Two DAG families:
#
# *Append* ("update_append"): grow the factor by one tile-row R of new
# observations.  The new row's tiles obey the same recurrence as the TRSM
# row of a factorization step, solved against the frozen existing factor:
#
#   row_j = (K(R, j) - sum_{k<j} row_k L(j,k)^T) L(j,j)^{-T}      (j < R)
#   corner = chol(K(R, R) - sum_j row_j row_j^T)
#
# *Rank update* ("update_rank"): L' L'^T = L L^T + sigma W W^T for a
# tile-column carry W (sliding-window eviction uses sigma=+1 on the trailing
# factor; a true downdate is sigma=-1 via hyperbolic rotations) — the
# blocked cholupdate recurrence (per column j):
#
#   L'(j,j) = chol(L(j,j) L(j,j)^T + s W_j W_j^T)
#   X_j = L'(j,j)^{-1} L(j,j);  Y_j = L'(j,j)^{-1} W_j
#   C_j = chol(I - s Y_j^T Y_j)                 <- positivity check (s=-1)
#   L'(i,j) = L(i,j) X_j^T + s W_i Y_j^T                          (i > j)
#   W_i    <- (W_i - L'(i,j) Y_j) C_j^{-T}                        (i > j)
UASM = "uasm"            # assemble cross tile K(x_row, x_j) of the new row
UASMD = "uasmd"          # assemble the new diagonal (corner) tile
UTRSM = "utrsm"          # row_j <- row_j L(j,j)^{-T}
UGEMM = "ugemm"          # row_j -= row_k L(j,k)^T
USYRK = "usyrk"          # corner -= row_j row_j^T
UPOTRF = "upotrf"        # corner <- chol(corner)
UPREP = "uprep"          # column head: L'(j,j) + the X/Y/C auxiliaries
UPROW = "uprow"          # L'(i,j) = L(i,j) X_j^T + s W_i Y_j^T
UCARRY = "ucarry"        # W_i <- (W_i - L'(i,j) Y_j) C_j^{-T}

# Low-rank (Nyström) tier op (DESIGN.md §14): the n-side contraction of the
# inducing system.  One tile task per (inducing row p, training column j) of
# the K_un grid — every task is independent (the n axis is embarrassingly
# parallel), so the whole family is a single bulk launch in the executor.
LRGEMM = "lrgemm"        # c_p += K_un[p, j] @ y_j  /  G += K_un[:, j] K_un[:, j]^T

Task = Tuple[str, int, int, int]

# Ops that the wavefront scheduler does NOT count against the stream pool:
# the pool models the paper's per-stream cuBLAS/cuSOLVER handles (one tile
# BLAS kernel resident per stream), whereas these ops are single batched
# custom-kernel launches in the executor no matter how many tiles they cover
# (exactly how the staged pipeline issues them).  They still enter waves as
# soon as their dependencies resolve — riding along with whatever BLAS wave
# is current — so the cross-stage overlap is preserved without inflating the
# launch count.
BULK_OPS = frozenset(
    {ASSEMBLE, CROSS, PRIOR, VINIT, XGEMV, GRAM, UASM, UASMD, LRGEMM}
)

# Dispatch groups: tasks whose batched kernel is literally the same launch.
# SYRK is GEMM with both panels equal, so the executor fuses both into one
# trailing-update launch per level (executor.TRAIL).  The update-family bulk
# ops are the assembly of the appended row (single batched launch).
TRAIL_GROUP = "trail"


def dispatch_group(op: str) -> str:
    return TRAIL_GROUP if op in (SYRK, GEMM) else op


def _deps(task: Task, m_tiles: int) -> List[Task]:
    """Direct dependencies of a task in the right-looking tiled Cholesky.

    Matches the paper's Fig. 1 loop nest:
      POTRF(J,J)   needs SYRK(J,J) of step J-1            (last writer of (J,J))
      TRSM(I,J)    needs POTRF(J,J) and GEMM(I,J) of step J-1 (last writer of (I,J))
      SYRK(I,I)@J  needs TRSM(I,J) and SYRK(I,I) of step J-1
      GEMM(I,K)@J  needs TRSM(I,J), TRSM(K,J) and GEMM(I,K) of step J-1
    """
    op, i, j, k = task
    deps: List[Task] = []
    if op == POTRF:
        # last update of tile (j, j) was SYRK at step j-1
        if j > 0:
            deps.append((SYRK, j, j - 1, -1))
    elif op == TRSM:
        deps.append((POTRF, j, j, -1))
        if j > 0:
            deps.append((GEMM, i, j - 1, j))  # last writer of (i, j): GEMM(I=i, K=j) at step j-1
    elif op == SYRK:
        # SYRK at step j updates tile (i, i) using panel tile (i, j)
        deps.append((TRSM, i, j, -1))
        if j > 0:
            deps.append((SYRK, i, j - 1, -1))
    elif op == GEMM:
        # GEMM at step j updates tile (i, k) using panel tiles (i, j), (k, j)
        deps.append((TRSM, i, j, -1))
        deps.append((TRSM, k, j, -1))
        if j > 0:
            deps.append((GEMM, i, j - 1, k))
    else:
        raise ValueError(op)
    return deps


def all_tasks(m_tiles: int) -> List[Task]:
    """Every task of the factorization, in the paper's Fig. 1 program order."""
    tasks: List[Task] = []
    for j in range(m_tiles):
        tasks.append((POTRF, j, j, -1))
        for i in range(j + 1, m_tiles):
            tasks.append((TRSM, i, j, -1))
        for i in range(j + 1, m_tiles):
            tasks.append((SYRK, i, j, -1))
            for k in range(j + 1, i):
                tasks.append((GEMM, i, j, k))
    return tasks


@dataclasses.dataclass(frozen=True)
class Schedule:
    m_tiles: int
    levels: Tuple[Tuple[Task, ...], ...]
    kind: str = "cholesky"  # "cholesky" | "forward" | "backward" | "program"
    q_tiles: int = 0        # test tile count (program schedules only)
    head: Head = Head.NONE  # the program's uncertainty head

    @property
    def critical_path(self) -> int:
        return len(self.levels)

    @property
    def n_tasks(self) -> int:
        return sum(len(l) for l in self.levels)

    def max_width(self) -> int:
        return max(len(l) for l in self.levels)

    def op_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for level in self.levels:
            for t in level:
                counts[t[0]] = counts.get(t[0], 0) + 1
        if self.kind == "cholesky":
            for op in (POTRF, TRSM, SYRK, GEMM):
                counts.setdefault(op, 0)
        return counts


def _asap_levels(tasks: Sequence[Task], deps_fn) -> Tuple[Tuple[Task, ...], ...]:
    """ASAP level assignment; ``tasks`` must be in topological order."""
    level_of: Dict[Task, int] = {}
    for t in tasks:
        deps = deps_fn(t)
        level_of[t] = 0 if not deps else 1 + max(level_of[d] for d in deps)
    n_levels = 1 + max(level_of.values()) if level_of else 0
    levels: List[List[Task]] = [[] for _ in range(n_levels)]
    for t in tasks:
        levels[level_of[t]].append(t)
    return tuple(tuple(l) for l in levels)


def build_schedule(m_tiles: int) -> Schedule:
    """ASAP level schedule of the tiled Cholesky DAG."""
    levels = _asap_levels(all_tasks(m_tiles), lambda t: _deps(t, m_tiles))
    return Schedule(m_tiles=m_tiles, levels=levels)


def solve_deps(task: Task, m_tiles: int, *, lower: bool = True) -> List[Task]:
    """Direct dependencies of a triangular-solve task.

    Forward (``L b = y``, right-looking): once row j is solved, every pending
    row i > j receives the update ``b_i -= L_ij b_j``:

      TRSV(i)      needs GEMV(i, i-1)             (last accumulation into row i)
      GEMV(i, j)   needs TRSV(j) and GEMV(i, j-1) (last writer of row i's acc)

    Backward (``L^T a = b``) mirrors this with the recurrence running from
    row M-1 down; GEMV(i, j) with j > i applies ``a_i -= L_ji^T a_j``.
    """
    op, i, j, _ = task
    deps: List[Task] = []
    if op == TRSV:
        if lower and i > 0:
            deps.append((GEMV, i, i - 1, -1))
        elif not lower and i < m_tiles - 1:
            deps.append((GEMV, i, i + 1, -1))
    elif op == GEMV:
        deps.append((TRSV, j, j, -1))
        if lower and j > 0:
            deps.append((GEMV, i, j - 1, -1))
        elif not lower and j < m_tiles - 1:
            deps.append((GEMV, i, j + 1, -1))
    else:
        raise ValueError(op)
    return deps


def solve_tasks(m_tiles: int, *, lower: bool = True) -> List[Task]:
    """Every task of a tiled triangular solve, in dataflow program order."""
    tasks: List[Task] = []
    cols = range(m_tiles) if lower else reversed(range(m_tiles))
    for j in cols:
        tasks.append((TRSV, j, j, -1))
        rows = range(j + 1, m_tiles) if lower else range(j)
        for i in rows:
            tasks.append((GEMV, i, j, -1))
    return tasks


def build_solve_schedule(m_tiles: int, *, lower: bool = True) -> Schedule:
    """ASAP level schedule of forward (lower) / backward substitution.

    The same schedule drives both the vector solves (``L b = y``) and the
    tiled-matrix solves (``L V = B``): the DAG over tile-rows is identical,
    only the per-task operand shapes differ (see executor.run_solve).
    Critical path is 2M - 1 levels: TRSV and batched-GEMV levels alternate.
    """
    levels = _asap_levels(
        solve_tasks(m_tiles, lower=lower),
        lambda t: solve_deps(t, m_tiles, lower=lower),
    )
    return Schedule(
        m_tiles=m_tiles, levels=levels, kind="forward" if lower else "backward"
    )


# ---------------------------------------------------------------------------
# The whole-pipeline program DAG (assembly -> factorization -> solves ->
# cross covariance -> mean / full covariance) with cross-stage edges.
# ---------------------------------------------------------------------------


def _prior_tiles(q_tiles: int, head: Head) -> List[Tuple[int, int]]:
    """The prior tiles a head reads: all Q^2, or the Q diagonal ones."""
    if head == Head.COVARIANCE:
        return [(p, q) for p in range(q_tiles) for q in range(q_tiles)]
    if head == Head.VARIANCE:
        return [(p, p) for p in range(q_tiles)]
    return []


def program_tasks(m_tiles: int, q_tiles: int, *, head: Head = Head.NONE) -> List[Task]:
    """Every task of the fused prediction pipeline, in program order.

    The order is topological for :func:`program_deps` (assembly first, then
    factorization, forward substitution, backward substitution, prediction
    heads), which is what ``_asap_levels`` requires.  Both uncertainty heads
    run the same matrix solve; they differ in the PRIOR tiles they assemble
    and in what the closing GRAM computes.
    """
    head = Head(head)
    tasks: List[Task] = []
    for j in range(m_tiles):
        for i in range(j, m_tiles):
            tasks.append((ASSEMBLE, i, j, -1))
    for p in range(q_tiles):
        for q in range(m_tiles):
            tasks.append((CROSS, p, q, -1))
    for p, q in _prior_tiles(q_tiles, head):
        tasks.append((PRIOR, p, q, -1))
    tasks += all_tasks(m_tiles)
    tasks += solve_tasks(m_tiles, lower=True)  # forward: TRSV / GEMV
    for op, i, j, k in solve_tasks(m_tiles, lower=False):
        tasks.append((TRSV_B if op == TRSV else GEMV_B, i, j, k))
    for p in range(q_tiles):
        tasks.append((XGEMV, p, -1, -1))
    if head != Head.NONE:
        for i in range(m_tiles):
            tasks.append((VINIT, i, -1, -1))
        for op, i, j, k in solve_tasks(m_tiles, lower=True):
            tasks.append((VTRSV if op == TRSV else VGEMV, i, j, k))
        tasks.append((GRAM, -1, -1, -1))
    return tasks


def program_deps(
    task: Task, m_tiles: int, q_tiles: int, head: Head = Head.NONE
) -> List[Task]:
    """Direct dependencies of a task in the fused prediction program.

    These are the paper's cross-stage dataflow edges: a consumer waits only
    for the *tiles it reads*, never for a whole stage.  The last writer of an
    off-diagonal packed tile (i, j) is ``TRSM(i, j)``; of a diagonal tile
    (j, j) it is ``POTRF(j)``; of vector row i it is the forward/backward
    ``TRSV`` of that row.  Hence e.g. ``TRSV(0)`` depends on ``POTRF@col0``
    only — forward substitution starts while the factorization of later
    columns is still in flight (the paper's Fig. 5 overlap).

    Buffer hazards: forward substitution runs in the ``y`` buffer; its
    diagonal solve also publishes the row into the separate ``alpha`` buffer
    that the backward pass accumulates in, so backward writes can never
    clobber rows a forward GEMV still reads (no WAR anti-edges needed).

    ``head`` matters only to GRAM, which waits for the PRIOR tiles its head
    assembles (:func:`program_tasks`).
    """
    op, i, j, k = task
    m = m_tiles
    if op in (ASSEMBLE, CROSS, PRIOR):
        return []
    if op == POTRF:
        return [(SYRK, j, j - 1, -1) if j > 0 else (ASSEMBLE, j, j, -1)]
    if op == TRSM:
        return [
            (POTRF, j, j, -1),
            (GEMM, i, j - 1, j) if j > 0 else (ASSEMBLE, i, j, -1),
        ]
    if op == SYRK:
        return [
            (TRSM, i, j, -1),
            (SYRK, i, j - 1, -1) if j > 0 else (ASSEMBLE, i, i, -1),
        ]
    if op == GEMM:
        return [
            (TRSM, i, j, -1),
            (TRSM, k, j, -1),
            (GEMM, i, j - 1, k) if j > 0 else (ASSEMBLE, i, k, -1),
        ]
    if op == TRSV:  # forward; reads L(i,i), accumulations must be done
        deps = [(POTRF, i, i, -1)]
        if i > 0:
            deps.append((GEMV, i, i - 1, -1))
        return deps
    if op == GEMV:  # forward: b_i -= L(i,j) b_j; reads finalized tile (i, j)
        deps = [(TRSV, j, j, -1), (TRSM, i, j, -1)]
        if j > 0:
            deps.append((GEMV, i, j - 1, -1))
        return deps
    if op == TRSV_B:  # backward; row i seeded by the forward solve of row i
        deps = [(POTRF, i, i, -1), (TRSV, i, i, -1)]
        if i < m - 1:
            deps.append((GEMV_B, i, i + 1, -1))
        return deps
    if op == GEMV_B:  # a_i -= L(j,i)^T a_j; reads finalized tile (j, i)
        deps = [(TRSV_B, j, j, -1), (TRSV, i, i, -1), (TRSM, j, i, -1)]
        if j < m - 1:
            deps.append((GEMV_B, i, j + 1, -1))
        return deps
    if op == XGEMV:  # mean row p reads every cross tile of row p and all alpha
        return [(CROSS, i, q, -1) for q in range(m)] + [
            (TRSV_B, q, q, -1) for q in range(m)
        ]
    if op == VINIT:  # V row i is the transposed cross column i
        return [(CROSS, p, i, -1) for p in range(q_tiles)]
    if op == VTRSV:
        deps = [(VINIT, i, -1, -1), (POTRF, i, i, -1)]
        if i > 0:
            deps.append((VGEMV, i, i - 1, -1))
        return deps
    if op == VGEMV:  # V_i -= L(i,j) V_j
        deps = [(VTRSV, j, j, -1), (TRSM, i, j, -1), (VINIT, i, -1, -1)]
        if j > 0:
            deps.append((VGEMV, i, j - 1, -1))
        return deps
    if op == GRAM:
        return [(VTRSV, r, r, -1) for r in range(m)] + [
            (PRIOR, p, q, -1) for p, q in _prior_tiles(q_tiles, Head(head))
        ]
    raise ValueError(op)


def build_program_schedule(
    m_tiles: int, q_tiles: int, *, head: Head = Head.NONE
) -> Schedule:
    """ASAP level schedule of the fused prediction program.

    Cross-stage overlap falls out of the DAG: e.g. ``TRSV(0)`` levels right
    next to the TRSM panel of column 0, and every ``CROSS`` tile sits at
    level 0 alongside the covariance assembly.
    """
    head = Head(head)
    tasks = program_tasks(m_tiles, q_tiles, head=head)
    levels = _asap_levels(tasks, lambda t: program_deps(t, m_tiles, q_tiles, head))
    return Schedule(
        m_tiles=m_tiles,
        levels=levels,
        kind="program",
        q_tiles=q_tiles,
        head=head,
    )


def build_nlml_schedule(m_tiles: int) -> Schedule:
    """The trainable NLML prefix of the prediction program (DESIGN.md §8).

    ``q_tiles=0`` degenerates the program DAG to exactly the tasks the
    negative log marginal likelihood needs — ASSEMBLE, the factorization,
    and both substitutions (``alpha = K^{-1} y`` for the quadratic term; the
    log-determinant reads the factor's diagonal tiles, which is a reduction
    head in the executor, not a scheduled task).  No CROSS/PRIOR tiles, no
    prediction heads.  This is the forward program that
    :func:`repro.core.mll.nlml_tiled` differentiates.
    """
    return build_program_schedule(m_tiles, 0, head=Head.NONE)


# ---------------------------------------------------------------------------
# Streaming-update DAGs (DESIGN.md §10): block Cholesky append / rank update.
# ---------------------------------------------------------------------------


def append_tasks(r_tiles: int) -> List[Task]:
    """Every task of a one-tile-row block-Cholesky append, in program order.

    ``r_tiles`` is the number of *existing* factor tile-rows the new row is
    solved against (the new row gets index R = r_tiles).  ``r_tiles=0``
    degenerates to assembling + factoring a single corner tile (the very
    first observations of a GP whose partial tile is being refilled).
    """
    r = r_tiles
    tasks: List[Task] = []
    for j in range(r):
        tasks.append((UASM, j, -1, -1))
    tasks.append((UASMD, r, -1, -1))
    for j in range(r):
        for k in range(j):
            tasks.append((UGEMM, j, k, -1))
        tasks.append((UTRSM, j, -1, -1))
        tasks.append((USYRK, j, -1, -1))
    tasks.append((UPOTRF, r, -1, -1))
    return tasks


def append_deps(task: Task, r_tiles: int) -> List[Task]:
    """Direct dependencies of an append task.

    The existing factor is a frozen *input* (its last writer completed in a
    previous program), so edges only run between the new row's own tasks:
    the TRSM-row recurrence chains UGEMM corrections before each diagonal
    solve, and the corner accumulates SYRK contributions in program order.
    """
    op, i, j, _ = task
    r = r_tiles
    if op in (UASM, UASMD):
        return []
    if op == UTRSM:  # row_i <- row_i L(i,i)^{-T} after all corrections
        return [(UGEMM, i, i - 1, -1) if i > 0 else (UASM, i, -1, -1)]
    if op == UGEMM:  # row_i -= row_j L(i,j)^T; reads solved row_j
        deps = [(UTRSM, j, -1, -1)]
        deps.append((UGEMM, i, j - 1, -1) if j > 0 else (UASM, i, -1, -1))
        return deps
    if op == USYRK:  # corner -= row_i row_i^T (accumulation chain)
        return [
            (UTRSM, i, -1, -1),
            (USYRK, i - 1, -1, -1) if i > 0 else (UASMD, r, -1, -1),
        ]
    if op == UPOTRF:
        return [(USYRK, r - 1, -1, -1) if r > 0 else (UASMD, r, -1, -1)]
    raise ValueError(op)


def rank_update_tasks(m_tiles: int) -> List[Task]:
    """Every task of a tiled rank-b up/downdate, in program order."""
    tasks: List[Task] = []
    for j in range(m_tiles):
        tasks.append((UPREP, j, -1, -1))
        for i in range(j + 1, m_tiles):
            tasks.append((UPROW, i, j, -1))
        for i in range(j + 1, m_tiles):
            tasks.append((UCARRY, i, j, -1))
    return tasks


def rank_update_deps(task: Task, m_tiles: int) -> List[Task]:
    """Direct dependencies of a rank-update task (blocked cholupdate).

    The recurrence sweeps columns left to right; row i's carry W_i evolves
    once per column, so every column-j task on row i waits for UCARRY(i,
    j-1) — the last writer of W_i.  UPREP(j) writes the new diagonal into
    the factor *and* the X/Y/C auxiliaries its column reads; UPROW(i,j)
    overwrites L(i,j) in place (no later task reads the old value).
    """
    op, i, j, _ = task
    if op == UPREP:  # reads L(j,j) and the settled carry W_j
        return [(UCARRY, i, i - 1, -1)] if i > 0 else []
    if op == UPROW:
        deps = [(UPREP, j, -1, -1)]
        if j > 0:
            deps.append((UCARRY, i, j - 1, -1))
        return deps
    if op == UCARRY:
        return [(UPROW, i, j, -1), (UPREP, j, -1, -1)]
    raise ValueError(op)


def build_update_schedule(
    m_tiles: int, *, kind: str = "update_append"
) -> Schedule:
    """ASAP level schedule of an update DAG.

    ``kind="update_append"``: ``m_tiles`` is the *existing* row count R the
    appended row solves against.  ``kind="update_rank"``: ``m_tiles`` is the
    size of the factor being up/downdated.
    """
    tasks, deps_fn = _dag(m_tiles, kind)
    levels = _asap_levels(tasks, deps_fn)
    return Schedule(m_tiles=m_tiles, levels=levels, kind=kind)


def lowrank_tasks(mu_tiles: int, n_tiles: int) -> List[Task]:
    """The LRGEMM bulk family over the (mu_tiles × n_tiles) K_un grid.

    Single level: every tile contraction is independent, so the whole
    family compiles to ONE batched launch (BULK_OPS) — the low-rank tier's
    n-dimensional work is embarrassingly tile-parallel by construction.
    """
    return [(LRGEMM, p, j, -1) for p in range(mu_tiles) for j in range(n_tiles)]


def lowrank_deps(task: Task) -> List[Task]:
    if task[0] != LRGEMM:
        raise ValueError(task[0])
    return []


def task_deps(task: Task, schedule: Schedule) -> List[Task]:
    """Dependencies of ``task`` under the DAG family of ``schedule.kind``."""
    if schedule.kind == "cholesky":
        return _deps(task, schedule.m_tiles)
    if schedule.kind == "program":
        return program_deps(task, schedule.m_tiles, schedule.q_tiles, schedule.head)
    if schedule.kind == "update_append":
        return append_deps(task, schedule.m_tiles)
    if schedule.kind == "update_rank":
        return rank_update_deps(task, schedule.m_tiles)
    if schedule.kind == "lowrank":
        return lowrank_deps(task)
    return solve_deps(task, schedule.m_tiles, lower=schedule.kind == "forward")


def _dag(m_tiles: int, kind: str, q_tiles: int = 0, head: Head = Head.NONE):
    """(tasks in topological order, deps_fn) for a DAG family."""
    if kind == "cholesky":
        return all_tasks(m_tiles), lambda t: _deps(t, m_tiles)
    if kind in ("forward", "backward"):
        lower = kind == "forward"
        return (
            solve_tasks(m_tiles, lower=lower),
            lambda t: solve_deps(t, m_tiles, lower=lower),
        )
    if kind == "program":
        return (
            program_tasks(m_tiles, q_tiles, head=head),
            lambda t: program_deps(t, m_tiles, q_tiles, head),
        )
    if kind == "update_append":
        return append_tasks(m_tiles), lambda t: append_deps(t, m_tiles)
    if kind == "update_rank":
        return rank_update_tasks(m_tiles), lambda t: rank_update_deps(t, m_tiles)
    if kind == "lowrank":
        # q_tiles carries the n-side tile count of the K_un grid
        return lowrank_tasks(m_tiles, q_tiles), lambda t: lowrank_deps(t)
    raise ValueError(kind)


def _bottom_levels(tasks: Sequence[Task], deps_fn) -> Dict[Task, int]:
    """Longest path from each task to a sink (critical-path priority)."""
    bottom: Dict[Task, int] = {t: 0 for t in tasks}
    for t in reversed(tasks):  # reverse topological order
        for d in deps_fn(t):
            bottom[d] = max(bottom[d], bottom[t] + 1)
    return bottom


def build_wavefront_schedule(
    m_tiles: int,
    n_streams: int,
    *,
    kind: str = "cholesky",
    q_tiles: int = 0,
    head: Head = Head.NONE,
) -> Schedule:
    """Finite-stream-pool list schedule: the paper's round-robin pool, static.

    ASAP levels of the right-looking Cholesky DAG are *column-phased* (level
    3j+{0,1,2} holds exactly the POTRF / TRSM panel / trailing update of
    column j), so plain level chunking can never co-batch tasks of different
    columns.  HPX with a finite stream pool does better: when the trailing
    update of column j does not fill the pool, panel tasks of column j+1 that
    are already ready ride along.  This function reproduces that statically:

      wave k = the <= n_streams ready tasks with the greatest bottom-level
               (longest path to a sink, i.e. critical-path-first priority)

    Program DAGs additionally carry BULK_OPS tasks (covariance assembly and
    the prediction heads); those are single batched custom-kernel launches in
    the executor, so they ride every wave as soon as they are ready without
    consuming pool slots (the pool models per-stream BLAS handles).

    Every wave is an antichain (all members were simultaneously ready), and
    accumulation chains (SYRK/GEMM onto one tile) stay in program order, so
    executing waves in sequence is exactly dependency-faithful — but a wave
    may now mix, say, GEMM(i,k)@j with TRSM@j+1, which the executor turns
    into co-issued batched kernels.  ``n_streams=1`` degenerates to the
    fully sequential priority order (the paper's single-stream baseline).
    """
    import heapq

    if n_streams < 1:
        raise ValueError(f"n_streams must be >= 1 or None, got {n_streams}")
    head = Head(head)
    tasks, deps_fn = _dag(m_tiles, kind, q_tiles, head)
    bottom = _bottom_levels(tasks, deps_fn)
    order = {t: i for i, t in enumerate(tasks)}
    indeg = {t: len(deps_fn(t)) for t in tasks}
    succs: Dict[Task, List[Task]] = {}
    for t in tasks:
        for d in deps_fn(t):
            succs.setdefault(d, []).append(t)

    def push(h, t):
        heapq.heappush(h, (-bottom[t], order[t], t))

    heap: list = []       # pooled BLAS tile tasks (<= n_streams per wave)
    bulk_heap: list = []  # batched custom-kernel ops (ride along, see BULK_OPS)
    for t in tasks:
        if indeg[t] == 0:
            push(bulk_heap if t[0] in BULK_OPS else heap, t)
    waves: List[Tuple[Task, ...]] = []
    affinity = kind == "program"  # staged plans keep PR-1's pure priority order
    while heap or bulk_heap:
        wave = [heapq.heappop(bulk_heap)[2] for _ in range(len(bulk_heap))]
        if affinity and heap:
            # The wave leader is still chosen critical-path-first; remaining
            # pool slots prefer tasks of the leader's dispatch group so a wave
            # compiles to as few batched launches as possible.  Tasks are all
            # simultaneously ready, so this only reorders within the wave's
            # antichain — dependency-faithfulness is untouched.
            ready = [heapq.heappop(heap) for _ in range(len(heap))]
            leader = ready[0]
            grp = dispatch_group(leader[2][0])
            same = [e for e in ready[1:] if dispatch_group(e[2][0]) == grp]
            rest = [e for e in ready[1:] if dispatch_group(e[2][0]) != grp]
            picked = [leader] + (same + rest)[: n_streams - 1]
            wave += [e[2] for e in picked]
            for e in same[n_streams - 1 :] + rest[max(n_streams - 1 - len(same), 0) :]:
                heapq.heappush(heap, e)
        else:
            wave += [heapq.heappop(heap)[2] for _ in range(min(n_streams, len(heap)))]
        waves.append(tuple(wave))
        for t in wave:
            for s in succs.get(t, ()):
                indeg[s] -= 1
                if indeg[s] == 0:
                    push(bulk_heap if s[0] in BULK_OPS else heap, s)
    return Schedule(
        m_tiles=m_tiles,
        levels=tuple(waves),
        kind=kind,
        q_tiles=q_tiles,
        head=head,
    )


def chunk_tasks(
    tasks: Sequence[Task], n_streams: Optional[int]
) -> List[List[Task]]:
    """Round-robin chunking of one level into groups of <= n_streams tasks.

    The paper assigns tasks to a stream pool round-robin; a chunk here is the
    set of tasks that would be resident on the pool simultaneously, which we
    execute as a single batched kernel call.
    """
    tasks = list(tasks)
    if n_streams is None or n_streams >= len(tasks):
        return [tasks] if tasks else []
    return [tasks[i : i + n_streams] for i in range(0, len(tasks), n_streams)]


def split_by_op(tasks: Iterable[Task]) -> Dict[str, List[Task]]:
    out: Dict[str, List[Task]] = {}
    for t in tasks:
        out.setdefault(t[0], []).append(t)
    return out


def theoretical_task_counts(m_tiles: int) -> Dict[str, int]:
    m = m_tiles
    return {
        POTRF: m,
        TRSM: m * (m - 1) // 2,
        SYRK: m * (m - 1) // 2,
        GEMM: m * (m - 1) * (m - 2) // 6,
    }
