"""Schedule-driven level-batched executor — the stream pool of the paper.

HPX executes the tiled Cholesky/solve DAG by firing each task as its future
operands resolve, round-robin over a pool of CUDA streams; kernels from
*different* columns overlap whenever the dataflow allows it.  On TPU the graph
must be static, so this module compiles a :class:`repro.core.scheduler.Schedule`
into the equivalent static program:

  for each level (ASAP antichain, or <= n_streams wavefront wave):
      group the level's tasks by op        # POTRF / TRSM / SYRK / GEMM / ...
      for each round-robin chunk of <= n_streams tasks:
          gather operand tiles (precomputed numpy index arrays)
          ONE batched kernel call (vmapped jnp op or Pallas kernel)
          scatter results back into the packed store

With ``n_streams=None`` every ASAP level becomes one batch per op — the
TPU-native maximum-batching limit.  With a finite ``n_streams`` the plan is
the *wavefront* schedule (scheduler.build_wavefront_schedule): waves of at
most ``n_streams`` simultaneously-ready tasks, critical-path first, so the
GEMM tail of column j co-batches with the TRSM panel of column j+1 — exactly
the cross-column overlap the paper's Fig. 5 timeline shows for the stream
pool.  ``n_streams=1`` is the fully sequential single-stream baseline.

Plans (the gather/scatter index arrays per level) are pure functions of
``(m_tiles, n_streams)`` and are lru-cached, so repeated traces pay no
schedule-construction cost.  See DESIGN.md §3.

**Problem batching (DESIGN.md §9).**  Every buffer may carry an optional
leading problem-batch dimension ``B`` — ``B`` independent GPs of identical
tile geometry executed by the *same* Plan (the DAG depends only on
``m_tiles``/``q_tiles``, never on ``B``, so plans stay shared and
lru-cached).  Gathers/scatters move from axis 0 to axis 1 and every batched
kernel launch covers ``B x G`` tiles instead of ``G``: either flattened into
the kernel's existing batch/grid axis (``batch_dispatch="flat"``, the
default — one launch whose Pallas grid absorbs B) or via one more
``jax.vmap`` level over the single-problem kernels
(``batch_dispatch="vmap"``).  ``benchmarks/fig9_batched_fleet.py`` measures
both.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import kernels_math as km
from repro.core import scheduler as sch
from repro.core import tiling
from repro.dist import sharding as dist_sharding

# Each batch of a plan runs inside ``jax.named_scope("repro.exec.<op>")``:
# every device op of the compiled program then carries its op family in its
# metadata (HLO ``op_name``, the profiler trace's ``tf_op``).  Metadata only;
# the compiled program is unchanged (DESIGN.md §15).
_SCOPE = obs.Tracer("repro.exec")


# ---------------------------------------------------------------------------
# Tile-level ops (jnp backend).  a/b are (m, m) tiles; batched via vmap.
# The Pallas backend (repro.kernels.ops) exposes the same signatures.
# ---------------------------------------------------------------------------


def _potrf_jnp(a: jax.Array) -> jax.Array:
    return jnp.linalg.cholesky(a)


def _trsm_jnp(ljj: jax.Array, b: jax.Array) -> jax.Array:
    # Solve X @ L_JJ^T = B  (right-looking panel update: L_IJ = K_IJ L_JJ^{-T})
    return jax.lax.linalg.triangular_solve(
        ljj, b, left_side=False, lower=True, transpose_a=True
    )


def _syrk_jnp(kii: jax.Array, lij: jax.Array, update_dtype=None) -> jax.Array:
    a = lij if update_dtype is None else lij.astype(update_dtype)
    upd = (a @ a.T).astype(kii.dtype)
    return kii - upd


def _gemm_jnp(kik: jax.Array, lij: jax.Array, lkj: jax.Array, update_dtype=None) -> jax.Array:
    a, b = lij, lkj
    if update_dtype is not None:
        a, b = a.astype(update_dtype), b.astype(update_dtype)
    upd = (a @ b.T).astype(kik.dtype)
    return kik - upd


def get_ops(backend: str):
    """(potrf, trsm, syrk, gemm) tile ops for a backend name."""
    if backend == "jnp":
        return _potrf_jnp, _trsm_jnp, _syrk_jnp, _gemm_jnp
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.potrf, kops.trsm, kops.syrk, kops.gemm
    raise ValueError(f"unknown backend: {backend}")


def get_lrgemm_op(backend: str):
    """The per-tile LRGEMM contraction op (DESIGN.md §14): (m, mb) @ (mb,)."""
    if backend == "jnp":
        return lambda a, v: a @ v
    if backend == "pallas":
        from repro.kernels import ops as kops

        return kops.lrgemm
    raise ValueError(f"unknown backend: {backend}")


# ---------------------------------------------------------------------------
# Compiled plans: per level, per op, per stream-chunk gather/scatter indices.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Batch:
    """One batched kernel launch: gather operands, compute, scatter ``out``.

    Index semantics by op (all numpy int32, length = batch size):
      POTRF: a = diagonal slots;                       out = a
      TRSM:  a = L_JJ slots, b = panel slots;          out = b
      SYRK:  a = target (i,i) slots, b = panel slots;  out = a
      GEMM:  a = target slots, b/c = panel slots;      out = a
      TRSV:  a = diagonal slots;                       out = rhs tile-rows
      GEMV:  a = L tile slots, b = source tile-rows;   out = dest tile-rows
    """

    op: str
    tasks: Tuple[sch.Task, ...]
    out: np.ndarray
    a: np.ndarray
    b: Optional[np.ndarray] = None
    c: Optional[np.ndarray] = None

    @property
    def size(self) -> int:
        return len(self.tasks)


@dataclasses.dataclass(frozen=True)
class Plan:
    """A schedule compiled to batched gather/compute/scatter launches."""

    kind: str
    m_tiles: int
    n_streams: Optional[int]
    levels: Tuple[Tuple[Batch, ...], ...]

    @property
    def n_batches(self) -> int:
        return sum(len(l) for l in self.levels)

    def level_task_counts(self) -> List[int]:
        """Tasks per level — must match ``len(level)`` of the source Schedule."""
        return [sum(b.size for b in level) for level in self.levels]

    def flat_tasks(self) -> List[sch.Task]:
        """Tasks in issue order (level-major, batch order within a level)."""
        return [t for level in self.levels for b in level for t in b.tasks]


def _arr(xs: Sequence[int]) -> np.ndarray:
    return np.asarray(xs, np.int32)


# ---------------------------------------------------------------------------
# Wave-trace telemetry (DESIGN.md §15) — the live analogue of fig5: what one
# dispatch of a Plan launches, wave by wave, and how full the stream pool is.
# ---------------------------------------------------------------------------

# Plans are lru-cached and live for the process; keying the digest by id()
# makes the per-dispatch record a dict lookup, not a Plan walk.
_plan_stats_cache: dict = {}


def plan_wave_stats(plan: Plan) -> dict:
    """Static per-Plan wave digest: waves, launches, tasks by op family,
    bulk-op ride-alongs, and mean stream-pool occupancy.

    ``occupancy`` is pool tasks per pool-bearing wave over ``n_streams``
    (BULK_OPS ride along outside the pool budget — scheduler docstring);
    with ``n_streams=None`` the pool is unbounded and occupancy is 1.0 by
    definition.  Memoized per Plan object, so recording a dispatch costs a
    dict hit.
    """
    st = _plan_stats_cache.get(id(plan))
    if st is not None:
        return st
    by_op: dict = {}
    bulk_tasks = 0
    pool_tasks = 0
    pool_waves = 0
    for level in plan.levels:
        level_pool = 0
        for bt in level:
            by_op[bt.op] = by_op.get(bt.op, 0) + bt.size
            if bt.op in sch.BULK_OPS:
                bulk_tasks += bt.size
            else:
                level_pool += bt.size
        if level_pool:
            pool_waves += 1
            pool_tasks += level_pool
    if plan.n_streams and pool_waves:
        occupancy = pool_tasks / (pool_waves * plan.n_streams)
    else:
        occupancy = 1.0 if pool_tasks else 0.0
    st = {
        "plan": plan.kind,
        "waves": len(plan.levels),
        "launches": plan.n_batches,
        "tasks": bulk_tasks + pool_tasks,
        "bulk_tasks": bulk_tasks,
        "pool_tasks": pool_tasks,
        "n_streams": plan.n_streams,
        "occupancy": occupancy,
        "by_op": by_op,
    }
    _plan_stats_cache[id(plan)] = st
    return st


def record_dispatch(kind: str, plan: Plan, *, backend: str, batched: bool) -> None:
    """Count + log one host-side dispatch of ``plan`` (obs must be enabled;
    callers guard — and must never call this at trace time: under jit the
    program body runs once per trace, so an in-trace record would count
    compilations, not dispatches.  The eager run_* entry points check
    ``isinstance(operand, jax.core.Tracer)`` and log a retrace counter
    instead; the jitted fast paths record from their *callers* in
    predict/update, where operands are concrete)."""
    st = plan_wave_stats(plan)
    obs.inc(f"executor.dispatch.{kind}")
    obs.inc("executor.launches", st["launches"])
    for op, cnt in st["by_op"].items():
        obs.inc(f"executor.tasks.{op}", cnt)
    obs.event(
        "executor.wave",
        dispatch=kind,
        backend=backend,
        batched=bool(batched),
        **st,
    )


def _cholesky_batch(op: str, tasks: Sequence[sch.Task], m: int) -> Batch:
    slot = tiling.packed_index
    tasks = tuple(tasks)
    if op == sch.POTRF:
        d = _arr([slot(j, j, m) for _, _, j, _ in tasks])
        return Batch(op, tasks, out=d, a=d)
    if op == sch.TRSM:
        diag = _arr([slot(j, j, m) for _, _, j, _ in tasks])
        tgt = _arr([slot(i, j, m) for _, i, j, _ in tasks])
        return Batch(op, tasks, out=tgt, a=diag, b=tgt)
    if op == sch.SYRK:
        tgt = _arr([slot(i, i, m) for _, i, _, _ in tasks])
        panel = _arr([slot(i, j, m) for _, i, j, _ in tasks])
        return Batch(op, tasks, out=tgt, a=tgt, b=panel)
    if op == sch.GEMM:
        tgt = _arr([slot(i, k, m) for _, i, _, k in tasks])
        pa = _arr([slot(i, j, m) for _, i, j, _ in tasks])
        pb = _arr([slot(k, j, m) for _, _, j, k in tasks])
        return Batch(op, tasks, out=tgt, a=tgt, b=pa, c=pb)
    raise ValueError(op)


def _solve_batch(op: str, tasks: Sequence[sch.Task], m: int, lower: bool) -> Batch:
    slot = tiling.packed_index
    tasks = tuple(tasks)
    if op == sch.TRSV:
        rows = _arr([i for _, i, _, _ in tasks])
        diag = _arr([slot(i, i, m) for _, i, _, _ in tasks])
        return Batch(op, tasks, out=rows, a=diag)
    if op == sch.GEMV:
        dst = _arr([i for _, i, _, _ in tasks])
        src = _arr([j for _, _, j, _ in tasks])
        tiles = _arr(
            [slot(i, j, m) if lower else slot(j, i, m) for _, i, j, _ in tasks]
        )
        return Batch(op, tasks, out=dst, a=tiles, b=src)
    raise ValueError(op)


def _compile(schedule: sch.Schedule, n_streams: Optional[int], batch_fn) -> Plan:
    levels = []
    for level in schedule.levels:
        batches = []
        for op, tasks in sch.split_by_op(level).items():
            for chunk in sch.chunk_tasks(tasks, n_streams):
                batches.append(batch_fn(op, chunk, schedule.m_tiles))
        levels.append(tuple(batches))
    return Plan(schedule.kind, schedule.m_tiles, n_streams, tuple(levels))


@functools.lru_cache(maxsize=None)
def cholesky_plan(m_tiles: int, n_streams: Optional[int] = None) -> Plan:
    """``None``: whole-ASAP-level batches (TPU-native limit).  Finite: the
    wavefront schedule — waves of <= n_streams ready tasks, critical-path
    first, which co-batches trailing updates of column j with the panel of
    column j+1 exactly like the paper's round-robin stream pool."""
    if n_streams is None:
        schedule = sch.build_schedule(m_tiles)
    else:
        schedule = sch.build_wavefront_schedule(m_tiles, n_streams, kind="cholesky")
    return _compile(schedule, n_streams, _cholesky_batch)


@functools.lru_cache(maxsize=None)
def solve_plan(
    m_tiles: int, *, lower: bool = True, n_streams: Optional[int] = None
) -> Plan:
    kind = "forward" if lower else "backward"
    if n_streams is None:
        schedule = sch.build_solve_schedule(m_tiles, lower=lower)
    else:
        schedule = sch.build_wavefront_schedule(m_tiles, n_streams, kind=kind)
    return _compile(
        schedule, n_streams, functools.partial(_solve_batch, lower=lower)
    )


# ---------------------------------------------------------------------------
# Execution.
# ---------------------------------------------------------------------------


def m_tiles_of_packed(packed: jax.Array) -> int:
    """Tile count M of a packed (..., T, m, m) store, validating T = M(M+1)/2."""
    t = packed.shape[-3]
    m_tiles = int((np.sqrt(8 * t + 1) - 1) // 2)
    if tiling.num_packed_tiles(m_tiles) != t:
        raise ValueError(f"{t} is not a triangular number of tiles")
    return m_tiles


def _env_ops(batched: bool):
    """(take, put, add) buffer accessors for unbatched / problem-batched envs.

    Unbatched buffers gather/scatter on axis 0; batched buffers carry the
    problem axis B first and gather/scatter on axis 1 — same index arrays,
    same Plan.
    """
    if batched:
        return (
            lambda buf, idx: buf[:, idx],
            lambda buf, idx, val: buf.at[:, idx].set(val),
            lambda buf, idx, val: buf.at[:, idx].add(val),
        )
    return (
        lambda buf, idx: buf[idx],
        lambda buf, idx, val: buf.at[idx].set(val),
        lambda buf, idx, val: buf.at[idx].add(val),
    )


def _fleet_shard(mesh, batched: bool):
    """Layout pin for B-leading buffers: identity without a mesh (or for
    unbatched programs — a single problem has no axis to shard)."""
    if mesh is None or not batched:
        return lambda a: a
    return lambda a: dist_sharding.fleet_hint(a, mesh)


def _tile_dispatch(fn, batched: bool, mode: str = "flat"):
    """Lift a per-tile op to a (possibly problem-batched) batched launch.

    Unbatched: one ``jax.vmap`` over the gathered G tiles, as before.
    Batched (operands (B, G, ...)): ``mode="flat"`` reshapes to (B*G, ...)
    so the ONE launch's existing batch axis — the Pallas grid — absorbs B;
    ``mode="vmap"`` nests a second ``jax.vmap`` over the problem axis
    instead.  Both produce (B, G, ...) results; fig9 benchmarks the two.
    """
    f = jax.vmap(fn)
    if not batched:
        return f
    if mode == "vmap":
        return jax.vmap(f)
    if mode != "flat":
        raise ValueError(f"batch_dispatch must be 'flat' or 'vmap', got {mode!r}")

    def flat(*arrays):
        b, g = arrays[0].shape[:2]
        out = f(*[a.reshape((b * g,) + a.shape[2:]) for a in arrays])
        unflatten = lambda o: o.reshape((b, g) + o.shape[1:])
        return jax.tree_util.tree_map(unflatten, out)  # multi-output ops too

    return flat


def run_cholesky(
    packed: jax.Array,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    batch_dispatch: str = "flat",
) -> jax.Array:
    """Factor a packed store K -> L by walking the level schedule.

    Each Batch is one gather + one batched kernel + one scatter; tasks inside
    a level are mutually independent (ASAP antichain), so batches may contain
    tasks of *different* columns — the cross-column overlap that the paper
    obtains from HPX dataflow over the stream pool.

    packed: (T, m, m), or (B, T, m, m) for B independent problems driven by
    the same lru-cached Plan (every launch then covers B x chunk tiles).
    """
    batched = packed.ndim == 4
    take, put, _ = _env_ops(batched)
    plan = cholesky_plan(m_tiles_of_packed(packed), n_streams)
    potrf, trsm, syrk, gemm = get_ops(backend)
    potrf_b = _tile_dispatch(potrf, batched, batch_dispatch)
    trsm_b = _tile_dispatch(trsm, batched, batch_dispatch)
    syrk_b = _tile_dispatch(
        functools.partial(syrk, update_dtype=update_dtype), batched, batch_dispatch
    )
    gemm_b = _tile_dispatch(
        functools.partial(gemm, update_dtype=update_dtype), batched, batch_dispatch
    )
    for level in plan.levels:
        for bt in level:
            with _SCOPE.named_scope(bt.op):
                if bt.op == sch.POTRF:
                    packed = put(packed, bt.out, potrf_b(take(packed, bt.a)))
                elif bt.op == sch.TRSM:
                    packed = put(
                        packed, bt.out, trsm_b(take(packed, bt.a), take(packed, bt.b))
                    )
                elif bt.op == sch.SYRK:
                    packed = put(
                        packed, bt.out, syrk_b(take(packed, bt.a), take(packed, bt.b))
                    )
                else:
                    packed = put(
                        packed,
                        bt.out,
                        gemm_b(take(packed, bt.a), take(packed, bt.b), take(packed, bt.c)),
                    )
    return packed


def _trsv_batch(lii: jax.Array, x: jax.Array, transpose: bool) -> jax.Array:
    """Batched diagonal-tile solve.

    lii (..., G, m, m); x (..., G, m) vector chunks or (..., G, Q, m, mq)
    matrix tile-rows, where ``...`` is the optional problem-batch axis —
    ``triangular_solve`` broadcasts over all leading axes.
    """
    if x.ndim == lii.ndim - 1:  # vector rhs chunks
        sol = jax.lax.linalg.triangular_solve(
            lii, x[..., None], left_side=True, lower=True, transpose_a=transpose
        )
        return sol[..., 0]
    liiq = jnp.broadcast_to(
        lii[..., None, :, :], x.shape[:-2] + lii.shape[-2:]
    )
    return jax.lax.linalg.triangular_solve(
        liiq, x, left_side=True, lower=True, transpose_a=transpose
    )


# ---------------------------------------------------------------------------
# Whole-pipeline program execution (DESIGN.md §7).
#
# The program plan generalizes the single packed operand to a named *buffer
# environment*:
#
#   "packed"  (T, m, m)       covariance tiles -> Cholesky factor (in place)
#   "y"       (M, m)          y chunks -> beta (forward substitution)
#   "alpha"   (M, m)          beta -> alpha (backward substitution)
#   "cross"   (Q*M, m, m)     cross-covariance tile grid K_{X̂,X} (flat)
#   "mean"    (Q, m)          predictive-mean chunks
#   "v"       (M, Q, m, m)    uncertainty workspace V = L^{-1} K_{X,X̂}
#   "prior"   (Q*Q, m, m)     covariance head: prior test tiles -> posterior
#                             covariance tiles
#             (Q, m)          variance head: prior diagonal -> posterior
#                             variance chunks
#
# plus the read-only feature blocks xc (M, m, D) / xtc (Q, m, D).  One
# run_program walks the fused schedule issuing per-level multi-op batches;
# SYRK and GEMM tasks of a level are dispatched as a single fused
# trailing-update launch (TRAIL) since their batched kernel is identical
# (SYRK is GEMM with both panels equal).
# ---------------------------------------------------------------------------

TRAIL = sch.TRAIL_GROUP  # fused SYRK+GEMM dispatch group (program plans only)


def _program_batch(
    op: str, tasks: Sequence[sch.Task], m: int, q_tiles: int, head: sch.Head
) -> Batch:
    """Gather/scatter indices of one program batch (buffer roles fixed by op;
    a PRIOR tile lands in the covariance head's flat Q x Q grid, or in the
    variance head's row p of diagonal chunks)."""
    slot = tiling.packed_index
    tasks = tuple(tasks)
    if op in (sch.POTRF, sch.TRSM):
        return _cholesky_batch(op, tasks, m)
    if op == TRAIL:
        tgt, pa, pb = [], [], []
        for t in tasks:
            _, i, j, k = t
            if t[0] == sch.SYRK:
                tgt.append(slot(i, i, m))
                pa.append(slot(i, j, m))
                pb.append(slot(i, j, m))
            else:
                tgt.append(slot(i, k, m))
                pa.append(slot(i, j, m))
                pb.append(slot(k, j, m))
        return Batch(op, tasks, out=_arr(tgt), a=_arr(tgt), b=_arr(pa), c=_arr(pb))
    if op in (sch.TRSV, sch.GEMV):
        return _solve_batch(op, tasks, m, lower=True)
    if op in (sch.TRSV_B, sch.GEMV_B):
        base = _solve_batch(
            sch.TRSV if op == sch.TRSV_B else sch.GEMV, tasks, m, lower=False
        )
        return dataclasses.replace(base, op=op, tasks=tasks)
    if op == sch.ASSEMBLE:
        rows = _arr([i for _, i, _, _ in tasks])
        cols = _arr([j for _, _, j, _ in tasks])
        slots = _arr([slot(i, j, m) for _, i, j, _ in tasks])
        return Batch(op, tasks, out=slots, a=rows, b=cols)
    if op == sch.CROSS:
        p = _arr([i for _, i, _, _ in tasks])
        q = _arr([j for _, _, j, _ in tasks])
        return Batch(op, tasks, out=_arr([i * m + j for _, i, j, _ in tasks]), a=p, b=q)
    if op == sch.PRIOR:
        p = _arr([i for _, i, _, _ in tasks])
        q = _arr([j for _, _, j, _ in tasks])
        if head == sch.Head.VARIANCE:
            return Batch(op, tasks, out=p, a=p, b=q)
        return Batch(
            op, tasks, out=_arr([i * q_tiles + j for _, i, j, _ in tasks]), a=p, b=q
        )
    if op == sch.XGEMV:
        rows = _arr([i for _, i, _, _ in tasks])
        return Batch(op, tasks, out=rows, a=rows)
    if op == sch.VINIT:
        rows = _arr([i for _, i, _, _ in tasks])
        return Batch(op, tasks, out=rows, a=rows)
    if op in (sch.VTRSV, sch.VGEMV):
        # same row/tile indexing as the vector forward solve, on the v buffer
        base = _solve_batch(
            sch.TRSV if op == sch.VTRSV else sch.GEMV, tasks, m, lower=True
        )
        return dataclasses.replace(base, op=op, tasks=tasks)
    if op == sch.GRAM:
        return Batch(op, tasks, out=_arr([]), a=_arr([]))
    raise ValueError(op)


@functools.lru_cache(maxsize=None)
def program_plan(
    m_tiles: int,
    q_tiles: int,
    head: sch.Head = sch.Head.NONE,
    n_streams: Optional[int] = None,
) -> Plan:
    """Compile the fused prediction program into batched launches.

    ``head`` picks the closing uncertainty head (:class:`scheduler.Head`; a
    bool still reads as the old ``uncertainty`` flag) and joins the lru key.
    ``n_streams=None``: ASAP levels of the whole-pipeline DAG (cross tiles
    at level 0 alongside assembly, solve rows leveled against the columns
    that produce their tiles).  Finite: the cross-stage wavefront schedule
    — waves of <= n_streams simultaneously-ready tasks, critical-path
    first, so solve rows and cross assembly ride the tail of Cholesky
    columns (paper Fig. 5).
    """
    head = sch.Head(head)
    if n_streams is None:
        schedule = sch.build_program_schedule(m_tiles, q_tiles, head=head)
    else:
        schedule = sch.build_wavefront_schedule(
            m_tiles,
            n_streams,
            kind="program",
            q_tiles=q_tiles,
            head=head,
        )
    levels = []
    for level in schedule.levels:
        groups: dict = {}
        for t in level:
            groups.setdefault(sch.dispatch_group(t[0]), []).append(t)
        batches = []
        for gop, tasks in groups.items():
            # BULK ops are one batched custom-kernel launch regardless of the
            # pool size (see scheduler.BULK_OPS) — never chunk them.
            width = None if gop in sch.BULK_OPS else n_streams
            for chunk in sch.chunk_tasks(tasks, width):
                batches.append(_program_batch(gop, chunk, m_tiles, q_tiles, head))
        levels.append(tuple(batches))
    return Plan("program", m_tiles, n_streams, tuple(levels))


@functools.lru_cache(maxsize=None)
def lowrank_plan(
    mu_tiles: int, n_tiles: int, n_streams: Optional[int] = None
) -> Plan:
    """Compile the LRGEMM bulk family (DESIGN.md §14) into ONE batched launch.

    The lowrank schedule is a single level of ``mu_tiles * n_tiles``
    independent tile contractions over the K_un grid; like every BULK_OPS
    family it is never chunked by the stream pool.  The Plan depends only on
    the (mu_tiles, n_tiles) tile geometry — B-invariant, so every fleet
    width and every problem batch reuses the same cache entry.
    """
    tasks = tuple(sch.lowrank_tasks(mu_tiles, n_tiles))
    batch = Batch(
        sch.LRGEMM,
        tasks,
        out=_arr([p for _, p, _, _ in tasks]),           # c chunk rows
        a=_arr([p * n_tiles + j for _, p, j, _ in tasks]),  # flat K_un slots
        b=_arr([j for _, _, j, _ in tasks]),             # training chunks
    )
    return Plan("lowrank", mu_tiles, n_streams, ((batch,),))


def run_lowrank_contraction(
    kun: jax.Array,
    yc: jax.Array,
    *,
    backend: str = "jnp",
    batch_dispatch: str = "flat",
    n_streams: Optional[int] = None,
) -> jax.Array:
    """c = K_un y through the LRGEMM family: c_p = sum_j K_un[p, j] y_j.

    ``kun`` (MU, M, m, m) cross-covariance tile grid (rows = inducing
    points, cols = training points), ``yc`` (M, m) training chunks — or
    ``(B, ...)`` problem-batched operands driven by the SAME lru-cached
    Plan.  One gather + ONE batched tile matvec (jnp or the Pallas LRGEMM
    kernel through ``_tile_dispatch``) + one scatter-add; ragged problems
    need no masking here because padded K_un columns are assembled as zero.
    """
    batched = kun.ndim == 5
    take, _, add = _env_ops(batched)
    mu_tiles, n_tiles = kun.shape[-4], kun.shape[-3]
    plan = lowrank_plan(mu_tiles, n_tiles, n_streams)
    mv = _tile_dispatch(get_lrgemm_op(backend), batched, batch_dispatch)
    kflat = kun.reshape(kun.shape[:-4] + (mu_tiles * n_tiles,) + kun.shape[-2:])
    out = jnp.zeros(kun.shape[:-4] + (mu_tiles, kun.shape[-2]), kun.dtype)
    for level in plan.levels:
        for bt in level:
            with _SCOPE.named_scope(bt.op):
                out = add(out, bt.out, mv(take(kflat, bt.a), take(yc, bt.b)))
    return out


def staged_launch_count(
    m_tiles: int, *, head: sch.Head = sch.Head.NONE, n_streams: Optional[int] = None
) -> int:
    """Batched launches the *staged* pipeline issues end-to-end.

    One covariance assembly + the factorization plan + both vector-solve
    plans + cross assembly + mean matvec; with either uncertainty head also
    the prior assembly, the B-tile transpose pack, the matrix forward-solve
    plan, the gram (V^T V, or the column sums of V squared) and the
    subtraction from the prior.  The fused program plan must beat this
    strictly for M >= 8 (tests/test_executor.py).
    """
    n = 1 + cholesky_plan(m_tiles, n_streams).n_batches
    n += solve_plan(m_tiles, lower=True, n_streams=n_streams).n_batches
    n += solve_plan(m_tiles, lower=False, n_streams=n_streams).n_batches
    n += 1 + 1  # cross assembly, mean matvec
    if head != sch.Head.NONE:
        n += 1 + 1  # prior assembly, B-tile transpose pack
        n += solve_plan(m_tiles, lower=True, n_streams=n_streams).n_batches
        n += 1 + 1  # gram, prior - W subtraction
    return n


def _params_concrete(params) -> bool:
    """True iff the hyperparameters are concrete (not traced) leaves.

    The Pallas assembly kernels bake hyperparameters in as compile-time
    constants, which is impossible inside a gradient trace; callers use this
    to fall back to the differentiable jnp assembly tile (DESIGN.md §8).
    """
    return km.params_concrete(params)


def _cov_batch_fn(
    backend: str, params, nvr: int, nvc: int, symmetric: bool, kernel=None
):
    """Batched covariance-tile assembly: (G,m,D) x (G,m,D) -> (G,m,m).

    ``kernel`` picks the registered covariance family (None -> the paper's
    SE).  ``backend="pallas"`` requires concrete hyperparameters (they are
    baked into the kernel); under a gradient trace the params are tracers,
    so the differentiable jnp tile kernel is used instead — assembly is
    O(n^2), cheap relative to the O(n^3) tile BLAS which stays on Pallas.
    """
    kernel = km.resolve_kernel(kernel)
    if backend == "pallas" and _params_concrete(params):
        from repro.kernels import cov_assembly as cova
        from repro.kernels import ops as kops

        def pallas_fn(xa, xb, row0, col0):
            return cova.cov_tiles(
                xa,
                xb,
                row0,
                col0,
                kernel=kernel,
                params=params,
                n_valid_r=nvr,
                n_valid_c=nvc,
                symmetric=symmetric,
                interpret=kops._interpret(),
            )

        return pallas_fn

    def jnp_fn(xa, xb, row0, col0):
        f = lambda a, b, r, c: km.cov_tile(
            a, b, r, c, params, nvr, nvc, symmetric, kernel=kernel
        )
        return jax.vmap(f)(xa, xb, row0, col0)

    return jnp_fn


def _params_per_problem(params, kernel=None) -> bool:
    """True iff any hyperparameter leaf carries a problem-batch axis (B, ...)."""
    return km.params_per_problem(params, kernel)


def _cov_batch_fn_batched(
    backend: str, params, nvr, nvc, symmetric: bool, kernel=None
):
    """Problem-batched assembly: (B,G,m,D) x (B,G,m,D) -> (B,G,m,m).

    Shared hyperparameters (scalar leaves) flatten B into the single
    launch's batch axis and reuse :func:`_cov_batch_fn` (Pallas grid absorbs
    B).  Per-problem hyperparameters (leaves of shape (B,)) vmap the jnp
    tile kernel over the problem axis — the Pallas assembly kernel bakes
    hyperparameters in as compile-time constants, so it cannot vary them
    across the batch; assembly is O(n^2), cheap next to the tile BLAS.

    **Ragged batches (DESIGN.md §11):** ``nvr``/``nvc`` may be (B,) arrays
    of per-problem validity frontiers instead of one shared scalar.  On the
    jnp tile path the frontiers simply join the problem-axis vmap; on the
    Pallas path (concrete shared params) the (B,) frontiers expand to
    per-tile (B*G,) i32 operands and B problems of different valid sizes
    still share ONE flat kernel launch.
    """
    kernel = km.resolve_kernel(kernel)
    ragged = jnp.ndim(nvr) > 0 or jnp.ndim(nvc) > 0
    pallas_ok = backend == "pallas" and _params_concrete(params)
    if _params_per_problem(params, kernel) or (ragged and not pallas_ok):

        def per_problem(xa, xb, row0, col0):
            # mixed scalar/(B,) leaves are legal — normalize before the vmap
            b = xa.shape[0]
            pb = km.broadcast_params(params, b, kernel)
            nvr_b = jnp.broadcast_to(jnp.asarray(nvr), (b,))
            nvc_b = jnp.broadcast_to(jnp.asarray(nvc), (b,))

            def one(xa1, xb1, p, nr, nc):
                f = lambda a, b, r, c: km.cov_tile(
                    a, b, r, c, p, nr, nc, symmetric, kernel=kernel
                )
                return jax.vmap(f)(xa1, xb1, row0, col0)

            return jax.vmap(one, in_axes=(0, 0, 0, 0, 0))(xa, xb, pb, nvr_b, nvc_b)

        return per_problem

    if ragged:
        # concrete shared params on Pallas: per-problem frontiers become
        # per-tile (1,)-block operands of the ONE flattened launch.
        from repro.kernels import cov_assembly as cova
        from repro.kernels import ops as kops

        def flat_ragged(xa, xb, row0, col0):
            b, g = xa.shape[:2]
            nvr_t = jnp.repeat(jnp.broadcast_to(jnp.asarray(nvr), (b,)), g)
            nvc_t = jnp.repeat(jnp.broadcast_to(jnp.asarray(nvc), (b,)), g)
            out = cova.cov_tiles(
                xa.reshape((b * g,) + xa.shape[2:]),
                xb.reshape((b * g,) + xb.shape[2:]),
                jnp.tile(row0, b),
                jnp.tile(col0, b),
                kernel=kernel,
                params=params,
                n_valid_r=nvr_t,
                n_valid_c=nvc_t,
                symmetric=symmetric,
                interpret=kops._interpret(),
            )
            return out.reshape((b, g) + out.shape[1:])

        return flat_ragged

    single = _cov_batch_fn(backend, params, nvr, nvc, symmetric, kernel)

    def flat(xa, xb, row0, col0):
        b, g = xa.shape[:2]
        out = single(
            xa.reshape((b * g,) + xa.shape[2:]),
            xb.reshape((b * g,) + xb.shape[2:]),
            jnp.tile(row0, b),
            jnp.tile(col0, b),
        )
        return out.reshape((b, g) + out.shape[1:])

    return flat


def run_program(
    xc: jax.Array,
    yc: jax.Array,
    xtc: jax.Array,
    params,
    n_valid,
    nt_valid,
    *,
    head: sch.Head = sch.Head.NONE,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    batch_dispatch: str = "flat",
    mesh=None,
    kernel=None,
):
    """Execute the fused prediction pipeline as one multi-stage program.

    xc (M, m, D) / yc (M, m) / xtc (Q, m, D) are the padded feature and
    target blocks; ``n_valid`` / ``nt_valid`` the unpadded row counts.
    Returns the final buffer environment (see module section docstring):
    ``env["mean"]`` holds the predictive-mean chunks, ``env["prior"]`` the
    head's output — the posterior-covariance tiles (``Head.COVARIANCE``) or
    the posterior-variance chunks (``Head.VARIANCE``) — and
    ``env["packed"]`` / ``env["alpha"]`` the factor/weights slices a
    PosteriorState caches.

    **Problem batching:** with xc (B, M, m, D) / yc (B, M, m) /
    xtc (B, Q, m, D) — B independent problems of identical tile geometry —
    every env buffer gains the leading B axis and the SAME lru-cached Plan
    drives all of them: identical launch count, each launch B times wider
    (DESIGN.md §9).  Hyperparameters may be shared (scalar leaves) or
    per-problem (leaves of shape (B,)).  ``batch_dispatch`` picks how the
    tile kernels absorb B: ``"flat"`` folds it into the launch's batch/grid
    axis, ``"vmap"`` nests one more vmap level.

    **Ragged batches:** ``n_valid``/``nt_valid`` may also be (B,) arrays of
    per-problem row counts (or traced scalars) — problems of *different*
    valid sizes share the bucket's tile geometry, the same Plan, and the
    same jit trace; only the masked assembly sees the frontiers
    (DESIGN.md §11).

    **Sharded batches (DESIGN.md §12):** with a ``mesh``, every B-leading
    buffer — the inputs and all named env buffers — is pinned to the fleet
    layout (B over the mesh's DP axes, tiles replicated per problem) via
    ``with_sharding_constraint``.  Problems are independent, so GSPMD
    partitions every launch along B with zero collectives.  The mesh never
    reaches :func:`program_plan` — Plans stay shard-invariant.

    **Kernel zoo (DESIGN.md §13):** ``kernel`` picks the covariance family
    (None -> the paper's SE).  Only the ASSEMBLE/CROSS/PRIOR op payloads
    change; the kernel never reaches :func:`program_plan` either — Plans
    stay kernel-invariant and are reused across kernels.
    """
    batched = xc.ndim == 4
    m_tiles, m = xc.shape[-3], xc.shape[-2]
    q_tiles = xtc.shape[-3]
    head = sch.Head(head)
    plan = program_plan(m_tiles, q_tiles, head, n_streams)
    if obs.enabled():
        if isinstance(xc, jax.core.Tracer):
            obs.inc("executor.traces.run_program")
        else:
            record_dispatch("run_program", plan, backend=backend, batched=batched)
    dtype = xc.dtype
    lead = (xc.shape[0],) if batched else ()
    take, put, add = _env_ops(batched)
    Z = "z" if batched else ""  # einsum prefix for the problem-batch axis
    shard = _fleet_shard(mesh, batched)
    xc, yc, xtc = shard(xc), shard(yc), shard(xtc)

    potrf, trsm, _, gemm = get_ops(backend)
    potrf_b = _tile_dispatch(potrf, batched, batch_dispatch)
    trsm_b = _tile_dispatch(trsm, batched, batch_dispatch)
    trail_b = _tile_dispatch(
        functools.partial(gemm, update_dtype=update_dtype), batched, batch_dispatch
    )
    cov_fn = _cov_batch_fn_batched if batched else _cov_batch_fn
    asm = cov_fn(backend, params, n_valid, n_valid, True, kernel)
    crossf = cov_fn(backend, params, nt_valid, n_valid, False, kernel)
    priorf = cov_fn(backend, params, nt_valid, nt_valid, False, kernel)

    env = {
        "packed": shard(
            jnp.zeros(lead + (tiling.num_packed_tiles(m_tiles), m, m), dtype)
        ),
        "y": yc,
        "alpha": shard(jnp.zeros_like(yc)),
        "cross": shard(jnp.zeros(lead + (q_tiles * m_tiles, m, m), dtype)),
        "mean": shard(jnp.zeros(lead + (q_tiles, m), dtype)),
    }
    if head != sch.Head.NONE:
        env["v"] = shard(jnp.zeros(lead + (m_tiles, q_tiles, m, m), dtype))
    if head == sch.Head.COVARIANCE:
        env["prior"] = shard(jnp.zeros(lead + (q_tiles * q_tiles, m, m), dtype))
    elif head == sch.Head.VARIANCE:
        env["prior"] = shard(jnp.zeros(lead + (q_tiles, m), dtype))

    def off(idx):  # tile index -> global row/col offset, i32 on device
        return jnp.asarray(idx * m, jnp.int32)

    def cross_grid():  # cross buffer viewed as the (..., Q, M, m, m) tile grid
        return env["cross"].reshape(lead + (q_tiles, m_tiles, m, m))

    for level in plan.levels:
        for bt in level:
            with _SCOPE.named_scope(bt.op):
                op, packed = bt.op, env["packed"]
                if op == sch.ASSEMBLE:
                    tiles = asm(take(xc, bt.a), take(xc, bt.b), off(bt.a), off(bt.b))
                    env["packed"] = put(packed, bt.out, tiles)
                elif op == sch.CROSS:
                    tiles = crossf(take(xtc, bt.a), take(xc, bt.b), off(bt.a), off(bt.b))
                    env["cross"] = put(env["cross"], bt.out, tiles)
                elif op == sch.PRIOR:
                    tiles = priorf(take(xtc, bt.a), take(xtc, bt.b), off(bt.a), off(bt.b))
                    if head == sch.Head.VARIANCE:  # diagonal tiles: keep k(x, x)
                        tiles = jnp.diagonal(tiles, axis1=-2, axis2=-1)
                    env["prior"] = put(env["prior"], bt.out, tiles)
                elif op == sch.POTRF:
                    env["packed"] = put(packed, bt.out, potrf_b(take(packed, bt.a)))
                elif op == sch.TRSM:
                    env["packed"] = put(
                        packed, bt.out, trsm_b(take(packed, bt.a), take(packed, bt.b))
                    )
                elif op == TRAIL:
                    env["packed"] = put(
                        packed,
                        bt.out,
                        trail_b(take(packed, bt.a), take(packed, bt.b), take(packed, bt.c)),
                    )
                elif op == sch.TRSV:
                    sol = _trsv_batch(take(packed, bt.a), take(env["y"], bt.out), False)
                    env["y"] = put(env["y"], bt.out, sol)
                    # publish the solved row into the backward pass's buffer
                    env["alpha"] = put(env["alpha"], bt.out, sol)
                elif op == sch.GEMV:
                    upd = jnp.einsum(
                        f"{Z}gab,{Z}gb->{Z}ga", take(packed, bt.a), take(env["y"], bt.b)
                    )
                    env["y"] = add(env["y"], bt.out, -upd.astype(dtype))
                elif op == sch.TRSV_B:
                    sol = _trsv_batch(take(packed, bt.a), take(env["alpha"], bt.out), True)
                    env["alpha"] = put(env["alpha"], bt.out, sol)
                elif op == sch.GEMV_B:
                    upd = jnp.einsum(
                        f"{Z}gba,{Z}gb->{Z}ga", take(packed, bt.a), take(env["alpha"], bt.b)
                    )
                    env["alpha"] = add(env["alpha"], bt.out, -upd.astype(dtype))
                elif op == sch.XGEMV:
                    rows = take(cross_grid(), bt.out)
                    env["mean"] = put(
                        env["mean"],
                        bt.out,
                        jnp.einsum(f"{Z}gqab,{Z}qb->{Z}ga", rows, env["alpha"]),
                    )
                elif op == sch.VINIT:
                    if batched:
                        cols = cross_grid()[:, :, bt.out]      # (B, Q, G, m, m)
                        vrows = cols.transpose(0, 2, 1, 4, 3)  # (B, G, Q, m, m)
                    else:
                        cols = cross_grid()[:, bt.out]         # (Q, G, m, m)
                        vrows = cols.transpose(1, 0, 3, 2)     # (G, Q, m, m)
                    env["v"] = put(env["v"], bt.out, vrows)
                elif op == sch.VTRSV:
                    sol = _trsv_batch(take(packed, bt.a), take(env["v"], bt.out), False)
                    env["v"] = put(env["v"], bt.out, sol)
                elif op == sch.VGEMV:
                    upd = jnp.einsum(
                        f"{Z}gab,{Z}gqbc->{Z}gqac", take(packed, bt.a), take(env["v"], bt.b)
                    )
                    env["v"] = add(env["v"], bt.out, -upd.astype(dtype))
                elif op == sch.GRAM and head == sch.Head.VARIANCE:
                    # diag(V^T V): column sums of V squared, element-wise in
                    # the buffer dtype — no Q x Q product is formed
                    vsq = jnp.sum(jnp.square(env["v"]), axis=(-4, -2))
                    env["prior"] = env["prior"] - vsq
                elif op == sch.GRAM:
                    w = jnp.einsum(f"{Z}ipab,{Z}iqac->{Z}pqbc", env["v"], env["v"])
                    env["prior"] = env["prior"] - w.reshape(
                        lead + (q_tiles * q_tiles, m, m)
                    )
                else:
                    raise ValueError(op)
    return env


def run_solve(
    lpacked: jax.Array,
    rhs: jax.Array,
    *,
    lower: bool = True,
    n_streams: Optional[int] = None,
) -> jax.Array:
    """Level-batched triangular solve on the packed factor.

    rhs: (M, m) vector chunks or (M, Q, m, mq) matrix tile rows; solved in
    place (functionally).  ``lower=True`` solves L x = rhs, else L^T x = rhs
    (reading the stored lower tiles transposed).  Unlike the old per-row
    loops there is no O(M) restacking: the rhs stays one array and every
    level is a single gather/einsum/scatter.

    With lpacked (B, T, m, m) and rhs (B, M, m) / (B, M, Q, m, mq) the same
    Plan solves B independent systems at once (DESIGN.md §9).
    """
    batched = lpacked.ndim == 4
    take, put, add = _env_ops(batched)
    m_tiles = rhs.shape[1] if batched else rhs.shape[0]
    if tiling.num_packed_tiles(m_tiles) != lpacked.shape[-3]:
        raise ValueError(
            f"rhs rows {m_tiles} inconsistent with packed store {lpacked.shape}"
        )
    plan = solve_plan(m_tiles, lower=lower, n_streams=n_streams)
    transpose = not lower
    matrix = rhs.ndim == (5 if batched else 4)
    Z = "z" if batched else ""
    if matrix:
        ein = f"{Z}gba,{Z}gqbc->{Z}gqac" if transpose else f"{Z}gab,{Z}gqbc->{Z}gqac"
    else:
        ein = f"{Z}gba,{Z}gb->{Z}ga" if transpose else f"{Z}gab,{Z}gb->{Z}ga"
    for level in plan.levels:
        for bt in level:
            with _SCOPE.named_scope(bt.op):
                if bt.op == sch.TRSV:
                    sol = _trsv_batch(take(lpacked, bt.a), take(rhs, bt.out), transpose)
                    rhs = put(rhs, bt.out, sol)
                else:
                    upd = jnp.einsum(ein, take(lpacked, bt.a), take(rhs, bt.b))
                    rhs = add(rhs, bt.out, -upd.astype(rhs.dtype))
    return rhs


# ---------------------------------------------------------------------------
# Streaming updates (DESIGN.md §10): block Cholesky append / rank update.
#
# The append plan's buffer environment:
#   "packed" (T_store, m, m)  the frozen existing factor (read-only)
#   "row"    (R + 1, m, m)    the appended tile-row; slot R is the corner
# plus the read-only feature chunks xc and the new row chunk x_row.  The
# rank-update plan's environment:
#   "packed" (T', m, m)       the factor, rewritten column by column
#   "w"      (M', m, m)       the rank-b carry blocks
#   "xaux/yaux/caux" (M', m, m)  per-column X / Y / C auxiliaries
# All buffers accept the optional leading problem-batch axis B (§9).
# ---------------------------------------------------------------------------


def _append_batch(
    op: str, tasks: Sequence[sch.Task], r_tiles: int, m_store: int
) -> Batch:
    """Gather/scatter indices of one append batch.

    The packed store may hold ``m_store`` tile-rows with ``m_store >
    r_tiles`` (refilling a partially padded trailing row reads only the
    frozen prefix rows < R but indexes slots of the full store).
    """
    slot = tiling.packed_index
    tasks = tuple(tasks)
    if op in (sch.UASM, sch.UASMD):
        cols = _arr([i for _, i, _, _ in tasks])
        return Batch(op, tasks, out=cols, a=cols)
    if op == sch.UTRSM:
        rows = _arr([i for _, i, _, _ in tasks])
        diag = _arr([slot(i, i, m_store) for _, i, _, _ in tasks])
        return Batch(op, tasks, out=rows, a=diag, b=rows)
    if op == sch.UGEMM:  # row_i -= row_j L(i,j)^T
        tgt = _arr([i for _, i, _, _ in tasks])
        src = _arr([j for _, _, j, _ in tasks])
        til = _arr([slot(i, j, m_store) for _, i, j, _ in tasks])
        return Batch(op, tasks, out=tgt, a=tgt, b=src, c=til)
    if op == sch.USYRK:  # corner -= row_i row_i^T
        tgt = _arr([r_tiles] * len(tasks))
        panel = _arr([i for _, i, _, _ in tasks])
        return Batch(op, tasks, out=tgt, a=tgt, b=panel)
    if op == sch.UPOTRF:
        d = _arr([r_tiles])
        return Batch(op, tasks, out=d, a=d)
    raise ValueError(op)


@functools.lru_cache(maxsize=None)
def update_append_plan(
    r_tiles: int, m_store: int, n_streams: Optional[int] = None
) -> Plan:
    """Compile the one-tile-row append DAG into batched launches."""
    if n_streams is None:
        schedule = sch.build_update_schedule(r_tiles, kind="update_append")
    else:
        schedule = sch.build_wavefront_schedule(
            r_tiles, n_streams, kind="update_append"
        )
    levels = []
    for level in schedule.levels:
        batches = []
        for op, tasks in sch.split_by_op(level).items():
            width = None if op in sch.BULK_OPS else n_streams
            for chunk in sch.chunk_tasks(tasks, width):
                batches.append(_append_batch(op, chunk, r_tiles, m_store))
        levels.append(tuple(batches))
    return Plan("update_append", r_tiles, n_streams, tuple(levels))


def run_append(
    lpacked: jax.Array,
    xc: jax.Array,
    x_row: jax.Array,
    params,
    r_tiles: int,
    n_valid_new,
    *,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    update_dtype=None,
    batch_dispatch: str = "flat",
    mesh=None,
    kernel=None,
) -> jax.Array:
    """Solve one appended tile-row against the frozen factor (DESIGN.md §10).

    lpacked: the existing packed factor, (T_store, m, m) or (B, T_store,
    m, m); xc the matching padded feature chunks; x_row (m, D) / (B, m, D)
    the (padded) chunk of the appended row; ``r_tiles`` the number of frozen
    prefix rows the new row is solved against (``r_tiles == m_store`` grows
    the factor; ``r_tiles < m_store`` recomputes tile-row ``r_tiles`` of the
    store in place — the trailing partially padded row in the scalar case,
    or ANY interior row of a ragged batch's sweep, see
    ``update.extend_state_ragged``).  ``n_valid_new`` is the total valid
    observation count *after* the append — a scalar, a traced scalar, or a
    (B,) per-problem array for ragged batches.  For problems whose frontier
    lies at or below ``r_tiles * m`` the masked assembly degenerates to
    identity/zero tiles and the recomputed row reproduces the padding
    contract exactly (the refill is idempotent).

    Returns the row buffer (R + 1, m, m): the R solved off-diagonal tiles
    followed by the factored corner.  The caller scatters it into a grown
    or refilled packed store (tiling.grow_packed_indices /
    tiling.replace_row_indices).
    """
    batched = xc.ndim == 4
    m_store = xc.shape[-3]
    m = xc.shape[-2]
    if not 0 <= r_tiles <= m_store:
        raise ValueError(
            f"r_tiles must be in [0, m_store] = [0, {m_store}] "
            f"(m_store grows, less refills a row in place); got {r_tiles}"
        )
    if tiling.num_packed_tiles(m_store) != lpacked.shape[-3]:
        raise ValueError(
            f"feature chunks ({m_store} tiles) inconsistent with packed "
            f"store {lpacked.shape}"
        )
    plan = update_append_plan(r_tiles, m_store, n_streams)
    if obs.enabled():
        if isinstance(lpacked, jax.core.Tracer):
            obs.inc("executor.traces.run_append")
        else:
            record_dispatch("run_append", plan, backend=backend, batched=batched)
    take, put, _ = _env_ops(batched)
    lead = (xc.shape[0],) if batched else ()
    dtype = lpacked.dtype
    shard = _fleet_shard(mesh, batched)
    lpacked, xc, x_row = shard(lpacked), shard(xc), shard(x_row)

    potrf, trsm, syrk, gemm = get_ops(backend)
    potrf_b = _tile_dispatch(potrf, batched, batch_dispatch)
    trsm_b = _tile_dispatch(trsm, batched, batch_dispatch)
    syrk_b = _tile_dispatch(
        functools.partial(syrk, update_dtype=update_dtype), batched, batch_dispatch
    )
    gemm_b = _tile_dispatch(
        functools.partial(gemm, update_dtype=update_dtype), batched, batch_dispatch
    )
    cov_fn = _cov_batch_fn_batched if batched else _cov_batch_fn
    # both axes mask at n_valid_new: prefix columns past a problem's
    # frontier (possible only in the ragged sweep) zero out, and for the
    # scalar callers every prefix column < r_tiles*m <= n_valid_new is
    # valid anyway — identical to the old r_tiles*m column mask.
    crossf = cov_fn(backend, params, n_valid_new, n_valid_new, False, kernel)
    diagf = cov_fn(backend, params, n_valid_new, n_valid_new, True, kernel)

    row = shard(jnp.zeros(lead + (r_tiles + 1, m, m), dtype))
    row0 = r_tiles * m

    def bcast_row(g):  # the row chunk, repeated for each gathered tile
        if batched:
            return jnp.broadcast_to(x_row[:, None], lead + (g,) + x_row.shape[1:])
        return jnp.broadcast_to(x_row[None], (g,) + x_row.shape)

    def off(idx):
        return jnp.asarray(idx * m, jnp.int32)

    for level in plan.levels:
        for bt in level:
            with _SCOPE.named_scope(bt.op):
                if bt.op == sch.UASM:
                    tiles = crossf(
                        bcast_row(bt.size), take(xc, bt.a),
                        jnp.full((bt.size,), row0, jnp.int32), off(bt.a),
                    )
                    row = put(row, bt.out, tiles)
                elif bt.op == sch.UASMD:
                    tiles = diagf(
                        bcast_row(1), bcast_row(1),
                        jnp.full((1,), row0, jnp.int32),
                        jnp.full((1,), row0, jnp.int32),
                    )
                    row = put(row, bt.out, tiles)
                elif bt.op == sch.UTRSM:
                    row = put(
                        row, bt.out, trsm_b(take(lpacked, bt.a), take(row, bt.b))
                    )
                elif bt.op == sch.UGEMM:
                    row = put(
                        row,
                        bt.out,
                        gemm_b(take(row, bt.a), take(row, bt.b), take(lpacked, bt.c)),
                    )
                elif bt.op == sch.USYRK:
                    row = put(
                        row, bt.out, syrk_b(take(row, bt.a), take(row, bt.b))
                    )
                elif bt.op == sch.UPOTRF:
                    row = put(row, bt.out, potrf_b(take(row, bt.a)))
                else:
                    raise ValueError(bt.op)
    return row


# -- rank-b up/downdate ------------------------------------------------------


def _rank_batch(op: str, tasks: Sequence[sch.Task], m: int) -> Batch:
    """Gather/scatter indices of one rank-update batch."""
    slot = tiling.packed_index
    tasks = tuple(tasks)
    if op == sch.UPREP:
        rows = _arr([i for _, i, _, _ in tasks])
        diag = _arr([slot(i, i, m) for _, i, _, _ in tasks])
        return Batch(op, tasks, out=rows, a=diag)
    if op == sch.UPROW:  # L'(i,j) = L(i,j) X_j^T + s W_i Y_j^T
        tgt = _arr([slot(i, j, m) for _, i, j, _ in tasks])
        wrows = _arr([i for _, i, _, _ in tasks])
        cols = _arr([j for _, _, j, _ in tasks])
        return Batch(op, tasks, out=tgt, a=tgt, b=wrows, c=cols)
    if op == sch.UCARRY:  # W_i <- (W_i - L'(i,j) Y_j) C_j^{-T}
        wrows = _arr([i for _, i, _, _ in tasks])
        til = _arr([slot(i, j, m) for _, i, j, _ in tasks])
        cols = _arr([j for _, _, j, _ in tasks])
        return Batch(op, tasks, out=wrows, a=til, b=wrows, c=cols)
    raise ValueError(op)


@functools.lru_cache(maxsize=None)
def update_rank_plan(m_tiles: int, n_streams: Optional[int] = None) -> Plan:
    """Compile the blocked cholupdate sweep into batched launches."""
    if n_streams is None:
        schedule = sch.build_update_schedule(m_tiles, kind="update_rank")
    else:
        schedule = sch.build_wavefront_schedule(
            m_tiles, n_streams, kind="update_rank"
        )
    return _compile(schedule, n_streams, _rank_batch)


def get_update_ops(backend: str, sign: float):
    """(uprep, uprow, ucarry) per-tile ops of the rank-update sweep.

    ``sign=+1.0``: L' L'^T = L L^T + W W^T (eviction of a leading window is
    a *positive* update of the trailing factor).  ``sign=-1.0``: the true
    hyperbolic downdate L L^T - W W^T; its Cholesky heads go NaN when the
    downdated matrix is not positive definite — callers check and fall back
    to a full refactorization (repro.core.update).
    """
    if backend == "pallas":
        from repro.kernels import ops as kops

        potrf_tile = kops.potrf
        carry = kops.carry_update
    elif backend == "jnp":
        potrf_tile = _potrf_jnp

        def carry(wi, lij, yj, cj):
            b = wi - lij @ yj
            return jax.lax.linalg.triangular_solve(
                cj, b, left_side=False, lower=True, transpose_a=True
            )
    else:
        raise ValueError(f"unknown backend: {backend}")

    def uprep(ljj, wj):
        d = ljj @ ljj.T + sign * (wj @ wj.T)
        lnew = potrf_tile(d)
        x = jax.lax.linalg.triangular_solve(lnew, ljj, left_side=True, lower=True)
        y = jax.lax.linalg.triangular_solve(lnew, wj, left_side=True, lower=True)
        eye = jnp.eye(ljj.shape[-1], dtype=ljj.dtype)
        c = potrf_tile(eye - sign * (y.T @ y))
        return lnew, x, y, c

    def uprow(lij, wi, xj, yj):
        return (lij @ xj.T + sign * (wi @ yj.T)).astype(lij.dtype)

    return uprep, uprow, carry


def run_rank_update(
    lpacked: jax.Array,
    w: jax.Array,
    *,
    sign: float = 1.0,
    n_streams: Optional[int] = None,
    backend: str = "jnp",
    batch_dispatch: str = "flat",
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Blocked rank-b up/downdate: L' L'^T = L L^T + sign * W W^T.

    lpacked (T, m, m) packed factor; w (M, m, m) carry blocks (one per
    tile-row; unused trailing columns of a rank-b < m carry must be zero —
    they propagate zeros through Y and keep C identity there).  Optional
    leading problem-batch axis B on both (§9).  Returns (new factor, final
    carry).  NaNs in the new factor signal a failed (non-PD) downdate.
    """
    batched = lpacked.ndim == 4
    take, put, _ = _env_ops(batched)
    m_tiles = w.shape[1] if batched else w.shape[0]
    if tiling.num_packed_tiles(m_tiles) != lpacked.shape[-3]:
        raise ValueError(
            f"carry rows {m_tiles} inconsistent with packed store {lpacked.shape}"
        )
    m = lpacked.shape[-1]
    lead = (lpacked.shape[0],) if batched else ()
    plan = update_rank_plan(m_tiles, n_streams)
    if obs.enabled():
        if isinstance(lpacked, jax.core.Tracer):
            obs.inc("executor.traces.run_rank_update")
        else:
            record_dispatch(
                "run_rank_update", plan, backend=backend, batched=batched
            )
    uprep, uprow, ucarry = get_update_ops(backend, sign)
    uprep_b = _tile_dispatch(uprep, batched, batch_dispatch)
    uprow_b = _tile_dispatch(uprow, batched, batch_dispatch)
    ucarry_b = _tile_dispatch(ucarry, batched, batch_dispatch)

    shard = _fleet_shard(mesh, batched)
    lpacked, w = shard(lpacked), shard(w)
    xaux = shard(jnp.zeros(lead + (m_tiles, m, m), lpacked.dtype))
    yaux = shard(jnp.zeros_like(xaux))
    caux = shard(jnp.zeros_like(xaux))
    for level in plan.levels:
        for bt in level:
            with _SCOPE.named_scope(bt.op):
                if bt.op == sch.UPREP:
                    lnew, x, y, c = uprep_b(take(lpacked, bt.a), take(w, bt.out))
                    lpacked = put(lpacked, bt.a, lnew)
                    xaux = put(xaux, bt.out, x)
                    yaux = put(yaux, bt.out, y)
                    caux = put(caux, bt.out, c)
                elif bt.op == sch.UPROW:
                    lpacked = put(
                        lpacked,
                        bt.out,
                        uprow_b(
                            take(lpacked, bt.a), take(w, bt.b),
                            take(xaux, bt.c), take(yaux, bt.c),
                        ),
                    )
                elif bt.op == sch.UCARRY:
                    w = put(
                        w,
                        bt.out,
                        ucarry_b(
                            take(w, bt.b), take(lpacked, bt.a),
                            take(yaux, bt.c), take(caux, bt.c),
                        ).astype(w.dtype),
                    )
                else:
                    raise ValueError(bt.op)
    return lpacked, w


# Expose every plan cache to obs.cache_stats() — plan-invariance regressions
# (a cache that grows per call instead of per geometry) become visible at
# runtime, not just in tests (DESIGN.md §15).
obs.register_cache("executor.cholesky_plan", cholesky_plan)
obs.register_cache("executor.solve_plan", solve_plan)
obs.register_cache("executor.program_plan", program_plan)
obs.register_cache("executor.lowrank_plan", lowrank_plan)
obs.register_cache("executor.update_append_plan", update_append_plan)
obs.register_cache("executor.update_rank_plan", update_rank_plan)
