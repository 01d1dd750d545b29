"""Fig. 5 analogue: schedule occupancy trace.

The paper shows an NVVP timeline of overlapping kernels.  Without a hardware
profiler, the equivalent structural artifact is the schedule itself: tasks
per level, op mix, width/critical-path summary — plus, since the Schedule is
now the real execution plan, the *executor's* per-level batch counts (which
must match ``Schedule.levels`` exactly) and the wavefront stream-pool
occupancy for finite ``n_streams`` (the static analogue of the paper's
timeline: how full the pool is per wave, and how often a wave co-issues
tasks of different columns).

The fused whole-pipeline program (DESIGN.md §7) gets its own trace: per-wave
op mixes showing substitution rows and cross-covariance assembly co-batched
into the tail of Cholesky columns, plus fused-vs-staged batched-launch
totals.
"""

from __future__ import annotations

from benchmarks.common import row
from repro.core import executor
from repro.core import scheduler as sch


def run(m_tiles: int = 16, out=print):
    s = sch.build_schedule(m_tiles)
    counts = s.op_counts()
    out(row(f"fig5/tasks/tiles{m_tiles}", 0.0, f"total={s.n_tasks}"))
    out(row(f"fig5/critical_path/tiles{m_tiles}", 0.0, f"levels={s.critical_path}"))
    out(row(f"fig5/max_width/tiles{m_tiles}", 0.0, f"width={s.max_width()}"))
    out(row(
        f"fig5/op_mix/tiles{m_tiles}", 0.0,
        f"potrf={counts['potrf']};trsm={counts['trsm']};"
        f"syrk={counts['syrk']};gemm={counts['gemm']}",
    ))
    # per-level occupancy (the 'timeline'): level -> number of parallel tasks
    widths = [len(l) for l in s.levels]
    head = ";".join(str(w) for w in widths[:12])
    out(row(f"fig5/level_widths/tiles{m_tiles}", 0.0, f"first12={head}"))
    avg = s.n_tasks / s.critical_path
    out(row(f"fig5/avg_parallelism/tiles{m_tiles}", 0.0, f"avg={avg:.2f}"))

    # -- executor plan: the schedule as the execution plan ------------------
    plan = executor.cholesky_plan(m_tiles, None)
    match = plan.level_task_counts() == widths
    out(row(
        f"fig5/executor_levels/tiles{m_tiles}", 0.0,
        f"match_schedule={match};levels={len(plan.levels)};batches={plan.n_batches}",
    ))
    assert match, "executor per-level batch counts diverged from Schedule.levels"

    # -- wavefront stream-pool occupancy (finite pools) ---------------------
    for ns in (1, 4, 16):
        wplan = executor.cholesky_plan(m_tiles, ns)
        waves = wplan.level_task_counts()
        occ = sum(waves) / (len(waves) * ns)
        cross = sum(
            1 for lvl in wplan.levels
            if len({t[2] for b in lvl for t in b.tasks}) > 1
        )
        out(row(
            f"fig5/wavefront/tiles{m_tiles}/streams{ns}", 0.0,
            f"waves={len(waves)};occupancy={occ:.3f};"
            f"cross_column_waves={cross};batches={wplan.n_batches}",
        ))

    # -- triangular-solve DAGs (the rest of the pipeline) -------------------
    for kind, lower in (("forward", True), ("backward", False)):
        ss = sch.build_solve_schedule(m_tiles, lower=lower)
        splan = executor.solve_plan(m_tiles, lower=lower, n_streams=None)
        match = splan.level_task_counts() == [len(l) for l in ss.levels]
        out(row(
            f"fig5/solve_{kind}/tiles{m_tiles}", 0.0,
            f"tasks={ss.n_tasks};levels={ss.critical_path};match_schedule={match}",
        ))

    # -- fused whole-pipeline program: the cross-stage wave trace -----------
    # The paper's Fig. 5 timeline shows substitution / cross-covariance
    # kernels overlapping the tail of the factorization; the static analogue
    # is the program wavefront's per-wave op mix.  Waves mixing a Cholesky op
    # with a solve/cross op are exactly the cross-stage overlap.
    chol_ops = {sch.POTRF, sch.TRSM, sch.SYRK, sch.GEMM}
    solve_cross_ops = {
        sch.TRSV, sch.GEMV, sch.TRSV_B, sch.GEMV_B,
        sch.CROSS, sch.VINIT, sch.VTRSV, sch.VGEMV, sch.XGEMV,
    }
    q_tiles = max(m_tiles // 4, 1)
    for ns in (4, 16):
        plan = executor.program_plan(m_tiles, q_tiles, sch.Head.COVARIANCE, ns)
        staged = executor.staged_launch_count(
            m_tiles, head=sch.Head.COVARIANCE, n_streams=ns
        )
        mixed = 0
        trace = []
        for wi, lvl in enumerate(plan.levels):
            ops = {}
            for b in lvl:
                for t in b.tasks:
                    ops[t[0]] = ops.get(t[0], 0) + 1
            is_mixed = set(ops) & chol_ops and set(ops) & solve_cross_ops
            if is_mixed:
                mixed += 1
                if len(trace) < 8:
                    mix = ",".join(f"{o}:{c}" for o, c in sorted(ops.items()))
                    trace.append(f"wave{wi}[{mix}]")
        out(row(
            f"fig5/program/tiles{m_tiles}/streams{ns}", 0.0,
            f"waves={len(plan.levels)};batches={plan.n_batches};"
            f"staged_batches={staged};cross_stage_waves={mixed}",
        ))
        for tr in trace:
            out(row(f"fig5/program_trace/tiles{m_tiles}/streams{ns}", 0.0, tr))


if __name__ == "__main__":
    run()
