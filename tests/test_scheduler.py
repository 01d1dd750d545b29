"""Tile-task DAG scheduler: counts, dependencies, critical path, chunking."""

import pytest

from repro.core import scheduler as sch


@pytest.mark.parametrize("m", [1, 2, 3, 5, 8, 16])
def test_task_counts(m):
    s = sch.build_schedule(m)
    assert s.op_counts() == sch.theoretical_task_counts(m)
    assert s.n_tasks == sum(sch.theoretical_task_counts(m).values())


@pytest.mark.parametrize("m", [2, 4, 8, 16])
def test_critical_path(m):
    # right-looking tiled Cholesky ASAP critical path is 3M - 2 levels
    assert sch.build_schedule(m).critical_path == 3 * m - 2


def test_levels_are_antichains():
    """No task may depend on another task in its own level."""
    m = 6
    s = sch.build_schedule(m)
    for level in s.levels:
        level_set = set(level)
        for t in level:
            for d in sch._deps(t, m):
                assert d not in level_set, (t, d)


def test_dependencies_respect_level_order():
    m = 5
    s = sch.build_schedule(m)
    level_of = {t: i for i, lvl in enumerate(s.levels) for t in lvl}
    for t, lv in level_of.items():
        for d in sch._deps(t, m):
            assert level_of[d] < lv


@pytest.mark.parametrize("n_streams", [1, 2, 3, None])
def test_chunking(n_streams):
    tasks = list(range(7))
    chunks = sch.chunk_tasks(tasks, n_streams)
    flat = [t for c in chunks for t in c]
    assert flat == tasks
    if n_streams is not None:
        assert all(len(c) <= n_streams for c in chunks)
    else:
        assert len(chunks) == 1


def test_max_width_grows_with_m():
    w4 = sch.build_schedule(4).max_width()
    w8 = sch.build_schedule(8).max_width()
    assert w8 > w4  # more tiles -> more exposed concurrency (paper Fig. 3)


# ---------------------------------------------------------------------------
# Triangular-solve DAGs.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("m", [1, 2, 5, 9])
def test_solve_schedule_counts_and_critical_path(m, lower):
    s = sch.build_solve_schedule(m, lower=lower)
    assert s.n_tasks == m + m * (m - 1) // 2  # M TRSVs + one GEMV per tile
    assert s.critical_path == 2 * m - 1       # TRSV/GEMV levels alternate
    counts = s.op_counts()
    assert counts[sch.TRSV] == m
    assert counts.get(sch.GEMV, 0) == m * (m - 1) // 2


@pytest.mark.parametrize("lower", [True, False])
def test_solve_dependencies_respect_level_order(lower):
    m = 6
    s = sch.build_solve_schedule(m, lower=lower)
    level_of = {t: i for i, lvl in enumerate(s.levels) for t in lvl}
    assert len(level_of) == s.n_tasks
    for t, lv in level_of.items():
        for d in sch.task_deps(t, s):
            assert level_of[d] < lv, (t, d)


@pytest.mark.parametrize("lower", [True, False])
def test_solve_levels_are_antichains(lower):
    m = 7
    s = sch.build_solve_schedule(m, lower=lower)
    for level in s.levels:
        level_set = set(level)
        for t in level:
            for d in sch.solve_deps(t, m, lower=lower):
                assert d not in level_set, (t, d)


# ---------------------------------------------------------------------------
# Whole-pipeline program DAG (DESIGN.md §7).
# ---------------------------------------------------------------------------


def _program_task_count(m, q, head):
    chol = sum(sch.theoretical_task_counts(m).values())
    solve = m + m * (m - 1) // 2
    count = m * (m + 1) // 2 + q * m + chol + 2 * solve + q  # +cross +xgemv
    if head != sch.Head.NONE:
        count += m + solve + 1  # vinit + matrix solve + gram
    # prior tiles: the whole Q x Q grid, or its diagonal for the variance
    count += {sch.Head.NONE: 0, sch.Head.COVARIANCE: q * q, sch.Head.VARIANCE: q}[head]
    return count


@pytest.mark.parametrize("uncertainty", [False, True, pytest.param(sch.Head.VARIANCE, id="variance")])
@pytest.mark.parametrize("m,q", [(1, 1), (4, 1), (8, 2)])
def test_program_task_counts(m, q, uncertainty):
    tasks = sch.program_tasks(m, q, head=uncertainty)
    assert len(tasks) == len(set(tasks)) == _program_task_count(m, q, uncertainty)
    s = sch.build_program_schedule(m, q, head=uncertainty)
    assert s.n_tasks == len(tasks)


@pytest.mark.parametrize("uncertainty", [False, True, pytest.param(sch.Head.VARIANCE, id="variance")])
def test_program_deps_respect_level_order(uncertainty):
    m, q = 6, 2
    s = sch.build_program_schedule(m, q, head=uncertainty)
    level_of = {t: i for i, lvl in enumerate(s.levels) for t in lvl}
    assert len(level_of) == s.n_tasks
    for t, lv in level_of.items():
        for d in sch.program_deps(t, m, q, uncertainty):
            assert level_of[d] < lv, (t, d)


def test_program_cross_stage_edges():
    """The defining property: solve/cross tasks wait for *tiles*, not stages.
    TRSV(0) depends only on POTRF@col0; CROSS tiles are ready at level 0."""
    m, q = 6, 2
    assert sch.program_deps((sch.TRSV, 0, 0, -1), m, q) == [(sch.POTRF, 0, 0, -1)]
    assert sch.program_deps((sch.CROSS, 0, 3, -1), m, q) == []
    s = sch.build_program_schedule(m, q)
    level_of = {t: i for i, lvl in enumerate(s.levels) for t in lvl}
    # forward substitution of row 0 fires long before the last POTRF
    assert level_of[(sch.TRSV, 0, 0, -1)] < level_of[(sch.POTRF, m - 1, m - 1, -1)]
    assert level_of[(sch.CROSS, 0, 0, -1)] == 0


@pytest.mark.parametrize("uncertainty", [False, True, pytest.param(sch.Head.VARIANCE, id="variance")])
@pytest.mark.parametrize("m", [4, 8])
def test_program_wavefront_mixes_stages(m, uncertainty):
    """Acceptance: for M >= 4 the fused wavefront has at least one wave
    mixing Cholesky tasks with solve/cross tasks (the paper's Fig. 5)."""
    chol_ops = {sch.POTRF, sch.TRSM, sch.SYRK, sch.GEMM}
    solve_cross = {
        sch.TRSV, sch.GEMV, sch.TRSV_B, sch.GEMV_B,
        sch.CROSS, sch.VINIT, sch.VTRSV, sch.VGEMV,
    }
    s = sch.build_wavefront_schedule(
        m, 4, kind="program", q_tiles=2, head=uncertainty
    )
    mixed = [
        lvl for lvl in s.levels
        if {t[0] for t in lvl} & chol_ops and {t[0] for t in lvl} & solve_cross
    ]
    assert mixed, "no wave mixed Cholesky with solve/cross tasks"


@pytest.mark.parametrize("n_streams", [2, 4])
def test_program_waves_are_antichains(n_streams):
    """Wavefront waves must stay antichains under bulk ride-along and
    op-affinity packing."""
    m, q = 5, 2
    s = sch.build_wavefront_schedule(
        m, n_streams, kind="program", q_tiles=q, head=sch.Head.COVARIANCE
    )
    for level in s.levels:
        level_set = set(level)
        for t in level:
            for d in sch.program_deps(t, m, q, sch.Head.COVARIANCE):
                assert d not in level_set, (t, d)


# The variance head: Q diagonal prior tiles and one closing gram, on the same
# matrix solve as the covariance head.
@pytest.mark.parametrize("m,q", [(1, 1), (4, 1), (8, 4)])
def test_variance_head_tasks(m, q):
    var = sch.program_tasks(m, q, head=sch.Head.VARIANCE)
    cov = sch.program_tasks(m, q, head=sch.Head.COVARIANCE)
    priors = [t for t in var if t[0] == sch.PRIOR]
    assert priors == [(sch.PRIOR, p, p, -1) for p in range(q)]
    assert [t for t in var if t[0] == sch.GRAM] == [(sch.GRAM, -1, -1, -1)]
    # everything but the prior grid is the covariance head's, in its order
    assert [t for t in var if t[0] != sch.PRIOR] == [t for t in cov if t[0] != sch.PRIOR]
    assert sch.program_deps((sch.GRAM, -1, -1, -1), m, q, sch.Head.VARIANCE) == [
        (sch.VTRSV, r, r, -1) for r in range(m)
    ] + priors


def test_heads_keep_the_old_flag_meaning():
    """False and True read as the old ``uncertainty`` flag did."""
    assert sch.Head(False) == sch.Head.NONE
    assert sch.Head(True) == sch.Head.COVARIANCE
    assert sch.program_tasks(5, 2, head=True) == sch.program_tasks(5, 2, head=sch.Head.COVARIANCE)
    assert sch.build_nlml_schedule(4).head == sch.Head.NONE


# Task counts of the parent program at M = 8, Q = 4, pinned: the covariance
# head and the mean-only program are unchanged by the variance head.
_PINNED_OP_COUNTS = {
    "assemble": 36, "cross": 32, "gemm": 56, "gemv": 28, "gemv_b": 28, "gram": 1,
    "potrf": 8, "prior": 16, "syrk": 28, "trsm": 28, "trsv": 8, "trsv_b": 8,
    "vgemv": 28, "vinit": 8, "vtrsv": 8, "xgemv": 4,
}


@pytest.mark.parametrize("head,n_tasks", [(sch.Head.NONE, 264), (sch.Head.COVARIANCE, 325),
                                          (sch.Head.VARIANCE, 313)])
def test_program_task_counts_pinned(head, n_tasks):
    tasks = sch.program_tasks(8, 4, head=head)
    assert len(tasks) == n_tasks
    counts = sch.build_program_schedule(8, 4, head=head).op_counts()
    if head == sch.Head.COVARIANCE:
        assert counts == _PINNED_OP_COUNTS
    if head == sch.Head.VARIANCE:
        assert counts == {**_PINNED_OP_COUNTS, "prior": 4}


# ---------------------------------------------------------------------------
# Level-batched executor plans must issue tasks in dependency order.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_streams", [None, 1, 2, 4])
def test_cholesky_plan_order_respects_deps(n_streams):
    from repro.core import executor

    m = 6
    plan = executor.cholesky_plan(m, n_streams)
    pos = {t: i for i, t in enumerate(plan.flat_tasks())}
    assert len(pos) == sch.build_schedule(m).n_tasks
    for t, i in pos.items():
        for d in sch._deps(t, m):
            assert pos[d] < i, (t, d)
    # within a level, batches only ever contain independent tasks, so the
    # stronger property also holds: every dep lives in an *earlier level*
    level_of = {t: li for li, lvl in enumerate(plan.levels) for b in lvl for t in b.tasks}
    for t, li in level_of.items():
        for d in sch._deps(t, m):
            assert level_of[d] < li, (t, d)


@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("n_streams", [None, 2])
def test_solve_plan_order_respects_deps(lower, n_streams):
    from repro.core import executor

    m = 6
    plan = executor.solve_plan(m, lower=lower, n_streams=n_streams)
    pos = {t: i for i, t in enumerate(plan.flat_tasks())}
    for t, i in pos.items():
        for d in sch.solve_deps(t, m, lower=lower):
            assert pos[d] < i, (t, d)


# ---------------------------------------------------------------------------
# The trainable NLML prefix (q_tiles=0 program, DESIGN.md §8).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 4, 6])
def test_nlml_schedule_is_program_prefix(m):
    """q_tiles=0 drops exactly the test-point stages: no CROSS/PRIOR tiles,
    no prediction heads — just assembly, factorization and both solves."""
    s = sch.build_nlml_schedule(m)
    counts = {}
    for lvl in s.levels:
        for t in lvl:
            counts[t[0]] = counts.get(t[0], 0) + 1
    for op in (sch.CROSS, sch.PRIOR, sch.XGEMV, sch.VINIT, sch.VTRSV, sch.VGEMV, sch.GRAM):
        assert op not in counts, op
    solve = m + m * (m - 1) // 2
    assert counts[sch.ASSEMBLE] == m * (m + 1) // 2
    assert counts[sch.TRSV] == counts[sch.TRSV_B] == m
    assert s.n_tasks == (
        m * (m + 1) // 2
        + sum(sch.theoretical_task_counts(m).values())
        + 2 * solve
    )
    # dependency-faithful leveling
    level_of = {t: i for i, lvl in enumerate(s.levels) for t in lvl}
    for t, lv in level_of.items():
        for d in sch.program_deps(t, m, 0):
            assert level_of[d] < lv, (t, d)


@pytest.mark.parametrize("n_streams", [1, 3])
def test_nlml_wavefront_schedule(n_streams):
    """The finite-pool wavefront handles the q_tiles=0 program too."""
    m = 5
    s = sch.build_wavefront_schedule(m, n_streams, kind="program", q_tiles=0)
    assert s.n_tasks == sch.build_nlml_schedule(m).n_tasks
    level_of = {t: i for i, lvl in enumerate(s.levels) for t in lvl}
    for t, lv in level_of.items():
        for d in sch.program_deps(t, m, 0):
            assert level_of[d] < lv, (t, d)
