"""The executor's op-family scopes (DESIGN.md §15).

Every batch of a plan runs inside ``jax.named_scope("repro.exec.<op>")``.
The scope has to reach the compiled program's metadata, where the profiler
trace reads it, and has to change nothing else.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor, tiling
from repro.core.kernels_math import SEKernelParams
from repro.core.scheduler import Head

M_TILES, Q_TILES, TILE, D = 4, 2, 128, 3
PARAMS = SEKernelParams(lengthscale=1.0, vertical=1.0, noise=0.1)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = .*? ([a-z][\w-]*)\(")
_SOLVERS = ("dot", "cholesky", "triangular-solve", "custom-call")
_SCOPE = re.compile(r"repro\.exec\.(\w+)")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _inputs():
    rng = np.random.default_rng(0)
    n, nh = M_TILES * TILE, Q_TILES * TILE
    x = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(nh, D)), jnp.float32)
    return (tiling.pad_features(x, TILE), tiling.pad_vector(y, TILE),
            tiling.pad_features(xt, TILE), n, nh)


def _compiled_text(head=Head.COVARIANCE) -> str:
    xc, yc, xtc, n, nh = _inputs()
    fn = jax.jit(lambda a, b, c: executor.run_program(
        a, b, c, PARAMS, n, nh, head=head))
    return fn.lower(xc, yc, xtc).compile().as_text()


def _instructions(text):
    """(opcode, line) of every HLO instruction in the module text."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            yield m.group(1), line


def _without_metadata(text: str) -> str:
    """The module text with every ``metadata={...}`` and the source tables
    (file names, lines, stack frames) taken out."""
    paragraphs = [p for p in text.split("\n\n")
                  if p.lstrip().split("\n", 1)[0] not in _TABLES]
    return re.sub(r", metadata=\{[^{}]*\}", "", "\n\n".join(paragraphs))


@pytest.fixture(scope="module")
def compiled():
    return _compiled_text()


def test_every_family_of_the_plan_names_its_ops(compiled):
    plan = executor.program_plan(M_TILES, Q_TILES, True, None)
    families = {bt.op for level in plan.levels for bt in level}
    assert {"potrf", "trsm", "trail", "vtrsv", "vgemv", "gram"} <= families
    assert set(_SCOPE.findall(compiled)) == families
    # a matmul or a factor/solve call that carries a source op at all
    # carries its family (the compiler's own rewrites may drop metadata)
    for opcode, line in _instructions(compiled):
        if opcode in _SOLVERS and "op_name=" in line:
            assert "repro.exec." in line, line


def test_scopes_change_metadata_only(compiled, monkeypatch):
    monkeypatch.setattr(executor._SCOPE, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text()
    assert "repro.exec." not in plain
    assert _without_metadata(plain) == _without_metadata(compiled)


def test_variance_head_names_gram_and_prior():
    # the variance head keeps the op names ``gram`` and ``prior``, so the
    # stage map that reads the covariance head's trace reads this one too
    compiled = _compiled_text(Head.VARIANCE)
    plan = executor.program_plan(M_TILES, Q_TILES, Head.VARIANCE, None)
    families = {bt.op for level in plan.levels for bt in level}
    assert families == {bt.op for level in executor.program_plan(
        M_TILES, Q_TILES, Head.COVARIANCE, None).levels for bt in level}
    assert set(_SCOPE.findall(compiled)) == families
    for family in ("gram", "prior"):
        assert any(f"repro.exec.{family}" in line and opcode not in ("parameter", "constant")
                   for opcode, line in _instructions(compiled)), family
