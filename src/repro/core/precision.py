"""Matmul precision of the pipeline's device programs.

A TPU runs an f32 ``dot_general`` at DEFAULT precision as one bf16 pass,
which keeps about three significant digits; the CPU computes the same dot
in f32 and never shows the difference.  The covariance distance cross
term, the SYRK/GEMM trailing updates, the tiled solves and the prediction
heads all need f32 — the posterior variance ``k** - v.v`` cancels, and a
bf16 trailing update can drive a Cholesky pivot non-positive.

So every program of the pipeline is traced under ``MATMUL_PRECISION``:
:func:`f32_matmuls` wraps each function that is jitted and each host-side
entry point that dispatches matmuls op by op.  Operands a caller chose to
store in bf16 (``update_dtype``) stay bf16: the precision applies to f32
operands only.
"""

from __future__ import annotations

import functools

import jax

MATMUL_PRECISION = "highest"


def f32_matmuls(fn):
    """Run (or trace) ``fn`` with every f32 matmul at ``MATMUL_PRECISION``."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return fn(*args, **kwargs)

    return wrapped
