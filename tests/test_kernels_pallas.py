"""Per-kernel validation: Pallas (interpret mode) vs ref.py oracles,
swept over shapes and dtypes as required for every kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core.kernels_math import SEKernelParams
from repro.kernels import ops, ref
from repro.kernels.cov_assembly import cov_tiles
from repro.kernels.trailing_update import trailing_update
from repro.kernels.trsm_tile import trsm_batched


def _spd(rng, n, dtype=np.float32):
    a = rng.standard_normal((n, n)).astype(dtype)
    return a @ a.T + n * np.eye(n, dtype=dtype)


# ---------------------------------------------------------------------------
# POTRF tile kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [8, 16, 64, 128])
def test_potrf_shapes(rng, m):
    k = _spd(rng, m)
    out = np.asarray(ops.potrf(jnp.asarray(k)))
    want = np.asarray(ref.ref_potrf(jnp.asarray(k)))
    np.testing.assert_allclose(out, want, atol=1e-4 * m)
    assert np.allclose(np.triu(out, 1), 0.0)


def test_potrf_f64(rng):
    with compat.enable_x64():
        k = _spd(rng, 32, np.float64)
        out = np.asarray(ops.potrf(jnp.asarray(k)))
        np.testing.assert_allclose(out, np.linalg.cholesky(k), atol=1e-10)


# ---------------------------------------------------------------------------
# TRSM tile kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [8, 32, 128])
def test_trsm_shapes(rng, m):
    l = np.linalg.cholesky(_spd(rng, m)).astype(np.float32)
    b = rng.standard_normal((m, m)).astype(np.float32)
    out = np.asarray(ops.trsm(jnp.asarray(l), jnp.asarray(b)))
    want = np.asarray(ref.ref_trsm(jnp.asarray(l), jnp.asarray(b)))
    np.testing.assert_allclose(out, want, atol=1e-3)


@pytest.mark.parametrize("batch", [1, 3, 7])
def test_trsm_panel_batched(rng, batch):
    m = 16
    l = np.linalg.cholesky(_spd(rng, m)).astype(np.float32)
    b = rng.standard_normal((batch, m, m)).astype(np.float32)
    out = np.asarray(trsm_batched(jnp.asarray(l), jnp.asarray(b), interpret=True))
    for i in range(batch):
        want = np.asarray(ref.ref_trsm(jnp.asarray(l), jnp.asarray(b[i])))
        np.testing.assert_allclose(out[i], want, atol=1e-3)


# ---------------------------------------------------------------------------
# Trailing-update kernel (batched SYRK/GEMM)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,block", [(16, 16), (64, 32), (128, 128), (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_trailing_update_blocks(rng, m, block, dtype):
    bsz = 3
    c = jnp.asarray(rng.standard_normal((bsz, m, m)), dtype)
    a = jnp.asarray(rng.standard_normal((bsz, m, m)), dtype)
    b = jnp.asarray(rng.standard_normal((bsz, m, m)), dtype)
    out = np.asarray(trailing_update(c, a, b, block=block, interpret=True), np.float32)
    want = np.asarray(ref.ref_trailing_update(c, a, b), np.float32)
    tol = 1e-3 * m if dtype == jnp.float32 else 0.3 * np.sqrt(m)
    np.testing.assert_allclose(out, want, atol=tol)


def test_syrk_uses_same_kernel(rng):
    m = 32
    kii = jnp.asarray(_spd(rng, m))
    lij = jnp.asarray(rng.standard_normal((m, m)).astype(np.float32))
    out = np.asarray(ops.syrk(kii, lij))
    np.testing.assert_allclose(out, np.asarray(kii) - np.asarray(lij) @ np.asarray(lij).T, atol=1e-3)


# ---------------------------------------------------------------------------
# Covariance assembly kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,d", [(8, 1), (16, 4), (32, 16), (128, 8)])
def test_cov_tiles_shapes(rng, m, d):
    t = 4
    xa = rng.standard_normal((t, m, d)).astype(np.float32)
    xb = rng.standard_normal((t, m, d)).astype(np.float32)
    row0 = np.arange(t, dtype=np.int32) * m
    col0 = np.zeros(t, dtype=np.int32)
    out = cov_tiles(
        jnp.asarray(xa), jnp.asarray(xb), jnp.asarray(row0), jnp.asarray(col0),
        lengthscale=1.0, vertical=1.0, noise=0.1,
        n_valid_r=t * m, n_valid_c=t * m, symmetric=True, interpret=True,
    )
    for i in range(t):
        want = ref.ref_cov_tile(
            jnp.asarray(xa[i]), jnp.asarray(xb[i]), int(row0[i]), int(col0[i]),
            lengthscale=1.0, vertical=1.0, noise=0.1,
            n_valid_r=t * m, n_valid_c=t * m, symmetric=True,
        )
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want), atol=1e-5)


def test_cov_tiles_padding_and_diagonal(rng):
    """Padded region -> identity; diagonal carries the noise term."""
    m, d, n_valid = 16, 3, 24   # second tile is half padding
    x = np.zeros((2, m, d), np.float32)
    x[0] = rng.standard_normal((m, d))
    x[1, : n_valid - m] = rng.standard_normal((n_valid - m, d))
    out = np.asarray(cov_tiles(
        jnp.asarray(x), jnp.asarray(x),
        jnp.asarray([0, m], jnp.int32), jnp.asarray([0, m], jnp.int32),
        lengthscale=1.0, vertical=1.0, noise=0.1,
        n_valid_r=n_valid, n_valid_c=n_valid, symmetric=True, interpret=True,
    ))
    # tile 1: rows/cols beyond n_valid are identity
    pad = out[1][n_valid - m :, n_valid - m :]
    np.testing.assert_allclose(pad, np.eye(m - (n_valid - m)), atol=1e-6)
    # diagonal noise: k(x,x) = v + sigma^2
    np.testing.assert_allclose(np.diagonal(out[0]), 1.1, atol=1e-5)


def test_assembled_covariance_matches_jnp_path(rng):
    from repro.core import predict as pred
    from repro.core import tiling

    x = rng.standard_normal((50, 4)).astype(np.float32)
    xc = tiling.pad_features(jnp.asarray(x), 16)
    p = SEKernelParams.paper_defaults()
    a = np.asarray(ops.assemble_packed_covariance(xc, p, 50))
    b = np.asarray(pred.assemble_packed_covariance(xc, p, 50, backend="jnp"))
    np.testing.assert_allclose(a, b, atol=1e-5)
