"""Tiled Cholesky vs the monolithic reference, across stream counts,
tile counts, dtypes, backends, and mixed precision."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import cholesky as chol
from repro.core import tiling


def _spd(rng, n, dtype=np.float32):
    a = rng.standard_normal((n, n)).astype(dtype)
    return a @ a.T + n * np.eye(n, dtype=dtype)


@pytest.mark.parametrize("n_streams", [None, 1, 2, 5])
@pytest.mark.parametrize("m", [8, 16, 32])
def test_tiled_matches_monolithic(rng, n_streams, m):
    k = _spd(rng, 64)
    l_t = np.asarray(chol.cholesky_dense_via_tiles(jnp.asarray(k), m, n_streams=n_streams))
    l_m = np.asarray(chol.monolithic_cholesky(jnp.asarray(k)))
    np.testing.assert_allclose(l_t, l_m, atol=1e-3)


def test_reconstruction(rng):
    k = _spd(rng, 96)
    l = np.asarray(chol.cholesky_dense_via_tiles(jnp.asarray(k), 16))
    np.testing.assert_allclose(l @ l.T, k, rtol=2e-2, atol=2e-2)
    assert np.allclose(np.triu(l, 1), 0.0)


def test_single_tile_degenerates_to_monolithic(rng):
    """M=1 is the paper's pure-cuSOLVER case."""
    k = _spd(rng, 32)
    l = np.asarray(chol.cholesky_dense_via_tiles(jnp.asarray(k), 32))
    np.testing.assert_allclose(l, np.linalg.cholesky(k), atol=1e-4)


def test_mixed_precision_update(rng):
    """bf16 trailing updates (paper future work): bounded deviation."""
    k = _spd(rng, 64).astype(np.float32)
    l32 = np.asarray(chol.cholesky_dense_via_tiles(jnp.asarray(k), 16))
    lmp = np.asarray(
        chol.cholesky_dense_via_tiles(jnp.asarray(k), 16, update_dtype=jnp.bfloat16)
    )
    rel = np.abs(lmp - l32).max() / np.abs(l32).max()
    assert rel < 0.02, rel


def test_pallas_backend_matches(rng):
    k = _spd(rng, 64)
    l_p = np.asarray(
        chol.cholesky_dense_via_tiles(jnp.asarray(k), 16, backend="pallas")
    )
    l_m = np.asarray(chol.monolithic_cholesky(jnp.asarray(k)))
    np.testing.assert_allclose(l_p, l_m, atol=1e-3)


def test_float64(rng):
    # f64 path (CPU validation dtype; TPU runs f32/bf16 — DESIGN.md §2)
    k = _spd(rng, 64, np.float64)
    with compat.enable_x64():
        l_t = np.asarray(chol.cholesky_dense_via_tiles(jnp.asarray(k), 16))
        np.testing.assert_allclose(l_t, np.linalg.cholesky(k), atol=1e-10)


def test_jit_compilable(rng):
    k = jnp.asarray(_spd(rng, 64))
    packed = tiling.pack_lower(k, 16)
    fn = jax.jit(chol.tiled_cholesky)
    out = fn(packed)
    ref = chol.tiled_cholesky(packed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
