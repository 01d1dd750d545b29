"""Kernel zoo (DESIGN.md §13): every registered family through the tiled pipeline.

The equivalence grid drives every registered kernel through the fused tiled
program on both op backends and pins predict / uncertainty / NLML against the
monolithic dense reference — the same contract the SE-only pipeline always
had, now a property of the registry.  Gradient cells check the autodiff VJP
(the fallback for kernels without a hand-derived dK/dtheta) against float64
central finite differences, and the composite acceptance test runs the
ARBO-style ``C * Matern52 + White`` model end to end: tiled NLML training,
prediction with uncertainty, and a streaming update — while the executor's
``program_plan`` cache stats prove the Plans are kernel-invariant.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import executor
from repro.core import kernels_math as km
from repro.core import mll
from repro.core import predict as pred
from repro.core.gp import GaussianProcess, GPFleet


# one cell per registered family, plus composite instances that exercise
# Sum / Product / Scaled over nested params pytrees
def _zoo():
    cells = [(name, km.get_kernel(name)) for name in sorted(km.KERNEL_REGISTRY)]
    cells += [
        ("se_ard2", km.ARDSquaredExponential(ndim=2)),
        ("scaled_m52", km.Scaled(km.Matern52())),
        ("sum_m52_white", km.Sum(km.Scaled(km.Matern52()), km.White())),
        ("prod_se_m32", km.Product(km.SquaredExponential(), km.Matern32())),
    ]
    return cells


def _params_for(name, kern):
    p = kern.default_params()
    if name == "se_ard2":
        # distinct per-dim lengthscales so ARD actually differs from SE
        p = km.ARDKernelParams(lengthscales=jnp.asarray([0.7, 1.6]))
    return p


def _data(n, nh=11, d=2, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = np.sin(x.sum(-1)).astype(np.float32) + 0.1 * rng.normal(size=n).astype(
        np.float32
    )
    xt = rng.normal(size=(nh, d)).astype(np.float32)
    return x, y, xt


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize(
    "n,m",
    [(64, 32), pytest.param(200, 64, marks=pytest.mark.slow)],
)
@pytest.mark.parametrize("name,kern", _zoo())
def test_zoo_equivalence_grid(name, kern, n, m, backend):
    """Tiled predict / uncertainty / NLML == monolithic dense, per kernel."""
    x, y, xt = _data(n)
    p = _params_for(name, kern)
    ref_mean, ref_cov = pred.predict_monolithic(
        x, y, xt, p, full_cov=True, kernel=kern
    )
    mean, cov = pred.predict(
        x, y, xt, p, m, full_cov=True, backend=backend, kernel=kern
    )
    np.testing.assert_allclose(mean, ref_mean, rtol=0, atol=5e-4)
    np.testing.assert_allclose(
        jnp.diagonal(cov), jnp.diagonal(ref_cov), rtol=0, atol=5e-3
    )
    ref_nlml = mll.negative_log_marginal_likelihood(x, y, p, kernel=kern)
    tiled = mll.nlml_tiled(x, y, p, tile_size=m, op_backend=backend, kernel=kern)
    # Product has no observation noise (child noise is ignored), so its K is
    # near-singular and tiled-vs-monolithic f32 accumulation orders diverge
    # more; every noised kernel holds the tight tolerance
    rtol = 2e-3 if float(kern.noise(p)) == 0.0 else 3e-4
    np.testing.assert_allclose(tiled, ref_nlml, rtol=rtol, atol=5e-3)


@pytest.mark.parametrize(
    "name,kern",
    [
        ("matern12", km.Matern12()),
        ("matern32", km.Matern32()),
        ("matern52", km.Matern52()),
        ("rq", km.RationalQuadratic()),
        ("se_ard2", km.ARDSquaredExponential(ndim=2)),
        ("sum_m52_white", km.Sum(km.Scaled(km.Matern52()), km.White())),
    ],
)
def test_zoo_autodiff_vjp_matches_finite_differences(name, kern):
    """The autodiff NLML gradient (the non-SE fallback) against f64 FD."""
    with compat.enable_x64():
        x, y, _ = _data(48)
        x64 = jnp.asarray(x, jnp.float64)
        y64 = jnp.asarray(y, jnp.float64)
        p = jax.tree_util.tree_map(
            lambda leaf: jnp.asarray(leaf, jnp.float64), _params_for(name, kern)
        )
        f = lambda pp: mll.nlml_tiled(
            x64, y64, pp, tile_size=16, dtype=jnp.float64, kernel=kern
        )
        grads = jax.grad(f)(p)
        leaves, treedef = jax.tree_util.tree_flatten(p)
        glv = jax.tree_util.tree_leaves(grads)
        eps = 1e-6
        for i, leaf in enumerate(leaves):
            leaf = jnp.asarray(leaf, jnp.float64)
            for idx in np.ndindex(*leaf.shape) if leaf.ndim else [()]:
                bump = jnp.zeros_like(leaf).at[idx].set(eps) if leaf.ndim \
                    else jnp.asarray(eps, jnp.float64)
                up = jax.tree_util.tree_unflatten(
                    treedef, leaves[:i] + [leaf + bump] + leaves[i + 1:]
                )
                dn = jax.tree_util.tree_unflatten(
                    treedef, leaves[:i] + [leaf - bump] + leaves[i + 1:]
                )
                fd = (f(up) - f(dn)) / (2 * eps)
                got = glv[i][idx] if leaf.ndim else glv[i]
                np.testing.assert_allclose(got, fd, rtol=5e-4, atol=5e-6)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fleet_ragged_matern32(backend):
    """GPFleet bucketed ragged cell on Matérn 3/2: predict + ragged update."""
    rng = np.random.default_rng(7)
    sizes = (20, 45, 90)
    xs = [rng.normal(size=(n, 2)).astype(np.float32) for n in sizes]
    ys = [rng.normal(size=(n,)).astype(np.float32) for n in sizes]
    xt = rng.normal(size=(6, 2)).astype(np.float32)
    fleet = GPFleet(xs, ys, tile_size=32, op_backend=backend, kernel="matern32")
    mean = fleet.predict(xt)
    for i in range(3):
        ref = pred.predict_monolithic(xs[i], ys[i], xt, fleet.params, kernel="matern32")
        np.testing.assert_allclose(mean[i], ref, rtol=0, atol=5e-4)
    counts = (4, 3, 2)
    xa = [rng.normal(size=(c, 2)).astype(np.float32) for c in counts]
    ya = [rng.normal(size=(c,)).astype(np.float32) for c in counts]
    fleet.update(xa, ya)
    mean2 = fleet.predict(xt)
    for i in range(3):
        ref = pred.predict_monolithic(
            fleet._xs[i], fleet._ys[i], xt, fleet.params, kernel="matern32"
        )
        np.testing.assert_allclose(mean2[i], ref, rtol=0, atol=5e-4)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_composite_workload_acceptance(backend):
    """ARBO-style ``C * Matern52 + White``: train, predict, stream updates.

    Also pins the Plan-reuse contract: running a *different* kernel family
    through the same tile geometry must add zero ``program_plan`` cache
    misses (Plans are kernel-invariant; only jit entries are per-kernel).
    """
    kern = km.Sum(km.Scaled(km.Matern52()), km.White())
    rng = np.random.default_rng(11)
    x = rng.normal(size=(70, 2)).astype(np.float32)
    y = np.sin(x.sum(-1)).astype(np.float32)
    xt = rng.normal(size=(9, 2)).astype(np.float32)
    m = 32

    # train through the tiled NLML (autodiff fallback — no analytic VJP)
    p0 = kern.default_params()
    p, losses = mll.optimize_hyperparameters(
        x, y, p0, steps=5, lr=0.05, method="tiled", tile_size=m,
        op_backend=backend, kernel=kern,
    )
    assert np.isfinite(np.asarray(losses)).all()
    assert losses[-1] <= losses[0]

    gp = GaussianProcess(
        x, y, params=p, tile_size=m, op_backend=backend, kernel=kern
    )
    mean, var = gp.predict_with_uncertainty(xt)
    ref_mean, ref_cov = pred.predict_monolithic(
        x, y, xt, p, full_cov=True, kernel=kern
    )
    np.testing.assert_allclose(mean, ref_mean, rtol=0, atol=5e-4)
    np.testing.assert_allclose(var, jnp.diagonal(ref_cov), rtol=0, atol=5e-3)

    # plan reuse: a different family through the same geometry — no new plans
    before = executor.program_plan.cache_info()
    gp_se = GaussianProcess(x, y, tile_size=m, op_backend=backend, kernel="se")
    gp_se.predict_with_uncertainty(xt)
    after = executor.program_plan.cache_info()
    assert after.misses == before.misses, "Plans must stay kernel-invariant"

    # streaming update: absorb observations, match the grown dense reference
    xn = rng.normal(size=(12, 2)).astype(np.float32)
    yn = np.sin(xn.sum(-1)).astype(np.float32)
    gp.update(xn, yn)
    mean2 = gp.predict(xt)
    ref2 = pred.predict_monolithic(
        np.vstack([x, xn]), np.concatenate([y, yn]), xt, p, kernel=kern
    )
    np.testing.assert_allclose(mean2, ref2, rtol=0, atol=5e-4)


def test_kernel_registry_contract():
    """Registry lookups, hashability, ids, and resolve_kernel round-trips."""
    assert isinstance(km.resolve_kernel(None), km.SquaredExponential)
    assert km.resolve_kernel("matern32") == km.get_kernel("matern32")
    k = km.Sum(km.Scaled(km.Matern52()), km.White())
    assert km.resolve_kernel(k) is k
    assert hash(k) == hash(km.Sum(km.Scaled(km.Matern52()), km.White()))
    assert k.kernel_id() == "sum(scaled(matern52),white)"
    with pytest.raises(KeyError):
        km.get_kernel("not-a-kernel")
    # params utilities are tree_maps: ARD leaves keep their base axis
    ard = km.ARDSquaredExponential(ndim=3)
    p = km.ARDKernelParams(lengthscales=jnp.asarray([1.0, 2.0, 3.0]))
    bp = km.broadcast_params(p, 4, ard)
    assert bp.lengthscales.shape == (4, 3)
    assert bp.noise.shape == (4,)
    gp = km.gather_params(bp, jnp.asarray([2, 0]), ard)
    assert gp.lengthscales.shape == (2, 3)
    np.testing.assert_allclose(gp.lengthscales[1], p.lengthscales)
