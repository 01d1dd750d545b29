"""The fused program's variance head (DESIGN.md §7).

``predict_with_uncertainty`` asks for ``Head.VARIANCE``: the program
assembles only the Q diagonal prior tiles and closes with
diag(prior) - colsum(V ∘ V), never the n̂ x n̂ covariance.  Every path that
runs it — single fused, problem-batched (ragged, per-problem params), the
warm tail off a cached factor, the fleet — must equal the diagonal of the
covariance head to f32 tolerance, for every kernel family; the covariance
head itself must trace the program it always did.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import GaussianProcess, GPBatch, GPFleet
from repro.core import executor
from repro.core import kernels_math as km
from repro.core import predict as pred
from repro.core.kernels_math import SEKernelParams
from repro.core.scheduler import Head

PARAMS = SEKernelParams(lengthscale=0.8, vertical=1.2, noise=0.1)
ATOL = 2e-6  # f32: the two heads sum V's squares in different orders


def _data(rng, n=70, nh=21, d=2):
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    xt = rng.standard_normal((nh, d)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(xt)


def _assert_variance_is_diagonal(var_out, cov_out, atol=ATOL):
    (mv, var), (mc, cov) = var_out, cov_out
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mc), rtol=0, atol=atol)
    np.testing.assert_allclose(
        np.asarray(var), np.diagonal(np.asarray(cov), axis1=-2, axis2=-1),
        rtol=0, atol=atol,
    )


@pytest.mark.parametrize("n_streams", [None, 2])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_variance_is_covariance_diagonal(rng, backend, n_streams):
    x, y, xt = _data(rng)  # 70 % 16 and 21 % 16: padding on both sides
    var_out = pred.predict_fused(x, y, xt, PARAMS, 16, head=Head.VARIANCE,
                                 n_streams=n_streams, backend=backend)
    cov_out = pred.predict_fused(x, y, xt, PARAMS, 16, head=Head.COVARIANCE,
                                 n_streams=n_streams, backend=backend)
    assert var_out[1].shape == (21,)
    _assert_variance_is_diagonal(var_out, cov_out)


@pytest.mark.parametrize("per_problem", [False, True])
def test_fused_batched_ragged_variance(rng, per_problem):
    ns, cap, nh = (20, 37, 64), 64, 13
    xs = [rng.standard_normal((n, 2)).astype(np.float32) for n in ns]
    ys = [rng.standard_normal(n).astype(np.float32) for n in ns]
    xst = jnp.stack([jnp.pad(jnp.asarray(v), ((0, cap - len(v)), (0, 0))) for v in xs])
    yst = jnp.stack([jnp.pad(jnp.asarray(v), (0, cap - len(v))) for v in ys])
    nv = jnp.asarray(ns, jnp.int32)
    ntv = jnp.asarray([13, 5, 9], jnp.int32)
    xt = jnp.asarray(rng.standard_normal((3, nh, 2)).astype(np.float32))
    params = PARAMS
    if per_problem:
        params = SEKernelParams(jnp.asarray([0.6, 0.9, 1.3]), jnp.asarray([1.0, 1.5, 0.7]),
                                jnp.asarray([0.05, 0.1, 0.2]))
    kw = dict(n_valid=nv, nt_valid=ntv, with_state=True)
    var_out, state = pred.predict_fused_batched(xst, yst, xt, params, 16,
                                                head=Head.VARIANCE, **kw)
    cov_out, _ = pred.predict_fused_batched(xst, yst, xt, params, 16,
                                            head=Head.COVARIANCE, **kw)
    assert var_out[1].shape == (3, nh)
    _assert_variance_is_diagonal(var_out, cov_out)
    for i, k in enumerate(np.asarray(ntv)):  # rows past a problem's n̂_i: 0
        np.testing.assert_array_equal(np.asarray(var_out[1][i, k:]), 0.0)
    # the warm tail off the ragged stacked state runs the same head
    warm_var = pred.predict_from_state_batched(state, xt, head=Head.VARIANCE, nt_valid=ntv)
    warm_cov = pred.predict_from_state_batched(state, xt, head=Head.COVARIANCE, nt_valid=ntv)
    _assert_variance_is_diagonal(warm_var, warm_cov)
    _assert_variance_is_diagonal(warm_var, cov_out, atol=1e-5)


def test_warm_single_variance(rng):
    x, y, xt = _data(rng)
    state = pred.posterior_state(x, y, PARAMS, 16)
    var_out = pred.predict_from_state(state, xt, head=Head.VARIANCE)
    cov_out = pred.predict_from_state(state, xt, head=Head.COVARIANCE)
    assert var_out[1].shape == (21,)
    _assert_variance_is_diagonal(var_out, cov_out)
    assert pred.predict_from_state(state, xt).shape == (21,)  # Head.NONE: the mean


def test_fleet_variance_is_covariance_diagonal(rng):
    ns = (20, 33, 50, 70)
    xs = [rng.standard_normal((n, 2)).astype(np.float32) for n in ns]
    ys = [rng.standard_normal(n).astype(np.float32) for n in ns]
    fleet = GPFleet(xs, ys, params=PARAMS, tile_size=16)
    xt = rng.standard_normal((11, 2)).astype(np.float32)
    mean, var = fleet.predict_with_uncertainty(xt)
    assert var.shape == (len(ns), 11)
    _assert_variance_is_diagonal((mean, var), fleet.predict_full_cov(xt))
    # each problem alone, as a single GP, through the fused variance head
    for i in range(len(ns)):
        mi, vi = GaussianProcess(xs[i], ys[i], params=PARAMS, tile_size=16) \
            .predict_with_uncertainty(xt)
        np.testing.assert_allclose(np.asarray(var[i]), np.asarray(vi), atol=1e-5)
        np.testing.assert_allclose(np.asarray(mean[i]), np.asarray(mi), atol=1e-4)


def test_gpbatch_variance_cold_and_warm(rng):
    x = rng.standard_normal((3, 40, 2)).astype(np.float32)
    y = rng.standard_normal((3, 40)).astype(np.float32)
    xt = rng.standard_normal((3, 9, 2)).astype(np.float32)
    cold = GPBatch(x, y, params=PARAMS, tile_size=16)
    ref = GPBatch(x, y, params=PARAMS, tile_size=16).predict_full_cov(xt)
    _assert_variance_is_diagonal(cold.predict_with_uncertainty(xt), ref)  # fused
    _assert_variance_is_diagonal(cold.predict_with_uncertainty(xt), ref, atol=1e-5)  # warm


@pytest.mark.parametrize("kern", [km.Matern32(), km.RationalQuadratic(),
                                  km.ARDSquaredExponential(ndim=2),
                                  km.Sum(km.Scaled(km.Matern52()), km.White())],
                         ids=["matern32", "rq", "se_ard2", "sum_m52_white"])
def test_variance_head_across_kernels(rng, kern):
    x, y, xt = _data(rng, n=48, nh=10)
    p = kern.default_params()
    mean, var = GaussianProcess(x, y, params=p, tile_size=16, kernel=kern) \
        .predict_with_uncertainty(xt)
    ref = GaussianProcess(x, y, params=p, tile_size=16, kernel=kern).predict_full_cov(xt)
    _assert_variance_is_diagonal((mean, var), ref)
    mono_mean, mono_var = GaussianProcess(x, y, params=p, tile_size=16, kernel=kern,
                                          pipeline="monolithic").predict_with_uncertainty(xt)
    np.testing.assert_allclose(np.asarray(var), np.asarray(mono_var), atol=5e-3)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mono_mean), atol=5e-4)


def test_variance_program_holds_no_test_by_test_buffer():
    """No value of the variance program has the Q x Q tile grid's shape
    (Q > M, so no batch of the matrix solve shares it)."""
    m_tiles, q_tiles, m, d = 3, 5, 8, 2
    xc = jnp.zeros((m_tiles, m, d))
    yc = jnp.zeros((m_tiles, m))
    xtc = jnp.zeros((q_tiles, m, d))

    def program(head):
        return jax.make_jaxpr(lambda a, b, c: executor.run_program(
            a, b, c, PARAMS, m_tiles * m, q_tiles * m, head=head))(xc, yc, xtc)

    def shapes(jaxpr):
        out = set()
        for eqn in jaxpr.eqns:
            out |= {tuple(v.aval.shape) for v in eqn.outvars}
            for sub in eqn.params.values():  # pjit / scan / cond bodies
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= shapes(sub)
        return out

    grid = {(q_tiles * q_tiles, m, m), (q_tiles, q_tiles, m, m)}
    assert grid & shapes(program(Head.COVARIANCE).jaxpr)
    assert not grid & shapes(program(Head.VARIANCE).jaxpr)
    env = jax.eval_shape(lambda a, b, c: executor.run_program(
        a, b, c, PARAMS, m_tiles * m, q_tiles * m, head=Head.VARIANCE), xc, yc, xtc)
    assert env["prior"].shape == (q_tiles, m)


@pytest.fixture
def metrics():
    obs.disable()
    obs.reset()
    obs.enable()
    yield lambda: obs.snapshot()["counters"]
    obs.disable()
    obs.reset()


def test_head_counters_count_dispatches(rng, metrics):
    x, y, xt = _data(rng, n=40, nh=8)
    gp = GaussianProcess(x, y, params=PARAMS, tile_size=16)
    gp.predict_with_uncertainty(xt)  # cold: the fused program
    gp.predict_with_uncertainty(xt)  # warm: the tail off the cached factor
    gp.predict(xt)                   # no head
    gp.predict_full_cov(xt)          # warm covariance
    c = metrics()
    assert c["predict.head.variance"] == 2.0
    assert c["predict.head.covariance"] == 1.0
    assert c["predict.warm_tail"] == 3.0

    fleet = GPFleet([np.asarray(x)[:20], np.asarray(x)], [np.asarray(y)[:20], np.asarray(y)],
                    params=PARAMS, tile_size=16)
    buckets = len(fleet.bucket_assignment())
    fleet.predict_with_uncertainty(np.asarray(xt))  # one warm dispatch per bucket
    assert metrics()["predict.head.variance"] == 2.0 + buckets
    pred.predict_fused_batched(jnp.stack([x, x]), jnp.stack([y, y]), jnp.stack([xt, xt]),
                               PARAMS, 16, head=Head.COVARIANCE)
    assert metrics()["predict.head.covariance"] == 2.0


def test_head_counters_off_by_default(rng):
    obs.disable()
    obs.reset()
    x, y, xt = _data(rng, n=40, nh=8)
    GaussianProcess(x, y, params=PARAMS, tile_size=16).predict_with_uncertainty(xt)
    assert "predict.head.variance" not in obs.snapshot()["counters"]


def test_predict_full_cov_unchanged():
    """The covariance head's numbers at a tiny size, as the program read
    before the variance head existed (CPU, float32)."""
    rng = np.random.default_rng(1234)
    x = rng.standard_normal((40, 2)).astype(np.float32)
    y = rng.standard_normal(40).astype(np.float32)
    xt = rng.standard_normal((20, 2)).astype(np.float32)
    gp = GaussianProcess(x, y, params=SEKernelParams(1.0, 1.0, 0.1), tile_size=16)
    mean, cov = (np.asarray(a) for a in gp.predict_full_cov(xt))
    assert cov.shape == (20, 20) and cov.dtype == np.float32
    got = [mean.sum(), np.trace(cov), cov.sum(), cov[0, 1], cov[19, 3], *np.diag(cov)[:4]]
    want = [5.212453842163086, 1.0280873775482178, 1.793001413345337,
            -0.002568960189819336, -0.0007287785410881042, 0.06044048070907593,
            0.019445598125457764, 0.047141075134277344, 0.12738585472106934]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
