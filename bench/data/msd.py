"""Mass-spring-damper NFIR data, fast enough for a benchmark's set-up.

The same chain, constants, forcing, RK4 integrator and NFIR features as
``repro.data.msd`` (the paper's system-identification workload), written
so that 16k rows take a second or two instead of a minute: the forcing
and the observation noise are drawn with one vectorised call each (numpy's
``Generator`` yields the same stream drawn at once as drawn one by one),
and the integration is a float64 ``lax.scan`` on the host's CPU device.

The benchmark keeps this copy so that its data cannot change with the
program under test.
"""

from __future__ import annotations

import functools

import numpy as np

# repro.data.msd.MSDConfig defaults: the paper's simulator settings.
N_MASSES = 3
MASS = 1.0
SPRING = 5.0
SPRING_CUBIC = 1.0
DAMPER = 1.5
DT = 0.5
SUBSTEPS = 20
NOISE_STD = 0.05
FORCE_SCALE = 4.0
FORCE_CUTOFF = 0.25


def _accel(jnp, pos, vel, u):
    """m q'' = spring + damper forces, each spring also pulling its upper mass."""
    ext = jnp.concatenate([pos[:1], pos[1:] - pos[:-1]])
    vel_ext = jnp.concatenate([vel[:1], vel[1:] - vel[:-1]])
    f_spring = -(SPRING * ext + SPRING_CUBIC * ext**3)
    f_damp = -DAMPER * vel_ext
    up = jnp.concatenate([f_spring[1:] + f_damp[1:], jnp.zeros(1, pos.dtype)])
    f = f_spring + f_damp - up
    return (f.at[0].add(u)) / MASS


@functools.lru_cache(maxsize=None)
def _rollout_fn():
    import jax
    import jax.numpy as jnp

    h = DT / SUBSTEPS

    def substep(carry, u):
        pos, vel = carry
        k1v = _accel(jnp, pos, vel, u)
        k1x = vel
        k2v = _accel(jnp, pos + 0.5 * h * k1x, vel + 0.5 * h * k1v, u)
        k2x = vel + 0.5 * h * k1v
        k3v = _accel(jnp, pos + 0.5 * h * k2x, vel + 0.5 * h * k2v, u)
        k3x = vel + 0.5 * h * k2v
        k4v = _accel(jnp, pos + h * k3x, vel + h * k3v, u)
        k4x = vel + h * k3v
        pos = pos + (h / 6.0) * (k1x + 2 * k2x + 2 * k3x + k4x)
        vel = vel + (h / 6.0) * (k1v + 2 * k2v + 2 * k3v + k4v)
        return (pos, vel)

    def step(carry, u):
        carry = jax.lax.fori_loop(0, SUBSTEPS, lambda _, c: substep(c, u), carry)
        return carry, carry[0][-1]

    def rollout(u_seq):
        z = jnp.zeros(N_MASSES, u_seq.dtype)
        _, y = jax.lax.scan(step, (z, z), u_seq)
        return y

    return jax.jit(rollout)


def simulate(n_steps: int, seed: int):
    """Force ``u`` and noisy last-mass position ``y``, both (n_steps,) float64."""
    import jax

    rng = np.random.default_rng(seed)
    w = rng.normal(0.0, FORCE_SCALE, size=n_steps)
    u = np.empty(n_steps)
    acc = 0.0
    for t in range(n_steps):  # smoothed random walk; cheap, kept sequential
        acc = (1 - FORCE_CUTOFF) * acc + FORCE_CUTOFF * w[t]
        u[t] = acc
    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        y = np.asarray(_rollout_fn()(jax.device_put(u, cpu)))
    return u, y + rng.normal(0.0, NOISE_STD, size=n_steps)


def _nfir(u, y, d):
    n = len(u) - d + 1
    idx = np.arange(n)[:, None] + np.arange(d)[None, :]
    return np.ascontiguousarray(u[idx][:, ::-1]), y[d - 1:].copy()


def make_dataset(n_train: int, n_test: int, n_regressors: int, seed: int):
    """z-scored NFIR (x_train, y_train, x_test, y_test) as float32.

    Two independent rollouts (seeds ``seed`` and ``seed + 1``); inputs are
    scaled so that D z-scored lags give squared distances of order one.
    """
    d = n_regressors
    u_tr, y_tr = simulate(n_train + d - 1, seed)
    u_te, y_te = simulate(n_test + d - 1, seed + 1)
    u_mu, u_sd = u_tr.mean(), u_tr.std() + 1e-12
    y_mu, y_sd = y_tr.mean(), y_tr.std() + 1e-12
    f_sd = u_sd * np.sqrt(2.0 * d)
    u_tr, u_te = (u_tr - u_mu) / f_sd, (u_te - u_mu) / f_sd
    y_tr, y_te = (y_tr - y_mu) / y_sd, (y_te - y_mu) / y_sd
    x_train, yy_train = _nfir(u_tr, y_tr, d)
    x_test, yy_test = _nfir(u_te, y_te, d)
    return tuple(a.astype(np.float32) for a in (x_train, yy_train, x_test, yy_test))
