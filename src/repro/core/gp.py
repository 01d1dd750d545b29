"""User-facing Gaussian-process API (GPRat-style).

Mirrors the GPRat Python API surface: construct with data + hyperparameters,
then ``predict`` / ``predict_with_uncertainty`` / ``predict_full_cov``.
Backend selection:

* ``pipeline="tiled"``      — the paper's tiled pipeline (default)
* ``pipeline="monolithic"`` — the cuSOLVER-reference analogue

* ``op_backend="jnp"``      — XLA ops per tile task
* ``op_backend="pallas"``   — explicit Pallas VMEM kernels per tile task

* ``fused=True`` (default)  — cold predictions run the whole pipeline as ONE
  multi-stage program with cross-stage wavefronts (DESIGN.md §7)
* ``fused=False``           — staged per-stage baseline

The tiled pipeline caches its :class:`repro.core.predict.PosteriorState`
(packed Cholesky factor + alpha — with ``fused`` it is a slice of the fused
program's buffer environment) across ``predict`` calls; the cache is
invalidated automatically when hyperparameters change (see ``posterior``).
Warm predictions at new test points reuse the cached factor through the
staged cross-covariance/mean stages, skipping the O(n^3) work entirely.

:class:`GPBatch` is the fleet front-end (DESIGN.md §9): B independent GPs
with stacked ``(B, n, D)`` inputs and per-problem hyperparameters, executed
as ONE problem-batched fused program — same validation / posterior-cache /
invalidation contract as :class:`GaussianProcess`, same executor Plan as a
single GP, every launch B times wider.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import kernels_math as km
from repro.core import lowrank
from repro.core import predict as pred
from repro.core import tiling
from repro.core.scheduler import Head

# Host spans of the predict calls (DESIGN.md §15): one ``repro.gp.predict``
# per call, around the cache lookup, the program's pad/launch/untile spans
# (``repro.predict.*``) and, closing ``predict_with_uncertainty``, ``diag``:
# the variance handed back (the variance head computes it directly, so the
# span holds no work on the tiled path).
_tracer = obs.Tracer("repro.gp")


def _variance_from_full(out, head: Head):
    """The low-rank tier and the monolithic pipeline compute only the full
    covariance: for the variance head, keep its diagonal."""
    if head != Head.VARIANCE:
        return out
    mean, sigma = out
    return mean, jnp.diagonal(sigma, axis1=-2, axis2=-1)

def _lowrank_state_with_retry(build, base_jitter: float) -> lowrank.LowRankState:
    """Cold Nyström build with escalating-jitter retries (DESIGN.md §15).

    ``chol(K_uu + jitter I)`` can fail when the inducing set has duplicate
    or near-duplicate rows and the jitter is too small — the whitened
    factors come back NaN and every downstream predict/NLML is poisoned.
    Retry the build with the jitter escalated tenfold (at most twice).  The
    finiteness probe reads only the two packed m×m inner factors — O(m²)
    and once per cold build, never on the per-predict path — and each
    incident is recorded as a ``health.lowrank_jitter_retry`` event.
    """
    jit = float(base_jitter)
    state = build(jit)
    for _ in range(2):
        if bool(
            jnp.all(jnp.isfinite(state.luu_packed))
            & jnp.all(jnp.isfinite(state.lb_packed))
        ):
            return state
        jit = max(jit, lowrank.DEFAULT_JITTER) * 10.0
        obs.health_event("lowrank_jitter_retry", jitter=jit)
        state = build(jit)
    return state


def _params_key(params):
    """Hashable digest of a kernel-params pytree for posterior cache keys.

    Works for any registered kernel (ARD vectors, nested composite trees):
    every leaf's host bytes, in tree order.  Leaves must be concrete here —
    the front-ends only ever hold concrete hyperparameters.
    """
    return tuple(
        np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(params)
    )


def _validate_fleet_params(params, kernel, b: int, cls: str) -> None:
    """Every hyperparameter leaf: base shape (shared) or (B,)+base (per-problem)."""
    base = kernel.base_ndims(params)
    for (path, leaf), nd in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree_util.tree_leaves(base),
    ):
        if jnp.ndim(leaf) > nd and jnp.shape(leaf)[0] != b:
            name = jax.tree_util.keystr(path)
            raise ValueError(
                f"{cls} params{name} must be shared (rank {nd}) or "
                f"per-problem with leading axis ({b},); got shape "
                f"{jnp.shape(leaf)}"
            )


@dataclasses.dataclass
class GaussianProcess:
    x_train: jax.Array
    y_train: jax.Array
    params: Optional[object] = None  # None -> kernel.default_params()
    tile_size: int = 256
    n_streams: Optional[int] = None
    pipeline: str = "tiled"
    op_backend: str = "jnp"
    update_dtype: Optional[object] = None
    dtype: object = jnp.float32
    fused: bool = True
    sliding_window: Optional[int] = None  # keep at most n_max observations
    # covariance family: None/registry name/Kernel instance (DESIGN.md §13).
    # The kernel id joins the posterior cache key and every jit cache key;
    # executor Plans stay kernel-invariant so switching families reuses them.
    kernel: Optional[object] = None
    # approximation tier (DESIGN.md §14): "exact" (default) factorizes the
    # full n×n covariance; "lowrank" runs the tiled Nyström/DTC tier —
    # O(n m²) build on an m_inducing-point inner system, O(m²) per test
    # point, streaming updates through the rank-m system (never O(n³)).
    # method="lowrank" takes precedence over ``pipeline``/``fused``.
    method: str = "exact"
    m_inducing: Optional[int] = None
    strategy: str = "subset"  # inducing selection: "subset" | "kmeans-lite"
    inducing: Optional[object] = None  # explicit inducing inputs (m_inducing, D)
    jitter: Optional[float] = None  # K_uu regularizer; None -> lowrank.DEFAULT_JITTER

    def __post_init__(self):
        self.kernel = km.resolve_kernel(self.kernel)
        if self.params is None:
            self.params = self.kernel.default_params()
        if self.method not in ("exact", "lowrank"):
            raise ValueError(
                f"method must be 'exact' or 'lowrank', got {self.method!r}"
            )
        if self.method == "lowrank" and self.m_inducing is None:
            raise ValueError("method='lowrank' requires m_inducing")
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(f"sliding_window must be >= 1, got {self.sliding_window}")
        x = jnp.asarray(self.x_train, self.dtype)
        if x.ndim == 1:  # (n,) convenience for 1-D problems
            x = x[:, None]
        self.y_train = jnp.asarray(self.y_train, self.dtype).reshape(-1)
        n = self.y_train.shape[0]
        if x.ndim != 2 or x.shape[0] != n:
            raise ValueError(
                f"x_train must be (n, D) or (n,) with n == len(y_train) == {n}; "
                f"got shape {tuple(x.shape)}. Pass x_train.T explicitly if your "
                "features are stored (D, n) — it is not transposed silently."
            )
        self.x_train = x
        self._posterior: Optional[pred.PosteriorState] = None
        self._posterior_key = None
        self._lowrank: Optional[lowrank.LowRankState] = None
        self._lowrank_key = None

    # -- cached posterior ---------------------------------------------------

    def _cache_key(self):
        # jax arrays are immutable, so object identity of the training data
        # is a sound staleness signal (rebinding x_train/y_train invalidates)
        return (
            id(self.x_train),
            id(self.y_train),
            self.kernel,
            _params_key(self.params),
            self.tile_size,
            self.n_streams,
            self.op_backend,
            str(self.update_dtype),
            str(jnp.dtype(self.dtype)),
            self.method,
            self.m_inducing,
            self.strategy,
            None if self.jitter is None else float(self.jitter),
            None if self.inducing is None else id(self.inducing),
        )

    def posterior(self) -> pred.PosteriorState:
        """The packed Cholesky factor + alpha, cached across ``predict`` calls.

        Recomputed only when hyperparameters or pipeline knobs change (e.g.
        after :meth:`optimize`); repeated predictions at new test points skip
        the O(n^3) assemble/factor/solve stage entirely.
        """
        key = self._cache_key()
        if self._posterior is None or self._posterior_key != key:
            obs.inc("cache.posterior.cold")
            self._posterior = pred.posterior_state(
                self.x_train,
                self.y_train,
                self.params,
                self.tile_size,
                n_streams=self.n_streams,
                backend=self.op_backend,
                update_dtype=self.update_dtype,
                dtype=self.dtype,
                kernel=self.kernel,
            )
            self._posterior_key = key
        else:
            obs.inc("cache.posterior.warm")
        return self._posterior

    def _effective_jitter(self) -> float:
        return lowrank.DEFAULT_JITTER if self.jitter is None else float(self.jitter)

    def lowrank_posterior(self) -> lowrank.LowRankState:
        """The cached Nyström state (method="lowrank"): inducing chunks, the
        whitened m×m inner factors, and the projected weights — rebuilt only
        when data/hyperparameters/knobs change, exactly like :meth:`posterior`.
        """
        key = self._cache_key()
        if self._lowrank is None or self._lowrank_key != key:
            obs.inc("cache.lowrank.cold")
            self._lowrank = _lowrank_state_with_retry(
                lambda jit: lowrank.lowrank_state(
                    self.x_train,
                    self.y_train,
                    self.params,
                    self.m_inducing,
                    self.tile_size,
                    strategy=self.strategy,
                    inducing=self.inducing,
                    jitter=jit,
                    n_streams=self.n_streams,
                    backend=self.op_backend,
                    update_dtype=self.update_dtype,
                    dtype=self.dtype,
                    kernel=self.kernel,
                ),
                self._effective_jitter(),
            )
            self._lowrank_key = key
        else:
            obs.inc("cache.lowrank.warm")
        return self._lowrank

    def invalidate_cache(self) -> None:
        self._posterior = None
        self._posterior_key = None
        self._lowrank = None
        self._lowrank_key = None

    # -- streaming updates (DESIGN.md §10) ----------------------------------

    def _cache_warm(self) -> bool:
        return self._posterior is not None and self._posterior_key == self._cache_key()

    def _lowrank_warm(self) -> bool:
        return self._lowrank is not None and self._lowrank_key == self._cache_key()

    def update(self, x_new: jax.Array, y_new: jax.Array) -> "GaussianProcess":
        """Absorb new observations online in O(n^2 b) — no re-factorization.

        Appends ``(x_new, y_new)`` to the training set; when the posterior
        cache is warm the cached factor/weights are *extended* in place via
        the tiled block Cholesky append (``PosteriorState.extend``), so the
        next ``predict`` skips straight to the warm tail.  A cold cache (or
        a numerically failed append — NaN heads) falls back to the
        established contract: the cache is invalidated and the next
        prediction re-factorizes.  With ``sliding_window=n_max``, the oldest
        observations are evicted (:meth:`forget`) once n exceeds n_max — in
        whole-tile chunks, so eviction stays on the O(n^2) fast path.
        """
        from repro.core import update as upd

        x_new = self._prep(x_new)
        y_new = jnp.asarray(y_new, self.dtype).reshape(-1)
        if x_new.shape[0] != y_new.shape[0]:
            raise ValueError(
                f"update needs matching x_new (b, D) and y_new (b,); got "
                f"{tuple(x_new.shape)} and {tuple(y_new.shape)}"
            )
        if x_new.shape[0] == 0:
            return self
        if self.method == "lowrank":
            # absorb through the rank-m inner system: O(b m² + m³), no O(n³)
            warm = self._lowrank_warm()
            state = self._lowrank
            self.x_train = jnp.concatenate([self.x_train, x_new], axis=0)
            self.y_train = jnp.concatenate([self.y_train, y_new], axis=0)
            if warm:
                try:
                    self._lowrank = lowrank.absorb(
                        state,
                        x_new,
                        y_new,
                        sign=1,
                        n_streams=self.n_streams,
                        backend=self.op_backend,
                        update_dtype=self.update_dtype,
                    )
                    self._lowrank_key = self._cache_key()
                except upd.CholeskyUpdateError:
                    obs.health_event("refactorize_fallback", site="gp.update.lowrank")
                    self.invalidate_cache()
            else:
                self.invalidate_cache()
            if self.sliding_window is not None:
                excess = self.y_train.shape[0] - self.sliding_window
                if excess > 0:
                    # no tile alignment needed: eviction is a rank-m downdate
                    self.forget(min(excess, self.y_train.shape[0] - 1))
            return self
        warm = self.pipeline == "tiled" and self._cache_warm()
        state = self._posterior
        self.x_train = jnp.concatenate([self.x_train, x_new], axis=0)
        self.y_train = jnp.concatenate([self.y_train, y_new], axis=0)
        if warm and x_new.shape[0] > 0:
            try:
                self._posterior = state.extend(
                    x_new,
                    y_new,
                    n_streams=self.n_streams,
                    backend=self.op_backend,
                    update_dtype=self.update_dtype,
                )
                self._posterior_key = self._cache_key()
            except upd.CholeskyUpdateError:
                obs.health_event("refactorize_fallback", site="gp.update")
                self.invalidate_cache()  # next predict refactorizes
        else:
            self.invalidate_cache()
        if self.sliding_window is not None:
            excess = self.y_train.shape[0] - self.sliding_window
            if excess > 0:
                # evict in whole-tile chunks so the O(n^2) downdate fast
                # path applies: round the overflow up to a tile multiple
                # (n stays <= n_max; slightly more than the overflow may
                # go).  A window smaller than one tile evicts exactly.
                m = self.tile_size
                self.forget(min(-(-excess // m) * m, self.y_train.shape[0] - 1))
        return self

    def forget(self, k: int) -> "GaussianProcess":
        """Evict the k oldest observations (sliding-window downdate).

        Tile-aligned k on a warm cache runs the O(n^2 k) rank-update sweep
        (``PosteriorState.shrink``); anything else (unaligned k, cold
        cache, numerical failure) invalidates the cache so the next
        prediction re-factorizes the kept window.
        """
        from repro.core import update as upd

        n = self.y_train.shape[0]
        if not 0 <= k < n:
            raise ValueError(f"forget(k) needs 0 <= k < n = {n}; got {k}")
        if k == 0:
            return self
        if self.method == "lowrank":
            # rank-m downdate of the inner system (absorb with sign=-1);
            # works for any k — no tile alignment requirement
            warm = self._lowrank_warm()
            state = self._lowrank
            x_old, y_old = self.x_train[:k], self.y_train[:k]
            self.x_train = self.x_train[k:]
            self.y_train = self.y_train[k:]
            if warm:
                try:
                    self._lowrank = lowrank.absorb(
                        state,
                        x_old,
                        y_old,
                        sign=-1,
                        n_streams=self.n_streams,
                        backend=self.op_backend,
                        update_dtype=self.update_dtype,
                    )
                    self._lowrank_key = self._cache_key()
                except upd.CholeskyUpdateError:
                    obs.health_event("refactorize_fallback", site="gp.forget.lowrank")
                    self.invalidate_cache()
            else:
                self.invalidate_cache()
            return self
        warm = self.pipeline == "tiled" and self._cache_warm()
        state = self._posterior
        self.x_train = self.x_train[k:]
        self.y_train = self.y_train[k:]
        # whole leading tiles on a warm cache; k < n already leaves >= 1 row
        if warm and k % self.tile_size == 0:
            try:
                self._posterior = state.shrink(
                    k, n_streams=self.n_streams, backend=self.op_backend
                )
                self._posterior_key = self._cache_key()
            except upd.CholeskyUpdateError:
                obs.health_event("refactorize_fallback", site="gp.forget")
                self.invalidate_cache()
        else:
            self.invalidate_cache()
        return self

    # -- prediction ---------------------------------------------------------

    def _predict_tiled(self, x_test: jax.Array, head: Head):
        """Route a tiled prediction: cached factor -> staged tail stages;
        cold + ``fused`` -> one whole-pipeline program whose buffer env also
        populates the posterior cache; cold staged -> posterior() then tail."""
        with _tracer.span("lookup"):
            key = self._cache_key()
            warm = self._posterior is not None and self._posterior_key == key
        if warm:
            obs.inc("cache.posterior.warm")
            state = self._posterior
        elif self.fused:
            obs.inc("cache.posterior.cold")
            result, state = pred.predict_fused(
                self.x_train,
                self.y_train,
                x_test,
                self.params,
                self.tile_size,
                head=head,
                n_streams=self.n_streams,
                backend=self.op_backend,
                update_dtype=self.update_dtype,
                dtype=self.dtype,
                with_state=True,
                kernel=self.kernel,
            )
            self._posterior, self._posterior_key = state, key
            return result
        else:
            state = self.posterior()
        return pred.predict_from_state(
            state,
            x_test,
            head=head,
            n_streams=self.n_streams,
            backend=self.op_backend,
            dtype=self.dtype,
        )

    def _predict_lowrank(self, x_test: jax.Array, full_cov: bool):
        return lowrank.predict_from_lowrank_state(
            self.lowrank_posterior(),
            x_test,
            full_cov=full_cov,
            n_streams=self.n_streams,
            backend=self.op_backend,
            dtype=self.dtype,
        )

    def _predict(self, x_test: jax.Array, head: Head):
        x_test = self._prep(x_test)
        full_cov = head != Head.NONE
        if self.method == "lowrank":
            return _variance_from_full(self._predict_lowrank(x_test, full_cov), head)
        if self.pipeline == "monolithic":
            out = pred.predict_monolithic(
                self.x_train, self.y_train, x_test, self.params,
                full_cov=full_cov, dtype=self.dtype, kernel=self.kernel,
            )
            return _variance_from_full(out, head)
        return self._predict_tiled(x_test, head)

    def predict(self, x_test: jax.Array) -> jax.Array:
        with _tracer.span("predict"):
            return self._predict(x_test, Head.NONE)

    def predict_full_cov(self, x_test: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """The paper's *Predict with Full Covariance Matrix* operation."""
        with _tracer.span("predict"):
            return self._predict(x_test, Head.COVARIANCE)

    def predict_with_uncertainty(self, x_test: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Means and posterior variances (n̂,): the variance head, which
        never forms the n̂ x n̂ covariance on the tiled path."""
        with _tracer.span("predict"):
            mean, var = self._predict(x_test, Head.VARIANCE)
            with _tracer.span("diag"):
                return mean, var

    # -- hyperparameters ----------------------------------------------------

    def nlml(self) -> jax.Array:
        """Negative log marginal likelihood from the *cached* tiled posterior.

        Reuses (or populates) the posterior cache: the quadratic term is
        ``y^T alpha`` over the cached weight chunks and the log-determinant
        comes from the packed factor's diagonal tiles — no monolithic
        re-factorization (mll.nlml_from_state).  Identity padding makes both
        terms exact for any n.
        """
        from repro.core import mll

        if self.method == "lowrank":
            return lowrank.nlml_from_lowrank_state(
                self.lowrank_posterior(), dtype=self.dtype
            )
        if self.pipeline == "monolithic":
            return mll.negative_log_marginal_likelihood(
                self.x_train, self.y_train, self.params,
                dtype=self.dtype, kernel=self.kernel,
            )
        return mll.nlml_from_state(self.posterior(), self.y_train, dtype=self.dtype)

    def log_marginal_likelihood(self) -> jax.Array:
        """``-nlml()`` — for ``pipeline="tiled"`` this reuses the cached tiled
        posterior (no monolithic Cholesky), consistent with :meth:`nlml`;
        previously it always ran the monolithic path regardless of pipeline."""
        return -self.nlml()

    def optimize(
        self, steps: int = 100, lr: float = 0.05, *, method: Optional[str] = None
    ) -> "GaussianProcess":
        """Fit hyperparameters by Adam on the negative log marginal likelihood.

        The optimizer is one jitted ``lax.scan`` (mll.adam_scan).  ``method``
        defaults to the GP's pipeline: ``pipeline="tiled"`` trains through
        the differentiable tiled program (``mll.nlml_tiled`` — zero
        monolithic Cholesky calls, same tile_size/n_streams/op_backend/
        update_dtype knobs as prediction); ``pipeline="monolithic"``
        differentiates the dense reference NLML.
        """
        from repro.core import mll

        if method is None:
            if self.method == "lowrank":
                method = "lowrank"
            else:
                method = "tiled" if self.pipeline == "tiled" else "monolithic"
        new_params, _ = mll.optimize_hyperparameters(
            self.x_train,
            self.y_train,
            self.params,
            steps=steps,
            lr=lr,
            dtype=self.dtype,
            method=method,
            tile_size=self.tile_size,
            n_streams=self.n_streams,
            op_backend=self.op_backend,
            update_dtype=self.update_dtype,
            kernel=self.kernel,
            m_inducing=self.m_inducing,
            strategy=self.strategy,
            inducing=self.inducing,
            jitter=self.jitter,
        )
        self.params = new_params
        self.invalidate_cache()  # the factor belongs to the old hyperparameters
        return self

    def _prep(self, x_test: jax.Array) -> jax.Array:
        x_test = jnp.asarray(x_test, self.dtype)
        if x_test.ndim == 1:
            x_test = x_test[:, None]
        return x_test


@dataclasses.dataclass
class GPBatch:
    """B independent GPs executed as ONE problem-batched fused program.

    Stacked inputs: ``x_train`` (B, n, D) (or (B, n) for 1-D problems),
    ``y_train`` (B, n) — ragged-free, every problem shares n and D so the
    whole fleet shares one executor Plan (the DAG depends only on the tile
    geometry, never on B; see DESIGN.md §9).  ``params`` leaves may be
    scalars (shared across the fleet — keeps the Pallas assembly kernels
    usable, with B folded into their grid) or vectors (B,) (per-problem —
    assembly routes through the vmapped jnp tile kernel).  Scalars are kept
    as scalars; :meth:`optimize` always returns per-problem (B,) leaves.

    Same contract as :class:`GaussianProcess`: shape validation raises
    instead of silently transposing, the stacked
    :class:`repro.core.predict.PosteriorState` is cached across ``predict``
    calls and invalidated when hyperparameters or pipeline knobs change,
    and :meth:`optimize` trains all B GPs' hyperparameters in one jitted
    Adam scan with independent optimizer states.
    """

    x_train: jax.Array
    y_train: jax.Array
    params: Optional[object] = None  # None -> kernel.default_params()
    tile_size: int = 256
    n_streams: Optional[int] = None
    op_backend: str = "jnp"
    update_dtype: Optional[object] = None
    dtype: object = jnp.float32
    batch_dispatch: str = "flat"
    # optional jax.sharding.Mesh: shard the problem axis B over its DP axes
    # (pure data parallelism — problems are independent, so every launch
    # partitions along B with zero collectives; DESIGN.md §12).  The mesh
    # changes layout only: results, Plans, and trace counts are identical
    # to the single-device path.
    mesh: Optional[object] = None
    kernel: Optional[object] = None  # covariance family (DESIGN.md §13)
    # approximation tier (DESIGN.md §14): "lowrank" runs the whole fleet's
    # Nyström builds/heads as ONE problem-batched program (B folded into the
    # bulk-op launches, Plans shared with the single-GP lowrank tier).
    method: str = "exact"
    m_inducing: Optional[int] = None
    strategy: str = "subset"
    inducing: Optional[object] = None  # (m_inducing, D) shared or (B, m_inducing, D)
    jitter: Optional[float] = None

    def __post_init__(self):
        self.kernel = km.resolve_kernel(self.kernel)
        if self.params is None:
            self.params = self.kernel.default_params()
        if self.method not in ("exact", "lowrank"):
            raise ValueError(
                f"method must be 'exact' or 'lowrank', got {self.method!r}"
            )
        if self.method == "lowrank" and self.m_inducing is None:
            raise ValueError("method='lowrank' requires m_inducing")
        x = jnp.asarray(self.x_train, self.dtype)
        if x.ndim == 2:  # (B, n) convenience for 1-D problems
            x = x[..., None]
        y = jnp.asarray(self.y_train, self.dtype)
        if x.ndim != 3 or y.ndim != 2 or x.shape[:2] != y.shape:
            raise ValueError(
                f"GPBatch needs stacked x_train (B, n, D) or (B, n) and "
                f"y_train (B, n) with matching leading axes; got "
                f"x {tuple(jnp.asarray(self.x_train).shape)}, "
                f"y {tuple(y.shape)}. Stack ragged problems to a common n "
                "(they are not padded silently)."
            )
        self.x_train = x
        self.y_train = y
        b = x.shape[0]
        _validate_fleet_params(self.params, self.kernel, b, "GPBatch")
        self._posterior: Optional[pred.PosteriorState] = None
        self._posterior_key = None
        self._lowrank: Optional[lowrank.LowRankState] = None
        self._lowrank_key = None
        self._params_bytes = None  # (params object, host bytes) memo

    @property
    def batch_size(self) -> int:
        return self.x_train.shape[0]

    # -- cached posterior ---------------------------------------------------

    def _cache_key(self):
        p = self.params
        # memoize the device->host transfer of the param leaves: params are
        # immutable jax arrays/floats, so the identity of the params pytree
        # (kept referenced here, so its id cannot be reused) is a sound
        # staleness signal — rebinding self.params (optimize()) refreshes it
        if self._params_bytes is None or self._params_bytes[0] is not p:
            self._params_bytes = (p, _params_key(p))
        return (
            id(self.x_train),
            id(self.y_train),
            self.kernel,
            self._params_bytes[1],
            self.tile_size,
            self.n_streams,
            self.op_backend,
            str(self.update_dtype),
            str(jnp.dtype(self.dtype)),
            self.batch_dispatch,
            self.mesh,
            self.method,
            self.m_inducing,
            self.strategy,
            None if self.jitter is None else float(self.jitter),
            None if self.inducing is None else id(self.inducing),
        )

    def posterior(self) -> pred.PosteriorState:
        """Stacked factors + weights (leading B axis), cached across calls.

        Runs the q_tiles=0 prefix of the problem-batched program (assembly →
        factorization → both substitutions) — the NLML program IS the
        prediction program with zero test tiles, so this shares every
        plan/jit cache with prediction.
        """
        key = self._cache_key()
        if self._posterior is None or self._posterior_key != key:
            obs.inc("cache.posterior.cold")
            env, yc = pred.nlml_program_env(
                self.x_train,
                self.y_train,
                self.params,
                self.tile_size,
                n_streams=self.n_streams,
                backend=self.op_backend,
                update_dtype=self.update_dtype,
                dtype=self.dtype,
                batch_dispatch=self.batch_dispatch,
                mesh=self.mesh,
                kernel=self.kernel,
            )
            self._posterior = pred.PosteriorState(
                lpacked=env["packed"],
                alpha=env["alpha"],
                x_chunks=tiling.pad_features(self.x_train, self.tile_size, dtype=self.dtype),
                n=self.x_train.shape[1],
                m=self.tile_size,
                params=self.params,
                beta=env["y"],
                y_chunks=yc,
                kernel=self.kernel,
            )
            self._posterior_key = key
        else:
            obs.inc("cache.posterior.warm")
        return self._posterior

    def _lowrank_inducing(self):
        """Explicit inducing inputs normalized to stacked (B, m_inducing, D)."""
        if self.inducing is None:
            return None
        ind = jnp.asarray(self.inducing, self.dtype)
        if ind.ndim == 2:  # shared set, broadcast across the fleet
            ind = jnp.broadcast_to(ind[None], (self.batch_size,) + ind.shape)
        return ind

    def lowrank_posterior(self) -> lowrank.LowRankState:
        """Stacked Nyström states (leading B axis), cached across calls."""
        key = self._cache_key()
        if self._lowrank is None or self._lowrank_key != key:
            obs.inc("cache.lowrank.cold")
            self._lowrank = _lowrank_state_with_retry(
                lambda jit: lowrank.lowrank_state(
                    self.x_train,
                    self.y_train,
                    self.params,
                    self.m_inducing,
                    self.tile_size,
                    strategy=self.strategy,
                    inducing=self._lowrank_inducing(),
                    jitter=jit,
                    n_streams=self.n_streams,
                    backend=self.op_backend,
                    update_dtype=self.update_dtype,
                    dtype=self.dtype,
                    batch_dispatch=self.batch_dispatch,
                    kernel=self.kernel,
                ),
                lowrank.DEFAULT_JITTER if self.jitter is None
                else float(self.jitter),
            )
            self._lowrank_key = key
        else:
            obs.inc("cache.lowrank.warm")
        return self._lowrank

    def _lowrank_warm(self) -> bool:
        return self._lowrank is not None and self._lowrank_key == self._cache_key()

    def invalidate_cache(self) -> None:
        self._posterior = None
        self._posterior_key = None
        self._lowrank = None
        self._lowrank_key = None

    # -- streaming updates (DESIGN.md §10) ----------------------------------

    def update(self, x_new: jax.Array, y_new: jax.Array) -> "GPBatch":
        """Fleet-wide online absorption: every problem appends b points.

        x_new (B, b, D) (or (B, b) for 1-D fleets) / y_new (B, b) — the
        shared count b keeps the fleet on one tile geometry, so the whole
        append runs as ONE problem-batched sweep through the same plans as
        a single GP (every launch B times wider).  Warm caches are extended
        in O(n^2 b); a cold cache or a numerically failed append (any
        problem) invalidates and the next prediction re-factorizes the
        fleet.
        """
        from repro.core import update as upd

        x_new = jnp.asarray(x_new, self.dtype)
        if x_new.ndim == 2 and self.x_train.shape[-1] == 1:
            x_new = x_new[..., None]
        y_new = jnp.asarray(y_new, self.dtype)
        b = self.batch_size
        if (
            x_new.ndim != 3
            or x_new.shape[0] != b
            or x_new.shape[-1] != self.x_train.shape[-1]
            or y_new.shape != x_new.shape[:-1]
        ):
            raise ValueError(
                f"GPBatch.update needs stacked x_new (B, b, D) and y_new "
                f"(B, b) with B == {b}; got x {tuple(jnp.asarray(x_new).shape)}, "
                f"y {tuple(y_new.shape)}"
            )
        if x_new.shape[1] == 0:
            return self
        if self.method == "lowrank":
            warm = self._lowrank_warm()
            state = self._lowrank
            self.x_train = jnp.concatenate([self.x_train, x_new], axis=1)
            self.y_train = jnp.concatenate([self.y_train, y_new], axis=1)
            if warm:
                try:
                    self._lowrank = lowrank.absorb(
                        state,
                        x_new,
                        y_new,
                        sign=1,
                        n_streams=self.n_streams,
                        backend=self.op_backend,
                        update_dtype=self.update_dtype,
                        batch_dispatch=self.batch_dispatch,
                    )
                    self._lowrank_key = self._cache_key()
                except upd.CholeskyUpdateError:
                    obs.health_event(
                        "refactorize_fallback", site="batch.update.lowrank"
                    )
                    self.invalidate_cache()
            else:
                self.invalidate_cache()
            return self
        warm = self._cache_warm()
        state = self._posterior
        self.x_train = jnp.concatenate([self.x_train, x_new], axis=1)
        self.y_train = jnp.concatenate([self.y_train, y_new], axis=1)
        if warm and x_new.shape[1] > 0:
            try:
                self._posterior = state.extend(
                    x_new,
                    y_new,
                    n_streams=self.n_streams,
                    backend=self.op_backend,
                    update_dtype=self.update_dtype,
                    batch_dispatch=self.batch_dispatch,
                    mesh=self.mesh,
                )
                self._posterior_key = self._cache_key()
            except upd.CholeskyUpdateError:
                obs.health_event("refactorize_fallback", site="batch.update")
                self.invalidate_cache()
        else:
            self.invalidate_cache()
        return self

    def forget(self, k: int) -> "GPBatch":
        """Evict every problem's k oldest observations (fleet downdate)."""
        from repro.core import update as upd

        n = self.y_train.shape[1]
        if not 0 <= k < n:
            raise ValueError(f"forget(k) needs 0 <= k < n = {n}; got {k}")
        if k == 0:
            return self
        if self.method == "lowrank":
            warm = self._lowrank_warm()
            state = self._lowrank
            x_old, y_old = self.x_train[:, :k], self.y_train[:, :k]
            self.x_train = self.x_train[:, k:]
            self.y_train = self.y_train[:, k:]
            if warm:
                try:
                    self._lowrank = lowrank.absorb(
                        state,
                        x_old,
                        y_old,
                        sign=-1,
                        n_streams=self.n_streams,
                        backend=self.op_backend,
                        update_dtype=self.update_dtype,
                        batch_dispatch=self.batch_dispatch,
                    )
                    self._lowrank_key = self._cache_key()
                except upd.CholeskyUpdateError:
                    obs.health_event(
                        "refactorize_fallback", site="batch.forget.lowrank"
                    )
                    self.invalidate_cache()
            else:
                self.invalidate_cache()
            return self
        warm = self._cache_warm()
        state = self._posterior
        self.x_train = self.x_train[:, k:]
        self.y_train = self.y_train[:, k:]
        if warm and k % self.tile_size == 0:
            try:
                self._posterior = state.shrink(
                    k,
                    n_streams=self.n_streams,
                    backend=self.op_backend,
                    batch_dispatch=self.batch_dispatch,
                    mesh=self.mesh,
                )
                self._posterior_key = self._cache_key()
            except upd.CholeskyUpdateError:
                obs.health_event("refactorize_fallback", site="batch.forget")
                self.invalidate_cache()
        else:
            self.invalidate_cache()
        return self

    def _cache_warm(self) -> bool:
        return self._posterior is not None and self._posterior_key == self._cache_key()

    # -- prediction ---------------------------------------------------------

    def _predict_batched(self, x_test: jax.Array, head: Head):
        """Cold: ONE problem-batched fused program (populates the posterior
        cache from its buffer env).  Warm: batched cross/mean tail off the
        cached stacked factor."""
        if self.method == "lowrank":
            out = lowrank.predict_from_lowrank_state(
                self.lowrank_posterior(),
                x_test,
                full_cov=head != Head.NONE,
                n_streams=self.n_streams,
                backend=self.op_backend,
                dtype=self.dtype,
                batch_dispatch=self.batch_dispatch,
            )
            return _variance_from_full(out, head)
        with _tracer.span("lookup"):
            key = self._cache_key()
            warm = self._posterior is not None and self._posterior_key == key
        if warm:
            obs.inc("cache.posterior.warm")
            return pred.predict_from_state_batched(
                self._posterior,
                x_test,
                head=head,
                n_streams=self.n_streams,
                dtype=self.dtype,
                mesh=self.mesh,
            )
        obs.inc("cache.posterior.cold")
        result, state = pred.predict_fused_batched(
            self.x_train,
            self.y_train,
            x_test,
            self.params,
            self.tile_size,
            head=head,
            n_streams=self.n_streams,
            backend=self.op_backend,
            update_dtype=self.update_dtype,
            dtype=self.dtype,
            with_state=True,
            batch_dispatch=self.batch_dispatch,
            mesh=self.mesh,
            kernel=self.kernel,
        )
        self._posterior, self._posterior_key = state, key
        return result

    def predict(self, x_test: jax.Array) -> jax.Array:
        """Predictive means (B, n̂) for stacked test points (B, n̂, D).

        A shared (n̂, D) test block is broadcast to every problem."""
        with _tracer.span("predict"):
            return self._predict_batched(self._prep(x_test), Head.NONE)

    def predict_full_cov(self, x_test: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Means (B, n̂) and posterior covariances (B, n̂, n̂)."""
        with _tracer.span("predict"):
            return self._predict_batched(self._prep(x_test), Head.COVARIANCE)

    def predict_with_uncertainty(self, x_test: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Means and posterior variances (B, n̂), from the variance head."""
        with _tracer.span("predict"):
            mean, var = self._predict_batched(self._prep(x_test), Head.VARIANCE)
            with _tracer.span("diag"):
                return mean, var

    # -- hyperparameters ----------------------------------------------------

    def nlml(self) -> jax.Array:
        """Per-problem NLML vector (B,) from the cached stacked posterior."""
        from repro.core import mll

        if self.method == "lowrank":
            return lowrank.nlml_from_lowrank_state(
                self.lowrank_posterior(), dtype=self.dtype
            )
        return mll.nlml_from_state(self.posterior(), self.y_train, dtype=self.dtype)

    def log_marginal_likelihood(self) -> jax.Array:
        return -self.nlml()

    def optimize(self, steps: int = 100, lr: float = 0.05) -> "GPBatch":
        """Adam on all B NLMLs in ONE jitted scan (independent Adam states,
        per-problem losses — mll.optimize_hyperparameters_batched)."""
        from repro.core import mll

        new_params, _ = mll.optimize_hyperparameters_batched(
            self.x_train,
            self.y_train,
            self.params,
            steps=steps,
            lr=lr,
            dtype=self.dtype,
            method="lowrank" if self.method == "lowrank" else "tiled",
            tile_size=self.tile_size,
            n_streams=self.n_streams,
            op_backend=self.op_backend,
            update_dtype=self.update_dtype,
            batch_dispatch=self.batch_dispatch,
            kernel=self.kernel,
            m_inducing=self.m_inducing,
            strategy=self.strategy,
            inducing=None if self.method != "lowrank" else self._lowrank_inducing(),
            jitter=self.jitter,
        )
        self.params = new_params
        self.invalidate_cache()  # the factors belong to the old hyperparameters
        return self

    def _prep(self, x_test: jax.Array) -> jax.Array:
        """Normalize test inputs to stacked (B, n̂, D).

        Accepted forms: (B, n̂, D) stacked; (n̂, D) shared across the fleet
        (broadcast); (n̂,) shared 1-D points; and — for 1-D fleets only —
        (B, n̂) stacked per-problem points, mirroring the constructor's
        (B, n) convenience.  When D == 1 and the leading axis equals B, a
        2-D input is read as *stacked* (the constructor's convention), so
        pass shared points for a size-B 1-D fleet as (n̂, 1) with n̂ != B or
        stack them explicitly.
        """
        x_test = jnp.asarray(x_test, self.dtype)
        d = self.x_train.shape[-1]
        b = self.batch_size
        if x_test.ndim == 1:  # shared 1-D test points
            x_test = x_test[:, None]
        if x_test.ndim == 2:
            if d == 1 and x_test.shape[0] == b:
                x_test = x_test[..., None]          # stacked (B, n̂) 1-D points
            elif x_test.shape[-1] == d:
                x_test = jnp.broadcast_to(          # shared (n̂, D) block
                    x_test[None], (b,) + x_test.shape
                )
        if x_test.ndim != 3 or x_test.shape[0] != b or x_test.shape[-1] != d:
            raise ValueError(
                f"x_test must be (n̂, {d}) shared, (B, n̂, {d}) stacked"
                + (", (n̂,) shared or (B, n̂) stacked 1-D points" if d == 1 else "")
                + f" with B == {b}; got {tuple(x_test.shape)}"
            )
        return x_test


# The device's memory over this many times M x (env bytes) of a problem is a
# cold launch's width.  The TPU compile of the ragged covariance-head program
# at tile 128 (8-225 problems, caps 4-16) holds 0.5-2.1 x M x the env buffers
# (packed factor, cross, V and prior tiles) a problem, and 2.8 x where it is
# given more memory than it needs (DESIGN.md §11): at the worst factor a
# launch takes 2.8 / 8 = 35% of the memory, the rest holding the fleet's
# stacked states (one copy: launches write into it) and the answers its
# callers keep.
_COLD_ENV_COPIES = 8


def _device_memory_limit() -> Optional[int]:
    """Bytes the default device can hold, where its backend says so."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def _cold_launch_width(cap_tiles: int, q_tiles: int, m: int, itemsize: int) -> int:
    """Problems per launch of a cold bucket program of this geometry: the
    device's memory over :data:`_COLD_ENV_COPIES` times a problem's share,
    unbounded where the device reports no limit."""
    limit = _device_memory_limit()
    if not limit:
        return sys.maxsize
    env_tiles = (tiling.num_packed_tiles(cap_tiles) + 2 * cap_tiles * q_tiles
                 + q_tiles * q_tiles)
    per_problem = _COLD_ENV_COPIES * cap_tiles * env_tiles * m * m * itemsize
    return max(1, int(limit) // per_problem)


def _pad_rows(a, pad: int):
    """``a`` with ``pad`` zero rows appended on its leading (problem) axis."""
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


@functools.partial(jax.jit, static_argnums=3, donate_argnums=0)
def _write_rows(buf, part, start, rows):
    """``buf`` with the first ``rows`` rows of ``part`` written from row
    ``start``, in place: each launch of a cold bucket lands in one stacked
    copy, never beside it."""
    return jax.tree_util.tree_map(
        lambda b, p: jax.lax.dynamic_update_slice_in_dim(b, p[:rows], start, 0),
        buf, part)


@jax.jit
def _rows(a):
    """The rows of a stacked array as separate arrays, in one dispatch."""
    return tuple(a[i] for i in range(a.shape[0]))


@dataclasses.dataclass
class _Bucket:
    """One bucket of a :class:`GPFleet`: a ragged slice sharing a geometry."""

    idx: Tuple[int, ...]                       # fleet indices, bucket order
    state: Optional[object]                    # stacked ragged state (warm):
    #   PosteriorState (exact) or lowrank.LowRankState (method="lowrank")
    key: object                                # fleet cache key at build time


@dataclasses.dataclass
class GPFleet:
    """B independent GPs of *different* sizes, bucketed by tile geometry.

    The ragged front-end (DESIGN.md §11): problems are grouped into buckets
    whose tile-count capacities come from ``tiling.bucket_boundaries``
    (default powers of two), zero-padded to the bucket capacity, and each
    bucket runs as ONE ragged problem-batched fused program with per-problem
    ``n_valid`` frontiers as *traced* operands.  One jit trace and one
    lru-cached executor Plan per bucket geometry serve every size mix and
    every batch width — never one per problem.

    ``update`` absorbs ragged arrival counts b_i in-place per bucket
    (``update.extend_state_ragged``) and transparently *migrates* problems
    that outgrow their bucket: the factor is re-embedded into the larger
    geometry as ``blockdiag(L, I)`` — a pure gather (``tiling.embed_packed``,
    zero FLOPs) — before the warm append, so migration never re-factorizes.

    Same caching contract as :class:`GPBatch`; hyperparameter leaves may be
    scalars (shared) or (B,) vectors (per-problem, gathered per bucket).
    """

    x_train: Sequence            # length-B list of (n_i, D) or (n_i,) arrays
    y_train: Sequence            # length-B list of (n_i,) arrays
    params: Optional[object] = None  # None -> kernel.default_params()
    tile_size: int = 64
    n_streams: Optional[int] = None
    op_backend: str = "jnp"
    update_dtype: Optional[object] = None
    dtype: object = jnp.float32
    batch_dispatch: str = "flat"
    boundaries: object = tiling.DEFAULT_BUCKETS
    # optional jax.sharding.Mesh: shard each bucket's stacked problem axis
    # over the mesh's DP axes (DESIGN.md §12).  Bucket programs are already
    # B-invariant, so the same Plans/traces drive any device count; buckets
    # whose width doesn't divide the mesh fall back to replication
    # per-bucket (fleet_spec), never to an error.
    mesh: Optional[object] = None
    kernel: Optional[object] = None  # covariance family (DESIGN.md §13)
    # approximation tier (DESIGN.md §14).  Under "lowrank" every bucket's
    # cached state is mu-sized (inducing chunks + m×m inner factors — nothing
    # n-sized), so bucket *migration* needs no factor re-embedding at all:
    # transfer is a pure row gather of the stacked state, then a ragged
    # absorb of the arrivals.
    method: str = "exact"
    m_inducing: Optional[int] = None
    strategy: str = "subset"
    inducing: Optional[object] = None  # (m_inducing, D) shared across the fleet
    jitter: Optional[float] = None

    def __post_init__(self):
        self.kernel = km.resolve_kernel(self.kernel)
        if self.params is None:
            self.params = self.kernel.default_params()
        if self.method not in ("exact", "lowrank"):
            raise ValueError(
                f"method must be 'exact' or 'lowrank', got {self.method!r}"
            )
        if self.method == "lowrank" and self.m_inducing is None:
            raise ValueError("method='lowrank' requires m_inducing")
        xs, ys = [], []
        if len(self.x_train) != len(self.y_train) or not len(self.x_train):
            raise ValueError(
                f"GPFleet needs equal-length, non-empty x/y lists; got "
                f"{len(self.x_train)} and {len(self.y_train)}"
            )
        d = None
        for i, (x, y) in enumerate(zip(self.x_train, self.y_train)):
            x = jnp.asarray(x, self.dtype)
            if x.ndim == 1:
                x = x[:, None]
            y = jnp.asarray(y, self.dtype).reshape(-1)
            if x.ndim != 2 or x.shape[0] != y.shape[0] or y.shape[0] < 1:
                raise ValueError(
                    f"problem {i}: x must be (n, D) or (n,) with n == "
                    f"len(y) >= 1; got x {tuple(x.shape)}, y {tuple(y.shape)}"
                )
            if d is None:
                d = x.shape[1]
            elif x.shape[1] != d:
                raise ValueError(
                    f"problem {i}: feature dim {x.shape[1]} != {d} — all "
                    "fleet problems must share D"
                )
            xs.append(x)
            ys.append(y)
        self._xs: List[jax.Array] = xs
        self._ys: List[jax.Array] = ys
        b = len(xs)
        _validate_fleet_params(self.params, self.kernel, b, "GPFleet")
        self._buckets: Dict[int, _Bucket] = {}
        self._stacks: Dict[int, tuple] = {}  # cap -> (data key, _stack's arrays)
        self._version = 0
        self._params_bytes = None

    @property
    def batch_size(self) -> int:
        return len(self._xs)

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(y.shape[0] for y in self._ys)

    def bucket_assignment(self) -> Dict[int, List[int]]:
        """Current ``{cap_tiles: [fleet indices]}`` map (recomputed)."""
        return tiling.bucket_problems(self.sizes, self.tile_size, self.boundaries)

    # -- cached per-bucket posteriors ---------------------------------------

    def _cache_key(self):
        p = self.params
        if self._params_bytes is None or self._params_bytes[0] is not p:
            self._params_bytes = (p, _params_key(p))
        return (
            self._version,
            self.kernel,
            self._params_bytes[1],
            self.tile_size,
            self.n_streams,
            self.op_backend,
            str(self.update_dtype),
            str(jnp.dtype(self.dtype)),
            self.batch_dispatch,
            self.boundaries if not isinstance(self.boundaries, (list, tuple))
            else tuple(self.boundaries),
            self.mesh,
            self.method,
            self.m_inducing,
            self.strategy,
            None if self.jitter is None else float(self.jitter),
            None if self.inducing is None else id(self.inducing),
        )

    def invalidate_cache(self) -> None:
        self._buckets = {}

    def _bucket_params(self, idx):
        """Per-problem leaves gathered into the bucket's rows, shared leaves
        passed through — a ``tree_map`` over the params pytree, so any
        registered kernel's params (ARD vectors, composite trees) bucket
        correctly (km.gather_params)."""
        return km.gather_params(self.params, jnp.asarray(idx), self.kernel)

    def _stack(self, idx, cap_tiles):
        """Zero-pad the bucket's problems to the capacity and stack them,
        on the host: one transfer per array, not one pad per problem."""
        capn = cap_tiles * self.tile_size
        dt = np.dtype(self.dtype)
        xs = np.zeros((len(idx), capn, self._xs[0].shape[-1]), dt)
        ys = np.zeros((len(idx), capn), dt)
        hx = jax.device_get([self._xs[i] for i in idx])
        hy = jax.device_get([self._ys[i] for i in idx])
        for pos, (x, y) in enumerate(zip(hx, hy)):
            xs[pos, : len(y)] = x
            ys[pos, : len(y)] = y
        nv = np.asarray([len(y) for y in hy], np.int32)
        return jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(nv)

    def _stacked(self, cap_tiles, idx):
        """The bucket's ``_stack``, kept until the data changes: new
        hyperparameters re-factor a bucket but never re-stack it."""
        key = (self._version, tuple(idx), self.tile_size, str(jnp.dtype(self.dtype)))
        rec = self._stacks.get(cap_tiles)
        if rec is None or rec[0] != key:
            with _tracer.span("stack"):
                rec = (key, self._stack(idx, cap_tiles))
            self._stacks[cap_tiles] = rec
        return rec[1]

    def _warm_state(self, cap_tiles, idx):
        """The bucket's cached state if it is current, else None."""
        with _tracer.span("bucket"):
            rec = self._buckets.get(cap_tiles)
            warm = rec is not None and rec.state is not None \
                and rec.idx == tuple(idx) and rec.key == self._cache_key()
        if not warm:
            return None
        obs.inc("cache.bucket.warm")
        return rec.state

    def _bucket_state(self, cap_tiles, idx):
        """Warm cached stacked state for one bucket, (re)built cold on miss."""
        state = self._warm_state(cap_tiles, idx)
        if state is not None:
            return state
        if self.method != "lowrank":
            return self._cold_bucket(cap_tiles, idx, None, None, Head.NONE)[1]
        obs.inc("cache.bucket.cold")
        xs, ys, nv = self._stacked(cap_tiles, idx)
        bp = self._bucket_params(idx)
        ind = self.inducing
        if ind is not None:
            ind = jnp.asarray(ind, self.dtype)
            if ind.ndim == 2:  # one shared set, broadcast over the bucket
                ind = jnp.broadcast_to(ind[None], (len(idx),) + ind.shape)
            else:
                ind = ind[jnp.asarray(idx)]
        state = _lowrank_state_with_retry(
            lambda jit: lowrank.lowrank_state(
                xs, ys, bp, self.m_inducing, self.tile_size,
                strategy=self.strategy, inducing=ind,
                jitter=jit,
                n_streams=self.n_streams, backend=self.op_backend,
                update_dtype=self.update_dtype, dtype=self.dtype,
                batch_dispatch=self.batch_dispatch, n_valid=nv,
                kernel=self.kernel,
            ),
            lowrank.DEFAULT_JITTER if self.jitter is None
            else float(self.jitter),
        )
        self._buckets[cap_tiles] = _Bucket(tuple(idx), state, self._cache_key())
        return state

    def _cold_bucket(self, cap_tiles, idx, xt, ntv, head: Head):
        """One bucket built cold by the fused ragged program (DESIGN.md §11).

        Answers the bucket's queries ``xt`` (b, n̂, D), ``ntv`` (b,) valid
        counts, under ``head`` — or none with ``xt=None``, the NLML prefix —
        and caches the stacked state the program leaves.  The bucket runs as
        ceil(b / W) launches of one program of width W
        (:func:`_cold_launch_width`), the last padded with masked problems
        (``n_valid`` 0); each launch's outputs are written into one stacked
        copy of the bucket's rows.  Returns ``(result, state)``.
        """
        obs.inc("cache.bucket.cold")
        self._buckets.pop(cap_tiles, None)  # free the stale state first
        b, m = len(idx), self.tile_size
        xs, ys, nv = self._stacked(cap_tiles, idx)
        if xt is None:
            xt = jnp.zeros((b, 0, xs.shape[-1]), self.dtype)
            ntv = jnp.zeros((b,), jnp.int32)
        w = min(b, _cold_launch_width(cap_tiles, -(-xt.shape[1] // m), m,
                                      jnp.dtype(self.dtype).itemsize))
        bp = self._bucket_params(idx)
        sizes = self.sizes
        out = None  # the launches' answers and state leaves, (b, ...) stacked
        for s in range(0, b, w):
            e = min(s + w, b)
            pad = w - (e - s)
            rows = lambda a: a[s:e] if pad == 0 else _pad_rows(a[s:e], pad)
            # masked rows repeat the last problem's hyperparameters
            p = bp if w == b else self._bucket_params(list(idx[s:e]) + [idx[e - 1]] * pad)
            with _tracer.span("launch"):
                result, st = pred.predict_fused_batched(
                    rows(xs), rows(ys), rows(xt), p, m, head=head,
                    n_streams=self.n_streams, backend=self.op_backend,
                    update_dtype=self.update_dtype, dtype=self.dtype,
                    with_state=True, batch_dispatch=self.batch_dispatch,
                    n_valid=rows(nv), nt_valid=rows(ntv), mesh=self.mesh,
                    kernel=self.kernel,
                )
            part = (result, (st.lpacked, st.alpha, st.x_chunks, st.beta, st.y_chunks))
            if w == b:
                out = part
            else:
                if out is None:
                    out = jax.tree_util.tree_map(
                        lambda a: jnp.zeros((b,) + a.shape[1:], a.dtype), part)
                out = _write_rows(out, part, s, e - s)
            del part, result, st
            if obs.enabled():
                obs.inc("fleet.cold.launches")
                obs.observe("fleet.cold.launch_width", w, obs.COUNT_EDGES)
                obs.inc("fleet.cold.masked_problems", pad)
                obs.inc("fleet.cold.rows_valid", sum(sizes[i] for i in idx[s:e]))
                obs.inc("fleet.cold.rows_padded", w * cap_tiles * m)
        result, (lpacked, alpha, x_chunks, beta, y_chunks) = out
        state = pred.PosteriorState(
            lpacked=lpacked, alpha=alpha, x_chunks=x_chunks,
            n=cap_tiles * m, m=m, params=bp, beta=beta, y_chunks=y_chunks,
            n_valid=nv, kernel=self.kernel,
        )
        self._buckets[cap_tiles] = _Bucket(tuple(idx), state, self._cache_key())
        return result, state

    # -- prediction ---------------------------------------------------------

    def _prep_shared(self, x_test) -> jax.Array:
        x_test = jnp.asarray(x_test, self.dtype)
        d = self._xs[0].shape[-1]
        if x_test.ndim == 1:
            x_test = x_test[:, None]
        if x_test.ndim != 2 or x_test.shape[-1] != d:
            raise ValueError(
                f"GPFleet shared x_test must be (n̂, {d})"
                + (" or (n̂,)" if d == 1 else "")
                + f"; got {tuple(jnp.asarray(x_test).shape)}. "
                "Use predict_each for per-problem test sets."
            )
        return x_test

    def _predict_bucket(self, cap, idx, xt, ntv, head: Head):
        """One bucket's answers to stacked queries: the fused program when
        its state is stale (exact tier), else the warm tail off the cache."""
        if self.method == "lowrank":
            out = lowrank.predict_from_lowrank_state(
                self._bucket_state(cap, idx), xt, full_cov=head != Head.NONE,
                n_streams=self.n_streams, backend=self.op_backend, dtype=self.dtype,
                nt_valid=ntv, batch_dispatch=self.batch_dispatch,
            )
            return _variance_from_full(out, head)
        state = self._warm_state(cap, idx)
        if state is None:
            return self._cold_bucket(cap, idx, xt, ntv, head)[0]
        return pred.predict_from_state_batched(
            state, xt, head=head, n_streams=self.n_streams, dtype=self.dtype,
            nt_valid=ntv, mesh=self.mesh,
        )

    def _predict_shared(self, x_test, head: Head):
        """One shared (n̂, D) test block evaluated under every problem."""
        x_test = self._prep_shared(x_test)
        nh = x_test.shape[0]
        b = self.batch_size
        mean = jnp.zeros((b, nh), self.dtype)
        unc = None  # the head's output: (B, n̂, n̂) covariances or (B, n̂) variances
        if head == Head.COVARIANCE:
            unc = jnp.zeros((b, nh, nh), self.dtype)
        elif head == Head.VARIANCE:
            unc = jnp.zeros((b, nh), self.dtype)
        for cap, idx in self.bucket_assignment().items():
            with _tracer.span("stack"):
                xt = jnp.broadcast_to(x_test[None], (len(idx),) + x_test.shape)
            out = self._predict_bucket(
                cap, idx, xt, jnp.full((len(idx),), nh, jnp.int32), head)
            gather = jnp.asarray(idx)
            if unc is not None:
                mean = mean.at[gather].set(out[0])
                unc = unc.at[gather].set(out[1])
            else:
                mean = mean.at[gather].set(out)
        return mean if unc is None else (mean, unc)

    def predict(self, x_test) -> jax.Array:
        """Means (B, n̂) for one shared (n̂, D) test block."""
        with _tracer.span("predict"):
            return self._predict_shared(x_test, Head.NONE)

    def predict_full_cov(self, x_test) -> Tuple[jax.Array, jax.Array]:
        with _tracer.span("predict"):
            return self._predict_shared(x_test, Head.COVARIANCE)

    def predict_with_uncertainty(self, x_test) -> Tuple[jax.Array, jax.Array]:
        """Means and posterior variances (B, n̂), from the variance head."""
        with _tracer.span("predict"):
            mean, var = self._predict_shared(x_test, Head.VARIANCE)
            with _tracer.span("diag"):
                return mean, var

    def predict_each(self, x_test_list, *, full_cov: bool = False):
        """Per-problem test sets (list of (n̂_i, D)); ragged n̂_i are padded
        to each bucket's max and masked with ``nt_valid`` — one launch per
        bucket (or per width-W slice of a cold one), results split back to
        each problem's own n̂_i.

        Returns a length-B list of (n̂_i,) means (or ``(mean, cov)`` tuples
        with cov (n̂_i, n̂_i) when ``full_cov``)."""
        with _tracer.span("predict"):
            return self._predict_each(x_test_list, full_cov)

    def _predict_each(self, x_test_list, full_cov: bool):
        b = self.batch_size
        if len(x_test_list) != b:
            raise ValueError(
                f"predict_each needs one test set per problem ({b}); "
                f"got {len(x_test_list)}"
            )
        d = self._xs[0].shape[-1]
        dt = np.dtype(self.dtype)
        tests = []
        for i, xt in enumerate(jax.device_get(list(x_test_list))):
            xt = np.asarray(xt, dt)
            if xt.ndim == 1:
                xt = xt[:, None]
            if xt.ndim != 2 or xt.shape[-1] != d:
                raise ValueError(
                    f"test set {i} must be (n̂, {d}); got {tuple(xt.shape)}"
                )
            tests.append(xt)
        head = Head.COVARIANCE if full_cov else Head.NONE
        out: List[object] = [None] * b
        empty = jnp.zeros((0,), self.dtype)
        empty_cov = jnp.zeros((0, 0), self.dtype)
        for cap, idx in self.bucket_assignment().items():
            nts = [tests[i].shape[0] for i in idx]
            if not any(nts):  # no pending queries touch this bucket
                for i in idx:
                    out[i] = (empty, empty_cov) if full_cov else empty
                continue
            nt_max = max(nts)
            with _tracer.span("stack"):
                xt = np.zeros((len(idx), nt_max, d), dt)
                for pos, i in enumerate(idx):
                    xt[pos, : nts[pos]] = tests[i]
                xt = jnp.asarray(xt)
            res = self._predict_bucket(
                cap, idx, xt, jnp.asarray(nts, jnp.int32), head)
            with _tracer.span("unstack"):
                means = _rows(res[0] if full_cov else res)
                covs = _rows(res[1]) if full_cov else None
                for pos, i in enumerate(idx):
                    k = nts[pos]
                    mean = means[pos] if k == nt_max else means[pos][:k]
                    if not full_cov:
                        out[i] = mean
                    else:
                        out[i] = (mean, covs[pos] if k == nt_max else covs[pos][:k, :k])
        return out

    # -- NLML ---------------------------------------------------------------

    def nlml(self) -> jax.Array:
        """Per-problem NLML vector (B,), one masked head per bucket."""
        from repro.core import mll

        b = self.batch_size
        out = jnp.zeros((b,), self.dtype)
        for cap, idx in self.bucket_assignment().items():
            state = self._bucket_state(cap, idx)
            if self.method == "lowrank":
                vals = lowrank.nlml_from_lowrank_state(state, dtype=self.dtype)
            else:
                _, ys, nv = self._stacked(cap, idx)
                vals = mll.nlml_from_state(state, ys, dtype=self.dtype, n_valid=nv)
            out = out.at[jnp.asarray(idx)].set(vals.astype(self.dtype))
        return out

    def log_marginal_likelihood(self) -> jax.Array:
        return -self.nlml()

    def optimize(self, steps: int = 100, lr: float = 0.05) -> "GPFleet":
        """Fit every problem's hyperparameters (the off-hot-path re-optimize
        the serving loop's drift monitor schedules — DESIGN.md §15).

        Each problem trains independently at its *own exact size* — no
        padding rows in the training loss, unlike a bucket-stacked scan —
        via the single-problem Adam scan (mll.optimize_hyperparameters) on
        its gathered leaves.  The fitted pytrees are stacked back into
        per-problem ``(B,) + base`` leaves: any leaf that started shared
        comes back per-problem, because independently fitted problems
        drift apart.  Caches invalidate; the next predict/nlml
        re-factorizes each bucket under the new hyperparameters.
        """
        from repro.core import mll

        method = "lowrank" if self.method == "lowrank" else "tiled"
        fitted = []
        for i in range(self.batch_size):
            pi = km.gather_params(self.params, jnp.asarray(i), self.kernel)
            new_pi, _ = mll.optimize_hyperparameters(
                self._xs[i],
                self._ys[i],
                pi,
                steps=steps,
                lr=lr,
                dtype=self.dtype,
                method=method,
                tile_size=self.tile_size,
                n_streams=self.n_streams,
                op_backend=self.op_backend,
                update_dtype=self.update_dtype,
                kernel=self.kernel,
                m_inducing=self.m_inducing,
                strategy=self.strategy,
                inducing=self.inducing,
                jitter=self.jitter,
            )
            fitted.append(new_pi)
        self.params = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack([jnp.asarray(l) for l in leaves]), *fitted
        )
        obs.inc("fleet.optimize")
        self.invalidate_cache()  # factors belong to the old hyperparameters
        return self

    # -- ragged streaming updates (DESIGN.md §11) ---------------------------

    def update(self, x_new_list, y_new_list) -> "GPFleet":
        """Absorb ragged arrivals: problem i gains ``len(y_new_list[i])``
        points (0 allowed).  Problems that stay inside their bucket extend
        warm in O(n^2 b); problems that outgrow it migrate — the factor is
        re-embedded into the destination geometry as ``blockdiag(L, I)``
        (pure gather) and extended there.  A cold or numerically failed
        bucket re-factorizes lazily on the next predict/nlml."""
        from repro.core import update as upd

        b = self.batch_size
        if len(x_new_list) != b or len(y_new_list) != b:
            raise ValueError(
                f"update needs one arrival block per problem ({b}); got "
                f"{len(x_new_list)} and {len(y_new_list)}"
            )
        d = self._xs[0].shape[-1]
        xn, yn = [], []
        for i, (x, y) in enumerate(zip(x_new_list, y_new_list)):
            x = jnp.asarray(x, self.dtype).reshape(-1, d)
            y = jnp.asarray(y, self.dtype).reshape(-1)
            if x.shape[0] != y.shape[0]:
                raise ValueError(
                    f"arrival {i}: x has {x.shape[0]} rows, y {y.shape[0]}"
                )
            xn.append(x)
            yn.append(y)
        counts = np.asarray([y.shape[0] for y in yn], np.int64)
        if not counts.any():
            return self
        if self.method == "lowrank":
            return self._update_lowrank(xn, yn, counts)

        old_assign = self.bucket_assignment()
        old_key = self._cache_key()
        # per-problem warm source rows: i -> (cap_old, state, row position)
        src: Dict[int, Tuple[int, pred.PosteriorState, int]] = {}
        for cap, idx in old_assign.items():
            rec = self._buckets.get(cap)
            if rec is not None and rec.key == old_key \
                    and rec.idx == tuple(idx) and rec.state is not None:
                for pos, i in enumerate(idx):
                    src[i] = (cap, rec.state, pos)

        old_ns = np.asarray(self.sizes, np.int64)
        for i in range(b):
            if counts[i]:
                self._xs[i] = jnp.concatenate([self._xs[i], xn[i]])
                self._ys[i] = jnp.concatenate([self._ys[i], yn[i]])
        self._version += 1
        new_key = self._cache_key()

        new_buckets: Dict[int, _Bucket] = {}
        for cap, idx in self.bucket_assignment().items():
            state = None
            if all(i in src for i in idx):
                try:
                    state = self._transfer_bucket(cap, idx, src, old_ns)
                    cnt = counts[np.asarray(idx)]
                    if cnt.any():
                        b_max = int(cnt.max())
                        xa = jnp.stack(
                            [jnp.pad(xn[i], ((0, b_max - xn[i].shape[0]), (0, 0)))
                             for i in idx]
                        )
                        ya = jnp.stack(
                            [jnp.pad(yn[i], (0, b_max - yn[i].shape[0]))
                             for i in idx]
                        )
                        state = upd.extend_state_ragged(
                            state, xa, ya, cnt,
                            n_streams=self.n_streams, backend=self.op_backend,
                            update_dtype=self.update_dtype,
                            batch_dispatch=self.batch_dispatch,
                            mesh=self.mesh,
                        )
                except upd.CholeskyUpdateError:
                    obs.health_event(
                        "refactorize_fallback", site="fleet.update", cap=cap
                    )
                    state = None
            new_buckets[cap] = _Bucket(tuple(idx), state, new_key)
        self._buckets = new_buckets
        return self

    def _transfer_bucket(self, cap, idx, src, old_ns) -> pred.PosteriorState:
        """Assemble a destination bucket's pre-append state from warm source
        rows, re-embedding factors that cross a geometry boundary as
        blockdiag(L, I) — a gather, zero FLOPs (``tiling.embed_packed``)."""
        m = self.tile_size
        d = self._xs[0].shape[-1]
        lp, al, xc, be, yc = [], [], [], [], []
        for i in idx:
            cap_s, st, pos = src[i]
            lpi = st.lpacked[pos]
            if cap_s != cap:
                lpi = tiling.embed_packed(lpi, cap_s, cap)
            pad = cap - cap_s
            lp.append(lpi)
            al.append(jnp.pad(st.alpha[pos], ((0, pad), (0, 0))))
            be.append(jnp.pad(st.beta[pos], ((0, pad), (0, 0))))
            yc.append(jnp.pad(st.y_chunks[pos], ((0, pad), (0, 0))))
            xc.append(jnp.pad(st.x_chunks[pos], ((0, pad), (0, 0), (0, 0))))
        return pred.PosteriorState(
            lpacked=jnp.stack(lp), alpha=jnp.stack(al), x_chunks=jnp.stack(xc),
            n=cap * m, m=m, params=self._bucket_params(idx),
            beta=jnp.stack(be), y_chunks=jnp.stack(yc),
            n_valid=jnp.asarray(old_ns[np.asarray(idx)], jnp.int32),
            kernel=self.kernel,
        )

    def _update_lowrank(self, xn, yn, counts) -> "GPFleet":
        """Ragged absorption through the rank-m inner systems.

        The low-rank bucket state is mu-sized (nothing n-sized lives in it),
        so a problem crossing a bucket boundary needs NO factor re-embedding:
        the destination state is a pure row gather of the warm source rows
        (``_gather_lowrank_rows``), followed by one ragged ``lowrank.absorb``
        per destination bucket.  A cold or numerically failed bucket rebuilds
        lazily on the next predict/nlml, same as the exact tier."""
        from repro.core import update as upd

        b = self.batch_size
        old_assign = self.bucket_assignment()
        old_key = self._cache_key()
        # per-problem warm source rows: i -> (state, row position)
        src: Dict[int, Tuple[object, int]] = {}
        for cap, idx in old_assign.items():
            rec = self._buckets.get(cap)
            if rec is not None and rec.key == old_key \
                    and rec.idx == tuple(idx) and rec.state is not None:
                for pos, i in enumerate(idx):
                    src[i] = (rec.state, pos)
        for i in range(b):
            if counts[i]:
                self._xs[i] = jnp.concatenate([self._xs[i], xn[i]])
                self._ys[i] = jnp.concatenate([self._ys[i], yn[i]])
        self._version += 1
        new_key = self._cache_key()
        new_buckets: Dict[int, _Bucket] = {}
        for cap, idx in self.bucket_assignment().items():
            state = None
            if all(i in src for i in idx):
                try:
                    state = self._gather_lowrank_rows(cap, idx, src)
                    cnt = counts[np.asarray(idx)]
                    if cnt.any():
                        b_max = int(cnt.max())
                        xa = jnp.stack(
                            [jnp.pad(xn[i], ((0, b_max - xn[i].shape[0]), (0, 0)))
                             for i in idx]
                        )
                        ya = jnp.stack(
                            [jnp.pad(yn[i], (0, b_max - yn[i].shape[0]))
                             for i in idx]
                        )
                        state = lowrank.absorb(
                            state, xa, ya, cnt, sign=1,
                            n_streams=self.n_streams, backend=self.op_backend,
                            update_dtype=self.update_dtype,
                            batch_dispatch=self.batch_dispatch,
                        )
                except upd.CholeskyUpdateError:
                    obs.health_event(
                        "refactorize_fallback", site="fleet.update.lowrank",
                        cap=cap,
                    )
                    state = None
            new_buckets[cap] = _Bucket(tuple(idx), state, new_key)
        self._buckets = new_buckets
        return self

    def _gather_lowrank_rows(self, cap, idx, src) -> lowrank.LowRankState:
        """Destination bucket's pre-absorb state from warm source rows — a
        gather, zero FLOPs (every per-problem piece is mu-sized)."""
        rows = [src[i] for i in idx]

        def g(field):
            return jnp.stack([getattr(st, field)[pos] for st, pos in rows])

        mv = jnp.asarray(
            [int(st.mu_valid[pos]) if st.mu_valid is not None
             else st.m_inducing for st, pos in rows],
            jnp.int32,
        )
        nv = jnp.asarray(
            [int(st.n_valid[pos]) if st.n_valid is not None else st.n
             for st, pos in rows],
            jnp.int32,
        )
        return lowrank.LowRankState(
            u_chunks=g("u_chunks"),
            luu_packed=g("luu_packed"),
            b_packed=g("b_packed"),
            lb_packed=g("lb_packed"),
            c_chunks=g("c_chunks"),
            gamma=g("gamma"),
            yty=g("yty"),
            n=cap * self.tile_size,
            m=self.tile_size,
            m_inducing=self.m_inducing,
            params=self._bucket_params(idx),
            jitter=rows[0][0].jitter,
            mu_valid=mv,
            n_valid=nv,
            kernel=self.kernel,
        )

