"""Gaussian-process covariance math: the kernel registry + jnp references.

The paper (Eq. 1) uses the squared-exponential kernel

    k(x_i, x_j) = v * exp( -1/(2*l) * sum_d (x_i_d - x_j_d)^2 ) + delta_ij * sigma^2

with hyperparameters: lengthscale ``l``, vertical lengthscale ``v`` and noise
variance ``sigma^2``.  Note the paper's parameterization divides by ``2*l``
(not ``2*l**2``); we follow the paper exactly, and every other stationary
family in the registry keeps the same convention (``lengthscale`` scales
*squared* distances).

Beyond the paper's SE kernel this module hosts the **kernel registry**
(DESIGN.md §13): ``Kernel`` subclasses (SE, Matérn 1/2 · 3/2 · 5/2, rational
quadratic, per-dimension ARD SE and Matérn 5/2, white noise) and ``Sum`` /
``Product`` / ``Scaled`` composition.  A kernel is a frozen, hashable
dataclass — it joins jit/posterior cache keys directly — and its
hyperparameters live in a separate params *pytree* so the same kernel object
serves concrete params (Pallas assembly with baked constants) and traced
params (differentiable jnp assembly under ``grad``).  The contract each kernel implements:

  * ``kfree(params, xa, xb)`` — the noise-free covariance block, pure jnp,
    valid both under tracing and inside a Pallas kernel body with host
    constants for params.
  * ``noise(params)`` — the variance added on the *global* diagonal of a
    training covariance (zero for kernels with no observation-noise role).
  * ``diag(params)`` — the exact value of ``kfree(x, x)`` for stationary
    kernels; assembly pins the global diagonal to ``diag + noise`` instead
    of trusting the cancellation-prone expanded distance form.
  * ``default_params()`` / ``base_ndims(params)`` — the params pytree and
    the per-leaf base rank (0 for scalars, 1 for ARD lengthscale vectors)
    that lets generic code detect/broadcast per-problem (B,)-batched leaves.

Everything here is dtype-parametric and shape-padding aware: covariance
assembly can generate *padded* matrices where rows/cols with global index
``>= n_valid`` are replaced by identity (diagonal blocks) or zero
(off-diagonal / cross blocks).  Padding with an identity block is exactly
equivalent to solving the unpadded system (the Cholesky factor of
``blockdiag(K, I)`` is ``blockdiag(L, I)``), which lets the tiled pipeline
require only ``n % m == 0`` internally while the public API accepts any n.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Hyperparameter pytrees
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SEKernelParams:
    """Hyperparameters of the paper's SE kernel (Eq. 1).

    Also the params pytree of every simple stationary family with the same
    three knobs (Matérn 1/2 · 3/2 · 5/2): lengthscale, vertical lengthscale
    and observation-noise variance.
    """

    lengthscale: jax.Array | float = 1.0
    vertical: jax.Array | float = 1.0
    noise: jax.Array | float = 0.1  # sigma^2 (variance, not std)

    @staticmethod
    def paper_defaults() -> "SEKernelParams":
        # Section 4.1: l = 1, v = 1, sigma^2 = 0.1.
        return SEKernelParams(1.0, 1.0, 0.1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RQKernelParams:
    """Rational-quadratic hyperparameters (SE mixture over lengthscales)."""

    lengthscale: jax.Array | float = 1.0
    vertical: jax.Array | float = 1.0
    noise: jax.Array | float = 0.1
    alpha: jax.Array | float = 1.0  # mixture concentration; RQ -> SE as alpha -> inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ARDKernelParams:
    """SE-ARD hyperparameters: one lengthscale per feature dimension."""

    lengthscales: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.ones((1,))
    )  # (D,) or per-problem (B, D)
    vertical: jax.Array | float = 1.0
    noise: jax.Array | float = 0.1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WhiteKernelParams:
    """White-noise hyperparameter: the observation-noise variance."""

    noise: jax.Array | float = 0.1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScaledParams:
    """Params of ``Scaled``: an output-scale knob wrapping the child's pytree."""

    scale: jax.Array | float = 1.0
    inner: Any = None


# ---------------------------------------------------------------------------
# Distance helpers
# ---------------------------------------------------------------------------


def sq_dists(x1: jax.Array, x2: jax.Array) -> jax.Array:
    """Pairwise squared euclidean distances. x1: (n1, D), x2: (n2, D) -> (n1, n2).

    Uses the expanded form |a|^2 + |b|^2 - 2 a.b so the inner product hits the
    MXU on TPU; clamped at zero for numerical safety.  The expanded form
    cancels catastrophically for large-magnitude inputs (the self-distance is
    not exactly zero in f32) — training-covariance assembly therefore never
    trusts it on the global diagonal and pins ``diag + noise`` exactly.
    """
    n1sq = jnp.sum(x1 * x1, axis=-1, keepdims=True)      # (n1, 1)
    n2sq = jnp.sum(x2 * x2, axis=-1, keepdims=True).T    # (1, n2)
    cross = jax.lax.dot_general(
        x1, x2, (((1,), (1,)), ((), ())), preferred_element_type=x1.dtype
    )
    return jnp.maximum(n1sq + n2sq - 2.0 * cross, 0.0)


def _safe_sqrt(d2: jax.Array) -> jax.Array:
    """sqrt with a zero (not NaN) gradient at d2 == 0 (double-where trick)."""
    pos = d2 > 0.0
    return jnp.where(pos, jnp.sqrt(jnp.where(pos, d2, 1.0)), 0.0)


# ---------------------------------------------------------------------------
# The kernel registry
# ---------------------------------------------------------------------------


class Kernel:
    """Base of the registry contract (see module docstring / DESIGN.md §13).

    Subclasses are frozen dataclasses: hashable with structural equality, so
    a kernel instance can join lru/jit cache keys directly.  ``analytic_vjp``
    marks kernels with hand-derived dK/dtheta in ``mll`` (only SE today);
    everything else trains through plain autodiff of the fused program.
    """

    name: ClassVar[str] = "kernel"
    analytic_vjp: ClassVar[bool] = False

    def default_params(self):
        raise NotImplementedError

    def kfree(self, params, xa: jax.Array, xb: jax.Array) -> jax.Array:
        """Noise-free covariance block (n1, n2); pure jnp, Pallas-body safe."""
        raise NotImplementedError

    def noise(self, params):
        return params.noise

    def diag(self, params):
        """Exact k(x, x) — constant for the stationary families hosted here."""
        return params.vertical

    def base_ndims(self, params):
        """Per-leaf base rank of the params pytree (before any (B,) batching)."""
        return jax.tree_util.tree_map(lambda _: 0, params)

    def kfree_vjp(self, params, xa, xb, g):
        """Hand-derived VJP of ``sum(g * kfree(params, xa, xb))``.

        Returns ``(g_params, g_xa, g_xb)`` where ``g_params`` matches the
        params pytree (the ``noise`` leaf is zero — kfree is noise-free; the
        caller folds its own noise cotangent in) and ``g_xa``/``g_xb`` match
        the input blocks.  Only kernels with ``analytic_vjp = True`` provide
        this; everything else trains through autodiff of the fused program.
        """
        raise NotImplementedError(
            f"{self.name} has no hand-derived kfree VJP (analytic_vjp is "
            f"{self.analytic_vjp})"
        )

    def kernel_id(self) -> str:
        return self.name


@dataclasses.dataclass(frozen=True)
class SquaredExponential(Kernel):
    """The paper's kernel: k = v * exp(-d2 / (2 l))."""

    name: ClassVar[str] = "se"
    analytic_vjp: ClassVar[bool] = True

    def default_params(self) -> SEKernelParams:
        return SEKernelParams.paper_defaults()

    def kfree(self, params, xa, xb):
        return params.vertical * jnp.exp(-0.5 / params.lengthscale * sq_dists(xa, xb))

    def kfree_vjp(self, params, xa, xb, g):
        l, v = params.lengthscale, params.vertical
        d2 = sq_dists(xa, xb)
        gk = g * (v * jnp.exp(-0.5 / l * d2))
        g_l = jnp.sum(gk * d2) / (2.0 * l * l)
        g_v = jnp.sum(gk) / v
        # dk/d(d2) = -k / (2 l); d(d2)/dxa = 2 (xa - xb) rowwise
        w = -gk / (2.0 * l)
        g_xa = 2.0 * (jnp.sum(w, axis=1, keepdims=True) * xa - w @ xb)
        g_xb = 2.0 * (jnp.sum(w, axis=0)[:, None] * xb - w.T @ xa)
        return SEKernelParams(g_l, g_v, jnp.zeros_like(params.noise)), g_xa, g_xb


@dataclasses.dataclass(frozen=True)
class Matern12(Kernel):
    """Matérn nu=1/2 (exponential): k = v * exp(-r), r^2 = d2 / l."""

    name: ClassVar[str] = "matern12"

    def default_params(self) -> SEKernelParams:
        return SEKernelParams.paper_defaults()

    def kfree(self, params, xa, xb):
        r = _safe_sqrt(sq_dists(xa, xb) / params.lengthscale)
        return params.vertical * jnp.exp(-r)


@dataclasses.dataclass(frozen=True)
class Matern32(Kernel):
    """Matérn nu=3/2: k = v * (1 + sqrt(3) r) exp(-sqrt(3) r)."""

    name: ClassVar[str] = "matern32"

    def default_params(self) -> SEKernelParams:
        return SEKernelParams.paper_defaults()

    def kfree(self, params, xa, xb):
        s = math.sqrt(3.0) * _safe_sqrt(sq_dists(xa, xb) / params.lengthscale)
        return params.vertical * (1.0 + s) * jnp.exp(-s)


@dataclasses.dataclass(frozen=True)
class Matern52(Kernel):
    """Matérn nu=5/2: k = v * (1 + sqrt(5) r + 5 r^2 / 3) exp(-sqrt(5) r)."""

    name: ClassVar[str] = "matern52"
    analytic_vjp: ClassVar[bool] = True

    def default_params(self) -> SEKernelParams:
        return SEKernelParams.paper_defaults()

    def kfree(self, params, xa, xb):
        s = math.sqrt(5.0) * _safe_sqrt(sq_dists(xa, xb) / params.lengthscale)
        return params.vertical * (1.0 + s + s * s / 3.0) * jnp.exp(-s)

    def kfree_vjp(self, params, xa, xb, g):
        l, v = params.lengthscale, params.vertical
        s = math.sqrt(5.0) * _safe_sqrt(sq_dists(xa, xb) / l)
        e = jnp.exp(-s)
        g_v = jnp.sum(g * (1.0 + s + s * s / 3.0) * e)
        # dk/dl = v s^2 (1 + s) e^{-s} / (6 l)   (via ds/dl = -s / (2 l))
        g_l = jnp.sum(g * s * s * (1.0 + s) * e) * v / (6.0 * l)
        # dk/d(d2) = -(5 v / (6 l)) (1 + s) e^{-s} — the 1/s of ds/d(d2)
        # cancels against dk/ds ∝ s, so this is finite at d2 == 0.
        w = g * (-(5.0 * v / (6.0 * l)) * (1.0 + s) * e)
        g_xa = 2.0 * (jnp.sum(w, axis=1, keepdims=True) * xa - w @ xb)
        g_xb = 2.0 * (jnp.sum(w, axis=0)[:, None] * xb - w.T @ xa)
        return SEKernelParams(g_l, g_v, jnp.zeros_like(params.noise)), g_xa, g_xb


@dataclasses.dataclass(frozen=True)
class RationalQuadratic(Kernel):
    """RQ: k = v * (1 + d2 / (2 alpha l))^-alpha — an SE lengthscale mixture."""

    name: ClassVar[str] = "rq"

    def default_params(self) -> RQKernelParams:
        return RQKernelParams()

    def kfree(self, params, xa, xb):
        base = 1.0 + sq_dists(xa, xb) / (2.0 * params.alpha * params.lengthscale)
        # base >= 1 so the log is safe under tracing and in a Pallas body.
        return params.vertical * jnp.exp(-params.alpha * jnp.log(base))


def _ard_sq_dists(lengthscales, xa, xb):
    """sum_d (xa_d - xb_d)^2 / l_d: the squared distance of the ARD kernels."""
    ls = lengthscales
    if isinstance(ls, tuple):
        # host-baked Pallas body: a vector constant would be captured by
        # the kernel jaxpr (pallas_call rejects non-scalar consts), so
        # ``concrete_params`` hands lengthscales over as a float tuple
        # and the per-dim scalars inline as literals.
        if len(ls) == 1:  # shared lengthscale broadcasts over D dims
            ls = ls * xa.shape[1]
        d2 = None
        for d, l in enumerate(ls):
            diff = xa[:, d : d + 1] - jnp.transpose(xb[:, d : d + 1])
            term = diff * diff * (1.0 / l)
            d2 = term if d2 is None else d2 + term
        return d2
    ls = jnp.asarray(ls, dtype=xa.dtype)
    inv = 1.0 / jnp.sqrt(ls)  # scale features so sq_dists stays on the MXU
    return sq_dists(xa * inv, xb * inv)


@dataclasses.dataclass(frozen=True)
class _ARDKernel(Kernel):
    """A stationary family with one lengthscale per feature dimension."""

    ndim: int = 1

    def default_params(self) -> ARDKernelParams:
        return ARDKernelParams(lengthscales=jnp.ones((self.ndim,)))

    def base_ndims(self, params) -> ARDKernelParams:
        return ARDKernelParams(lengthscales=1, vertical=0, noise=0)

    def kernel_id(self) -> str:
        return f"{self.name}{self.ndim}"


@dataclasses.dataclass(frozen=True)
class ARDSquaredExponential(_ARDKernel):
    """SE with one lengthscale per feature dim: k = v * exp(-0.5 sum d_i^2/l_i)."""

    name: ClassVar[str] = "se_ard"

    def kfree(self, params, xa, xb):
        return params.vertical * jnp.exp(-0.5 * _ard_sq_dists(params.lengthscales, xa, xb))


@dataclasses.dataclass(frozen=True)
class ARDMatern52(_ARDKernel):
    """Matérn nu=5/2 with one lengthscale per feature dim (ARBO's residual
    model): k = v * (1 + sqrt(5) r + 5 r^2 / 3) exp(-sqrt(5) r), with
    r^2 = sum_d (x_d - x'_d)^2 / l_d."""

    name: ClassVar[str] = "matern52_ard"

    def kfree(self, params, xa, xb):
        s = math.sqrt(5.0) * _safe_sqrt(_ard_sq_dists(params.lengthscales, xa, xb))
        return params.vertical * (1.0 + s + s * s / 3.0) * jnp.exp(-s)


@dataclasses.dataclass(frozen=True)
class White(Kernel):
    """White observation noise: zero off-diagonal, ``noise`` on the diagonal.

    Use inside ``Sum`` to give a composite an explicit noise term (the
    ARBO-style ``C * Matern52 + White`` residual model).
    """

    name: ClassVar[str] = "white"

    def default_params(self) -> WhiteKernelParams:
        return WhiteKernelParams()

    def kfree(self, params, xa, xb):
        return jnp.zeros((xa.shape[0], xb.shape[0]), xa.dtype)

    def diag(self, params):
        return 0.0


@dataclasses.dataclass(frozen=True, init=False)
class Sum(Kernel):
    """k = sum of children; params is the tuple of child params pytrees."""

    children: tuple

    name: ClassVar[str] = "sum"

    def __init__(self, *children: Kernel):
        object.__setattr__(self, "children", tuple(children))

    def default_params(self) -> tuple:
        return tuple(c.default_params() for c in self.children)

    def kfree(self, params, xa, xb):
        parts = [c.kfree(p, xa, xb) for c, p in zip(self.children, params)]
        return sum(parts[1:], parts[0])

    def noise(self, params):
        return sum(c.noise(p) for c, p in zip(self.children, params))

    def diag(self, params):
        return sum(c.diag(p) for c, p in zip(self.children, params))

    def base_ndims(self, params) -> tuple:
        return tuple(c.base_ndims(p) for c, p in zip(self.children, params))

    def kernel_id(self) -> str:
        return "sum(" + ",".join(c.kernel_id() for c in self.children) + ")"


@dataclasses.dataclass(frozen=True, init=False)
class Product(Kernel):
    """k = product of children's noise-free parts; params a tuple of pytrees.

    Child ``noise`` leaves are *ignored* (a product of observation noises
    has no meaning); give the composite noise via ``Sum(..., White())`` or
    the top-level leaf of a child under ``Sum``.
    """

    children: tuple

    name: ClassVar[str] = "product"

    def __init__(self, *children: Kernel):
        object.__setattr__(self, "children", tuple(children))

    def default_params(self) -> tuple:
        return tuple(c.default_params() for c in self.children)

    def kfree(self, params, xa, xb):
        out = self.children[0].kfree(params[0], xa, xb)
        for c, p in zip(self.children[1:], params[1:]):
            out = out * c.kfree(p, xa, xb)
        return out

    def noise(self, params):
        return 0.0

    def diag(self, params):
        out = self.children[0].diag(params[0])
        for c, p in zip(self.children[1:], params[1:]):
            out = out * c.diag(p)
        return out

    def base_ndims(self, params) -> tuple:
        return tuple(c.base_ndims(p) for c, p in zip(self.children, params))

    def kernel_id(self) -> str:
        return "prod(" + ",".join(c.kernel_id() for c in self.children) + ")"


@dataclasses.dataclass(frozen=True)
class Scaled(Kernel):
    """k = scale * child (scale multiplies kfree, diag AND the child's noise)."""

    inner: Kernel

    name: ClassVar[str] = "scaled"

    def default_params(self) -> ScaledParams:
        return ScaledParams(scale=1.0, inner=self.inner.default_params())

    def kfree(self, params, xa, xb):
        return params.scale * self.inner.kfree(params.inner, xa, xb)

    def noise(self, params):
        return params.scale * self.inner.noise(params.inner)

    def diag(self, params):
        return params.scale * self.inner.diag(params.inner)

    def base_ndims(self, params) -> ScaledParams:
        return ScaledParams(scale=0, inner=self.inner.base_ndims(params.inner))

    def kernel_id(self) -> str:
        return f"scaled({self.inner.kernel_id()})"


SQUARED_EXPONENTIAL = SquaredExponential()  # the default kernel everywhere

KERNEL_REGISTRY: dict[str, Callable[..., Kernel]] = {}


def register_kernel(name: str, factory: Callable[..., Kernel]) -> None:
    """Register a kernel factory under ``name`` (``get_kernel`` resolves it)."""
    KERNEL_REGISTRY[name] = factory


def get_kernel(name: str, **kwargs) -> Kernel:
    """Instantiate a registered kernel by name (e.g. ``get_kernel("matern32")``)."""
    try:
        factory = KERNEL_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(KERNEL_REGISTRY)}"
        ) from None
    return factory(**kwargs)


for _cls in (
    SquaredExponential,
    Matern12,
    Matern32,
    Matern52,
    RationalQuadratic,
    ARDSquaredExponential,
    ARDMatern52,
    White,
):
    register_kernel(_cls.name, _cls)


def resolve_kernel(kernel) -> Kernel:
    """None -> the SE default; a registry name -> its instance; else as-is."""
    if kernel is None:
        return SQUARED_EXPONENTIAL
    if isinstance(kernel, str):
        return get_kernel(kernel)
    return kernel


# ---------------------------------------------------------------------------
# Params-pytree utilities (concreteness, batching, bucketing)
# ---------------------------------------------------------------------------


def params_concrete(params) -> bool:
    """True iff every hyperparameter leaf is concrete (not traced).

    The Pallas assembly kernels bake hyperparameters in as compile-time
    constants, which is impossible inside a gradient trace; callers use this
    to fall back to the differentiable jnp assembly tile (DESIGN.md §8).
    """
    try:
        for leaf in jax.tree_util.tree_leaves(params):
            np.asarray(leaf)
        return True
    except (TypeError, jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return False


def concrete_params(params):
    """Params pytree as host constants for Pallas baking.

    Scalars become Python floats (inlined as jaxpr literals); vector leaves
    (ARD lengthscales) become float *tuples* — a np/jnp array constant inside
    a Pallas kernel body would be captured by its jaxpr, which ``pallas_call``
    rejects, so vector-aware kernels (``ARDSquaredExponential.kfree``) unroll
    tuple leaves dimension by dimension with scalar literals instead.
    """
    def conv(leaf):
        a = np.asarray(leaf)
        return float(a) if a.ndim == 0 else tuple(float(v) for v in a.ravel())
    return jax.tree_util.tree_map(conv, params)


def _base_ndims_of(params, kernel: Optional[Kernel]):
    if kernel is None:
        return jax.tree_util.tree_map(lambda _: 0, params)
    return resolve_kernel(kernel).base_ndims(params)


def params_per_problem(params, kernel: Optional[Kernel] = None) -> bool:
    """True iff any hyperparameter leaf carries a problem-batch axis (B, ...)."""
    base = _base_ndims_of(params, kernel)
    flags = jax.tree_util.tree_map(
        lambda leaf, nd: jnp.ndim(leaf) > nd, params, base
    )
    return any(jax.tree_util.tree_leaves(flags))


def broadcast_params(params, b: int, kernel: Optional[Kernel] = None):
    """Broadcast every hyperparameter leaf to per-problem shape (B, ...).

    Mixed leaves are legal inputs (e.g. per-problem lengthscales with a
    shared noise); this normalizes them for code that vmaps over the
    problem axis (DESIGN.md §9).  A ``tree_map`` over the params pytree, so
    it works for every registered kernel — ARD vectors gain a leading (B,)
    axis on top of their (D,) base shape.
    """
    base = _base_ndims_of(params, kernel)

    def bcast(leaf, nd):
        leaf = jnp.asarray(leaf)
        if leaf.ndim == nd:
            return jnp.broadcast_to(leaf, (b,) + leaf.shape)
        if leaf.ndim == nd + 1:
            return jnp.broadcast_to(leaf, (b,) + leaf.shape[1:])
        raise ValueError(
            f"hyperparameter leaf of rank {leaf.ndim} is neither shared "
            f"(rank {nd}) nor per-problem (rank {nd + 1})"
        )

    return jax.tree_util.tree_map(bcast, params, base)


def gather_params(params, idx, kernel: Optional[Kernel] = None):
    """Gather per-problem leaves at ``idx``; shared leaves pass through.

    The fleet-bucketing primitive (GPFleet): shared hyperparameters stay
    scalars (one trace serves every bucket) while per-problem leaves are
    gathered into the bucket's (B_bucket, ...) rows.
    """
    base = _base_ndims_of(params, kernel)
    idx = jnp.asarray(idx)

    def gather(leaf, nd):
        leaf = jnp.asarray(leaf)
        return leaf if leaf.ndim == nd else leaf[idx]

    return jax.tree_util.tree_map(gather, params, base)


# ---------------------------------------------------------------------------
# Dense reference assembly (monolithic; the tiled pipeline's ground truth)
# ---------------------------------------------------------------------------


def se_kernel(
    x1: jax.Array,
    x2: jax.Array,
    params: SEKernelParams,
    *,
    diag_offset: Optional[int] = None,
) -> jax.Array:
    """Dense SE covariance block between x1 (n1,D) and x2 (n2,D).

    If ``diag_offset`` is not None, the entry (i, j) with
    ``i + diag_offset == j`` receives the ``+ sigma^2`` noise term, i.e. the
    block lies on the global diagonal with the given column offset.  For the
    full training matrix use ``diag_offset=0``.
    """
    d2 = sq_dists(x1, x2)
    k = params.vertical * jnp.exp(-0.5 / params.lengthscale * d2)
    if diag_offset is not None:
        i = jnp.arange(x1.shape[0])[:, None]
        j = jnp.arange(x2.shape[0])[None, :]
        k = k + jnp.where(i + diag_offset == j, params.noise, 0.0).astype(k.dtype)
    return k


def cov_tile(
    xa: jax.Array,
    xb: jax.Array,
    row0,
    col0,
    params,
    n_valid_r,
    n_valid_c,
    symmetric: bool,
    kernel: Optional[Kernel] = None,
) -> jax.Array:
    """One covariance tile with global-index masking (vmap-friendly).

    xa: (m, D) rows, xb: (mb, D) cols; row0/col0 global offsets (traced or
    static scalars).  Padded region -> identity (symmetric) or zero (cross).
    Symmetric tiles pin the global diagonal to the *exact*
    ``kernel.diag + kernel.noise`` — the expanded-form squared distances
    cancel catastrophically in f32 for large-magnitude inputs, so the
    diagonal is never computed through them.  This is the jnp analogue of
    the Pallas cov-assembly kernel (repro.kernels.cov_assembly) and the
    per-task op behind the ASSEMBLE / CROSS / PRIOR program tasks.
    """
    kernel = resolve_kernel(kernel)
    k = kernel.kfree(params, xa, xb)
    gi = row0 + jnp.arange(xa.shape[0])[:, None]
    gj = col0 + jnp.arange(xb.shape[0])[None, :]
    on_diag = gi == gj
    valid = (gi < n_valid_r) & (gj < n_valid_c)
    if symmetric:
        diag_val = jnp.asarray(kernel.diag(params) + kernel.noise(params))
        k = jnp.where(on_diag, diag_val.astype(k.dtype), k)
        return jnp.where(valid, k, on_diag.astype(k.dtype))
    return jnp.where(valid, k, jnp.zeros((), k.dtype))


def assemble_covariance(
    x: jax.Array,
    params,
    *,
    kernel: Optional[Kernel] = None,
    n_valid: Optional[int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Full training covariance K = K_XX + sigma^2 I, optionally padded.

    x: (n_pad, D) where rows >= n_valid are padding (any values).  The padded
    region is overwritten: identity on the diagonal, zero elsewhere.  The
    valid diagonal is pinned to the exact ``diag + noise`` (same contract as
    the tiled assembly — see :func:`cov_tile`).
    """
    kernel = resolve_kernel(kernel)
    x = x.astype(dtype)
    k = kernel.kfree(params, x, x).astype(dtype)
    n_pad = x.shape[0]
    i = jnp.arange(n_pad)[:, None]
    j = jnp.arange(n_pad)[None, :]
    diag_val = jnp.asarray(kernel.diag(params) + kernel.noise(params))
    k = jnp.where(i == j, diag_val.astype(dtype), k)
    if n_valid is not None and n_valid != n_pad:
        valid = (i < n_valid) & (j < n_valid)
        eye = (i == j).astype(dtype)
        k = jnp.where(valid, k, eye)
    return k


def assemble_cross_covariance(
    x_test: jax.Array,
    x_train: jax.Array,
    params,
    *,
    kernel: Optional[Kernel] = None,
    n_test_valid: Optional[int] = None,
    n_train_valid: Optional[int] = None,
    dtype=jnp.float32,
) -> jax.Array:
    """Cross covariance K_{X̂,X} (n̂_pad × n_pad), padded region zeroed."""
    kernel = resolve_kernel(kernel)
    k = kernel.kfree(
        params, x_test.astype(dtype), x_train.astype(dtype)
    ).astype(dtype)
    nt, ntr = k.shape
    if (n_test_valid is not None and n_test_valid != nt) or (
        n_train_valid is not None and n_train_valid != ntr
    ):
        i = jnp.arange(nt)[:, None]
        j = jnp.arange(ntr)[None, :]
        valid = jnp.ones((nt, ntr), dtype=bool)
        if n_test_valid is not None:
            valid &= i < n_test_valid
        if n_train_valid is not None:
            valid &= j < n_train_valid
        k = jnp.where(valid, k, 0.0)
    return k


def assemble_prior_covariance(
    x_test: jax.Array,
    params,
    *,
    kernel: Optional[Kernel] = None,
    n_valid: Optional[int] = None,
    include_noise: bool = False,
    dtype=jnp.float32,
) -> jax.Array:
    """Prior test covariance K_{X̂,X̂}; optionally with observation noise."""
    kernel = resolve_kernel(kernel)
    xt = x_test.astype(dtype)
    k = kernel.kfree(params, xt, xt).astype(dtype)
    n_pad = k.shape[0]
    i = jnp.arange(n_pad)[:, None]
    j = jnp.arange(n_pad)[None, :]
    if include_noise:
        k = k + jnp.where(
            i == j, jnp.asarray(kernel.noise(params)), 0.0
        ).astype(dtype)
    if n_valid is not None and n_valid != n_pad:
        valid = (i < n_valid) & (j < n_valid)
        k = jnp.where(valid, k, 0.0)
    return k
