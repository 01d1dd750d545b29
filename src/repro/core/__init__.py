"""Core: the paper's contribution — tiled, device-resident GP regression."""

from repro.core.gp import GaussianProcess, GPBatch, GPFleet
from repro.core.kernels_math import (
    ARDKernelParams,
    ARDMatern52,
    ARDSquaredExponential,
    Kernel,
    Matern12,
    Matern32,
    Matern52,
    Product,
    RationalQuadratic,
    RQKernelParams,
    Scaled,
    ScaledParams,
    SEKernelParams,
    SquaredExponential,
    Sum,
    White,
    WhiteKernelParams,
    get_kernel,
    register_kernel,
    resolve_kernel,
)
from repro.core.update import CholeskyUpdateError

__all__ = [
    "GaussianProcess",
    "GPBatch",
    "GPFleet",
    "CholeskyUpdateError",
    # kernel zoo (DESIGN.md §13)
    "Kernel",
    "SquaredExponential",
    "Matern12",
    "Matern32",
    "Matern52",
    "RationalQuadratic",
    "ARDSquaredExponential",
    "ARDMatern52",
    "White",
    "Sum",
    "Product",
    "Scaled",
    "get_kernel",
    "register_kernel",
    "resolve_kernel",
    # params pytrees
    "SEKernelParams",
    "RQKernelParams",
    "ARDKernelParams",
    "WhiteKernelParams",
    "ScaledParams",
]
