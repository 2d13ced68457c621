"""Typed contract errors for the Pallas kernel layer.

Kernel preconditions used to be bare ``assert`` statements — invisible
under ``python -O`` and silent about *which* shape broke *which* block
constraint.  :class:`KernelContractError` carries the kernel name and
the offending (dimension, value, divisor) triples so a violation names
its fix, and ``repro.analysis`` rule RA005 enforces this style.

:class:`KernelUnavailableError` is the TPU counterpart: an ``ops.py``
wrapper on the TPU either runs its compiled kernel or raises it, naming
the kernel — it never runs the jnp oracle in the kernel's place.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax

#: (dimension name, dimension value, divisor name, divisor value)
Constraint = Tuple[str, int, str, int]


class KernelContractError(ValueError):
    """A Pallas kernel was called with shapes violating its contract."""

    def __init__(self, kernel: str, message: str, values: dict = None):
        self.kernel = kernel
        self.values = dict(values or {})
        detail = ""
        if self.values:
            detail = " (" + ", ".join(
                f"{k}={v}" for k, v in self.values.items()
            ) + ")"
        super().__init__(f"{kernel}: {message}{detail}")


def require_divisible(kernel: str, constraints: Sequence[Constraint]) -> None:
    """Raise :class:`KernelContractError` listing every violated triple.

    Each constraint is ``(dim_name, dim_value, divisor_name, divisor)``
    requiring ``dim_value % divisor == 0``.  All violations are reported
    at once so a caller fixing padding sees the full contract.
    """
    bad = [
        (dn, dv, bn, bv)
        for dn, dv, bn, bv in constraints
        if bv <= 0 or dv % bv != 0
    ]
    if bad:
        values = {}
        for dn, dv, bn, bv in bad:
            values[dn] = int(dv)
            values[bn] = int(bv)
        names = " and ".join(f"{dn} % {bn} != 0" for dn, dv, bn, bv in bad)
        raise KernelContractError(
            kernel,
            f"block divisibility violated: {names}; pad inputs to block "
            "multiples (see kernels/<name>/ops.py for the padding wrapper)",
            values,
        )


#: Kernels that Mosaic refuses to compile for TPU v5e, with the
#: compiler's message (``tests/test_tpu_compile.py`` keeps each entry
#: honest: it expects these compiles to fail, strictly).  Each kernel
#: gathers rows inside the kernel body, which Mosaic does not lower.
UNCOMPILED_ON_TPU = {
    "frontier_gather": "NotImplementedError: Only 2D gather is supported",
    "paged_gather": "ValueError: Shape mismatch in input, indices and output",
    "spmm": "ValueError: Shape mismatch in input, indices and output",
    "tag_probe": "ValueError: Shape mismatch in input, indices and output",
}


class KernelUnavailableError(RuntimeError):
    """A kernel that does not compile for the TPU was asked to run there."""

    def __init__(self, kernel: str, reason: str):
        self.kernel = kernel
        super().__init__(
            f"{kernel}: the Pallas kernel does not compile for the TPU "
            f"({reason}); its jnp oracle is not substituted on the TPU"
        )


def require_compiled(kernel: str) -> None:
    """Raise :class:`KernelUnavailableError` when the default backend is
    the TPU and ``kernel`` is in :data:`UNCOMPILED_ON_TPU`."""
    if kernel in UNCOMPILED_ON_TPU and jax.default_backend() == "tpu":
        raise KernelUnavailableError(kernel, UNCOMPILED_ON_TPU[kernel])
