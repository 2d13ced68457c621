"""Public jit'd wrappers for the SpMM kernel (padding + dispatch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.errors import require_compiled
from repro.kernels.spmm.kernel import spmm_pallas
from repro.kernels.spmm.ref import spmm_ref


def _pad_axis(x, axis, mult, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _spmm(src, nbr_idx, mask, mean: bool, block_n=128, block_d=128):
    """Pad to block multiples, run the kernel, slice back.

    Raises :class:`~repro.kernels.errors.KernelUnavailableError` on the
    TPU until the kernel compiles there.
    """
    if jax.default_backend() != "tpu":
        # CPU/GPU: interpret-mode Pallas is the correctness path but slow;
        # production non-TPU backends use the jnp oracle (same math).
        return spmm_ref(src, nbr_idx, mask, mean=mean)
    require_compiled("spmm")
    n, d = nbr_idx.shape[0], src.shape[1]
    src_p = _pad_axis(src, 1, block_d)
    idx_p = _pad_axis(nbr_idx, 0, block_n, value=-1)
    mask_p = _pad_axis(mask, 0, block_n, value=False)
    out = spmm_pallas(
        src_p, idx_p, mask_p, mean=mean, block_n=block_n, block_d=block_d
    )
    return out[:n, :d]


def spmm_mean(src, nbr_idx, mask, **kw):
    """Masked mean aggregation over sampled neighbors."""
    return _spmm(src, nbr_idx, mask, mean=True, **kw)


def spmm_sum(src, nbr_idx, mask, **kw):
    """Masked sum aggregation over sampled neighbors."""
    return _spmm(src, nbr_idx, mask, mean=False, **kw)
