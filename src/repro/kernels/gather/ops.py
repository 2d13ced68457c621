"""Public wrapper for the paged gather kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.errors import require_compiled
from repro.kernels.gather.kernel import paged_gather_pallas
from repro.kernels.gather.ref import gather_ref


def paged_gather(
    table: jax.Array,
    ids: jax.Array,
    *,
    block_n: int = 512,
    block_d: int = 128,
    page: int = 2048,
) -> jax.Array:
    """Masked embedding gather; INVALID / out-of-range ids produce zeros.

    Raises :class:`~repro.kernels.errors.KernelUnavailableError` on the
    TPU until the kernel compiles there.
    """
    if jax.default_backend() != "tpu":
        return gather_ref(table, ids)
    require_compiled("paged_gather")
    V, d = table.shape
    n = ids.shape[0]
    pad_v = (-V) % page
    pad_d = (-d) % block_d
    pad_n = (-n) % block_n
    table_p = jnp.pad(table, ((0, pad_v), (0, pad_d)))
    ids_p = jnp.pad(ids, (0, pad_n), constant_values=jnp.int32(2**31 - 1))
    out = paged_gather_pallas(
        table_p, ids_p, block_n=block_n, block_d=block_d, page=page
    )
    return out[:n, :d]
