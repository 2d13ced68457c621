"""Public wrappers for the fused unique-and-compact frontier op."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.unique_compact.kernel import unique_compact_pallas
from repro.kernels.unique_compact.ref import unique_with_inverse_ref

_INVALID = np.int32(2**31 - 1)  # numpy: safe to create at import time under a trace


def unique_with_inverse(
    ids: jax.Array,
    cap: int,
    *,
    block_m: int = 4096,
) -> tuple[jax.Array, jax.Array]:
    """(uniq (cap,), inv (m,)) of a flat int32 id vector.

    ``uniq`` is bit-identical to ``frontier.unique_padded(ids, cap)`` and
    ``inv`` to ``frontier.lookup(uniq, ids)``; INVALID-padding appended
    for blocking cannot perturb either (INVALID sorts last and maps to
    -1).  Dispatches to the Pallas sweep on TPU, to the pure-jnp fused
    oracle elsewhere.
    """
    flat = ids.reshape(-1)
    if jax.default_backend() != "tpu":
        return unique_with_inverse_ref(flat, cap)
    m = flat.shape[0]
    pad = (-m) % block_m
    flat_p = jnp.pad(flat, (0, pad), constant_values=_INVALID)
    order = jnp.argsort(flat_p)
    s = flat_p[order]
    inv_sorted, uniq = unique_compact_pallas(s, cap, block_m=block_m)
    inv = jnp.zeros((m + pad,), jnp.int32).at[order].set(inv_sorted)
    return uniq, inv[:m]


def unique_compact(ids: jax.Array, cap: int, *, block_m: int = 4096) -> jax.Array:
    """Sorted unique ids with INVALID padding (fused unique only)."""
    uniq, _ = unique_with_inverse(ids, cap, block_m=block_m)
    return uniq
