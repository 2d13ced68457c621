"""Public wrapper for the masked CSR frontier gather."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.errors import require_compiled
from repro.kernels.frontier_gather.kernel import frontier_gather_pallas
from repro.kernels.frontier_gather.ref import frontier_gather_ref

_INVALID = np.int32(2**31 - 1)  # numpy: safe to create at import time under a trace


def frontier_gather(
    indptr: jax.Array,   # (V+1,) int32
    indices: jax.Array,  # (E,) int32
    seeds: jax.Array,    # (n,) int32, INVALID padded
    max_degree: int,
    *,
    block_n: int = 256,
    page: int = 2048,
) -> tuple[jax.Array, jax.Array]:
    """(nbr (n, max_degree), mask) — bit-identical to the jnp oracle.

    Dispatches to the paged Pallas sweep on TPU (which raises
    :class:`~repro.kernels.errors.KernelUnavailableError` until the
    kernel compiles there), to the reference elsewhere.  Seeds pad with
    INVALID (contributing all-masked rows) and indices pad freely
    (padded edges are never inside any valid ``[offs, offs+deg)`` row
    slice), so blocking cannot perturb output.
    """
    if jax.default_backend() != "tpu":
        return frontier_gather_ref(indptr, indices, seeds, max_degree)
    require_compiled("frontier_gather")
    (n,) = seeds.shape
    (E,) = indices.shape
    pad_n = (-n) % block_n
    pad_e = (-E) % page
    seeds_p = jnp.pad(seeds, (0, pad_n), constant_values=_INVALID)
    ind_p = jnp.pad(indices, (0, pad_e), constant_values=_INVALID)
    nbr = frontier_gather_pallas(
        indptr, ind_p, seeds_p,
        max_degree=max_degree, block_n=block_n, page=page,
    )[:n]
    mask = nbr != _INVALID
    return nbr, mask
