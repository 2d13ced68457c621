"""Public wrapper for CSR indptr expansion."""
from __future__ import annotations

import jax

from repro.kernels.expand_indptr.kernel import expand_indptr_pallas
from repro.kernels.expand_indptr.ref import expand_indptr_ref


def expand_indptr(
    indptr: jax.Array,
    num_edges: int,
    *,
    block_e: int = 1024,
) -> jax.Array:
    """(num_edges,) int32 row id per edge slot, -1 past indptr[-1].

    Dispatches to the Pallas kernel on TPU, to the searchsorted oracle
    elsewhere.  The kernel runs on ``num_edges`` rounded up to a block
    multiple; slots past ``num_edges`` are sliced off (each slot's row
    depends only on ``indptr``, so padding cannot perturb the rest).
    ``block_e`` is a multiple of 1024, the TPU's 1-D int32 tile.
    """
    if jax.default_backend() != "tpu":
        return expand_indptr_ref(indptr, num_edges)
    padded = num_edges + (-num_edges) % block_e
    return expand_indptr_pallas(indptr, padded, block_e=block_e)[:num_edges]
