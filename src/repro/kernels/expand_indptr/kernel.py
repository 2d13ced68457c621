"""Pallas TPU kernel: CSR indptr -> per-edge-slot row ids.

Plan-local subgraph assembly (``layer_to_coo``) needs COO row ids for a
capacity-padded edge buffer.  The row of edge slot ``e`` is the number
of indptr entries ``<= e``, minus one — computed here per block via a
``(block_e, R+1)`` comparison matrix against the VMEM-resident indptr
(at most cap+1 int32s).  Each output tile is visited exactly once, so
no cross-step combine is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.errors import require_divisible


def _expand_kernel(iptr_ref, row_ref, *, block_e: int):
    i = pl.program_id(0)
    iptr = iptr_ref[...]                               # (R+1,)
    pos = jax.lax.broadcasted_iota(jnp.int32, (block_e, 1), 0)[:, 0]
    e = i * block_e + pos                              # global slot ids
    cnt = jnp.sum(iptr[None, :] <= e[:, None], axis=1).astype(jnp.int32)
    row = cnt - 1
    total = iptr[iptr.shape[0] - 1]
    row_ref[...] = jnp.where(e < total, row, -1)


@functools.partial(jax.jit, static_argnames=("num_edges", "block_e", "interpret"))
def expand_indptr_pallas(
    indptr: jax.Array,  # (R+1,) int32 ascending
    num_edges: int,     # output length, % block_e == 0
    *,
    block_e: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """(num_edges,) int32 row ids, -1 at or beyond indptr[-1]."""
    require_divisible("expand_indptr_pallas", [
        ("num_edges", num_edges, "block_e", block_e),
    ])
    R1 = indptr.shape[0]
    return pl.pallas_call(
        functools.partial(_expand_kernel, block_e=block_e),
        grid=(num_edges // block_e,),
        in_specs=[pl.BlockSpec((R1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_e,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((num_edges,), jnp.int32),
        interpret=interpret,
    )(indptr)
