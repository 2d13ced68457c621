"""Pallas TPU kernel: dedup + rank resolution of a sorted padded frontier.

Plan construction spends its hot loop deduplicating the concatenated
frontier ``cat = [S^l | sampled neighbors]`` and resolving every element
into the next frontier ``S^{l+1}``.  The sort itself stays in XLA (TPU
sort networks are already optimal there); this kernel fuses the
first-occurrence flags and the global unique ranks into ONE sequential
sweep over the sorted ids, laid out as ``(m / 128, 128)`` lane-dense
rows:

    grid = (m / block_m,)        -- sequential on TPU

Each step consumes one ``(block_m / 128, 128)`` block in row-major order
and carries two scalars across grid steps in SMEM scratch — the running
unique count and the previous block's last element.  An element is a
first occurrence iff it differs from its row-major predecessor (lane
and sublane rolls); its rank is the running count plus an inclusive
prefix sum of the flags (log-step roll scans along lanes, then rows).
All masks stay int32 0/1: Mosaic cannot truncate i8 vectors to i1.

The kernel emits ``inv``: each element's rank in the unique set, masked
to -1 for INVALID ids and for ranks beyond ``cap`` (the
keep-smallest-``cap`` overflow policy of ``frontier.unique_padded``).
The compacted ``uniq`` is one XLA scatter of the sorted ids to their
ranks; duplicates carry equal values, so the scatter is exact.

Replaces a ``jnp.unique`` + two ``searchsorted`` lookups per layer with
one fused pass over already-sorted data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.errors import KernelContractError, require_divisible

_INVALID = np.int32(2**31 - 1)
_LANES = 128


def _inclusive_scan(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive prefix sum along ``axis`` by log-step rolls (int32)."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k = 1
    while k < n:
        x = x + jnp.where(pos >= k, pltpu.roll(x, k, axis), 0)
        k *= 2
    return x


def _unique_kernel(s_ref, inv_ref, carry_ref, *, cap: int):
    i = pl.program_id(0)
    s = s_ref[...]                                     # (rows, 128) sorted

    @pl.when(i == 0)
    def _reset_carry():
        carry_ref[0] = 0                               # uniques seen so far
        carry_ref[1] = 0                               # previous last element

    base = carry_ref[0]
    prev = carry_ref[1]

    # row-major predecessor: lane c-1, or lane 127 of the row above for
    # c == 0, or the carried last element of the previous block
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    left = pltpu.roll(s, 1, 1)                         # s[r, c-1] (c > 0)
    up = pltpu.roll(left, 1, 0)                        # s[r-1, 127] at c == 0
    pred = jnp.where(col == 0, up, left)
    head = (row == 0) & (col == 0)
    first = jnp.where(
        head,
        jnp.where((i == 0) | (s != prev), 1, 0),
        jnp.where(s != pred, 1, 0),
    ).astype(jnp.int32)                                # (rows, 128) 0/1

    # global rank: inclusive prefix along lanes, plus the exclusive
    # prefix of the row totals down the sublanes
    in_row = _inclusive_scan(first, 1)
    totals = jnp.broadcast_to(in_row[:, _LANES - 1:], s.shape)
    rows_before = _inclusive_scan(totals, 0) - totals
    rank = base + rows_before + in_row - 1

    inv_ref[...] = jnp.where((rank < cap) & (s != _INVALID), rank, -1)

    carry_ref[0] = base + jnp.sum(first)
    carry_ref[1] = jnp.max(s)                         # sorted: last element


@functools.partial(
    jax.jit, static_argnames=("cap", "block_m", "interpret")
)
def unique_compact_pallas(
    sorted_ids: jax.Array,  # (m,) int32 ASCENDING, m % block_m == 0
    cap: int,
    *,
    block_m: int = 4096,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(inv (m,), uniq (cap,)) — see module docstring for semantics.

    ``block_m`` is a multiple of 128; on the TPU, of 1024 (whole
    ``(8, 128)`` tiles) unless one block spans all of ``m``.
    """
    (m,) = sorted_ids.shape
    require_divisible("unique_compact_pallas", [
        ("m", m, "block_m", block_m),
        ("block_m", block_m, "lanes", _LANES),
    ])
    if cap < 1:
        raise KernelContractError(
            "unique_compact_pallas", "cap must be >= 1", {"cap": cap}
        )
    rows = block_m // _LANES
    inv = pl.pallas_call(
        functools.partial(_unique_kernel, cap=cap),
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m // _LANES, _LANES), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        interpret=interpret,
    )(sorted_ids.reshape(m // _LANES, _LANES)).reshape(m)
    # ranks >= cap and INVALID ids carry -1: route them past the end
    uniq = jnp.full((cap,), _INVALID, jnp.int32).at[
        jnp.where(inv >= 0, inv, cap)
    ].set(sorted_ids, mode="drop")
    return inv, uniq
