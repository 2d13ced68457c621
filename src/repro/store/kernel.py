"""Pallas TPU kernel: batched set-associative tag probe (cache lookup).

The device CLOCK cache (`repro.store.clock`) resolves a batch of vertex
ids against a tag array ``tags[set, way]`` in one shot: for each id we
need the way whose tag equals it, or -1 on a miss.  Random row access
into the tag array is the same DMA-hostile pattern as the embedding
gather, so the kernel reuses the paged-sweep structure of
``repro.kernels.gather``:

    grid = (id blocks, tag pages)

Each step holds one ``(page, W)`` tag tile in VMEM; ids whose set index
falls inside the current page are resolved there, and results combine
across pages with ``max`` (a miss is -1 everywhere; the owning page
contributes the only way >= 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.errors import require_compiled, require_divisible


def probe_ref(tags: jax.Array, sets: jax.Array, ids: jax.Array) -> jax.Array:
    """Pure-jnp oracle: way of ``ids[i]`` in ``tags[sets[i]]``, -1 on miss.

    Callers must pre-mask padding ids to a value that can never appear
    as a tag (the CLOCK layer uses -1; tags hold vertex ids >= 0 or the
    INVALID empty sentinel).
    """
    rows = tags[sets]                               # (n, W)
    eq = rows == ids[:, None]
    return jnp.where(eq.any(1), jnp.argmax(eq, 1), -1).astype(jnp.int32)


def _probe_kernel(sets_ref, ids_ref, tags_ref, out_ref, *, page: int):
    p = pl.program_id(1)
    sets = sets_ref[...]                            # (bn,)
    ids = ids_ref[...]                              # (bn,)
    tab = tags_ref[...]                             # (page, W)
    local = sets - p * page
    inpage = (local >= 0) & (local < page)
    rows = tab[jnp.clip(local, 0, page - 1)]        # (bn, W)
    eq = rows == ids[:, None]
    way = jnp.where(
        eq.any(1) & inpage, jnp.argmax(eq, axis=1), -1
    ).astype(jnp.int32)

    @pl.when(p == 0)
    def _init():
        out_ref[...] = way

    @pl.when(p != 0)
    def _acc():
        out_ref[...] = jnp.maximum(out_ref[...], way)


@functools.partial(jax.jit, static_argnames=("block_n", "page", "interpret"))
def tag_probe_pallas(
    tags: jax.Array,   # (S, W) int32, S % page == 0
    sets: jax.Array,   # (n,) int32 set index per id, n % block_n == 0
    ids: jax.Array,    # (n,) int32 probe ids (padding pre-masked to -1)
    *,
    block_n: int = 512,
    page: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    S, W = tags.shape
    (n,) = ids.shape
    require_divisible("tag_probe_pallas", [
        ("S", S, "page", page),
        ("n", n, "block_n", block_n),
    ])
    if sets.shape != (n,):
        raise ValueError(f"sets shape {sets.shape} != ids shape {(n,)}")
    grid = (n // block_n, S // page)
    return pl.pallas_call(
        functools.partial(_probe_kernel, page=page),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda i, p: (i,)),
            pl.BlockSpec((block_n,), lambda i, p: (i,)),
            pl.BlockSpec((page, W), lambda i, p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i, p: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(sets, ids, tags)


def tag_probe(
    tags: jax.Array,
    sets: jax.Array,
    ids: jax.Array,
    *,
    block_n: int = 512,
    page: int = 1024,
) -> jax.Array:
    """Batched cache-tag probe; dispatches to the kernel on TPU, which
    raises :class:`~repro.kernels.errors.KernelUnavailableError` until
    the kernel compiles there.  The CLOCK cache calls :func:`probe_ref`
    on every backend."""
    if jax.default_backend() != "tpu":
        return probe_ref(tags, sets, ids)
    require_compiled("tag_probe")
    S, W = tags.shape
    (n,) = ids.shape
    pad_s = (-S) % page
    pad_n = (-n) % block_n
    tags_p = jnp.pad(tags, ((0, pad_s), (0, 0)), constant_values=jnp.int32(-2))
    sets_p = jnp.pad(sets, (0, pad_n))
    ids_p = jnp.pad(ids, (0, pad_n), constant_values=jnp.int32(-1))
    out = tag_probe_pallas(tags_p, sets_p, ids_p, block_n=block_n, page=page)
    return out[:n]
