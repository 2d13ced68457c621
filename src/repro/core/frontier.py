"""Padded, static-capacity vertex-set operations.

JAX/TPU cannot lower dynamic-size frontiers, so every expansion set
``S^l`` is a fixed-capacity int32 vector padded with ``INVALID`` and kept
*sorted* (valid ids first, then padding — INVALID is int32 max so a plain
sort yields this layout).  The plan builders' dedup-and-rank
(:func:`unique_with_inverse`) is one key-value sort, a cumulative sum and
two scatters, with no search; :func:`lookup` (a binary search, a while
loop on the TPU) and :func:`contains` stay as its independent test
oracle.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.graph import INVALID


def pad_to(ids: jax.Array, cap: int) -> jax.Array:
    """Pad / truncate a 1-D id vector to capacity ``cap``."""
    n = ids.shape[0]
    if n >= cap:
        return ids[:cap]
    return jnp.concatenate([ids, jnp.full((cap - n,), INVALID, ids.dtype)])


@partial(jax.jit, static_argnums=(1,))
def unique_padded(ids: jax.Array, cap: int) -> jax.Array:
    """Sorted unique ids with INVALID padding, capacity ``cap``.

    Overflow policy: if the true unique count exceeds ``cap`` the smallest
    ``cap`` ids are kept (deterministic; callers size capacities from
    fanout budgets so this only triggers under adversarial inputs).
    """
    flat = ids.reshape(-1)
    return jnp.unique(flat, size=cap, fill_value=INVALID)


@partial(jax.jit, static_argnums=(2,))
def union_padded(a: jax.Array, b: jax.Array, cap: int) -> jax.Array:
    return unique_padded(jnp.concatenate([a.reshape(-1), b.reshape(-1)]), cap)


PLAN_BACKENDS = ("reference", "fused")


def _check_backend(backend: str) -> None:
    if backend not in PLAN_BACKENDS:
        raise ValueError(
            f"unknown plan backend {backend!r}; expected one of {PLAN_BACKENDS}"
        )


def unique_with_inverse(
    ids: jax.Array, cap: int, backend: str = "reference"
) -> tuple[jax.Array, jax.Array]:
    """(uniq (cap,), inv (m,)): dedup + rank of every id in the result.

    ``uniq`` equals :func:`unique_padded` and ``inv`` equals
    :func:`lookup` of the flattened input against it — both backends are
    bit-identical.  ``"reference"`` is :func:`sort_unique_with_inverse`;
    ``"fused"`` routes through the :mod:`repro.kernels.unique_compact`
    sweep (Pallas on TPU).
    """
    _check_backend(backend)
    flat = ids.reshape(-1)
    if backend == "fused":
        from repro import kernels

        return kernels.unique_with_inverse(flat, cap)
    return sort_unique_with_inverse(flat, cap)


@partial(jax.jit, static_argnums=(1,))
def sort_unique_with_inverse(ids: jax.Array, cap: int) -> tuple[jax.Array, jax.Array]:
    """Dedup and rank a flat id vector with one key-value sort.

    Sorting ``(ids, iota)`` on the ids yields the sorted ids and the
    permutation that sorts them; first-occurrence flags and their
    cumulative sum give every sorted id its rank in the result, and the
    permutation carries each rank back to its input position — no
    search.  INVALID sorts last like any value; ids of rank >= ``cap``
    (the overflow policy of :func:`unique_padded`) and INVALID map to -1.
    """
    flat = ids.reshape(-1)
    m = flat.shape[0]
    s, order = jax.lax.sort(
        (flat, jnp.arange(m, dtype=jnp.int32)), num_keys=1, is_stable=False
    )
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    rank = jnp.cumsum(first, dtype=jnp.int32) - 1
    # rank >= cap parks in slot `cap`, sliced off below; all writers of a
    # slot < cap carry the same value, so the duplicate scatter is exact
    slot = jnp.where(rank < cap, rank, cap)
    uniq = jnp.full((cap + 1,), INVALID, flat.dtype).at[slot].set(s)[:cap]
    inv_sorted = jnp.where((rank < cap) & (s != INVALID), rank, -1)
    # a second key-value sort of (order, inv_sorted) gives the same inv;
    # the scatter is faster where the plan is vmapped, as the TPU compiler
    # lowers it to that sort in a flat layout
    inv = jnp.zeros((m,), jnp.int32).at[order].set(inv_sorted, unique_indices=True)
    return uniq, inv


def unique_compact(ids: jax.Array, cap: int, backend: str = "reference") -> jax.Array:
    """Backend-dispatched :func:`unique_padded` (no inverse)."""
    _check_backend(backend)
    if backend == "fused":
        from repro import kernels

        return kernels.unique_compact(ids.reshape(-1), cap)
    return unique_padded(ids, cap)


@jax.jit
def count_valid(ids: jax.Array) -> jax.Array:
    return jnp.sum(ids != INVALID)


@jax.jit
def lookup(sorted_ids: jax.Array, queries: jax.Array) -> jax.Array:
    """Index of each query in a sorted padded id vector; -1 if absent.

    ``queries`` may contain INVALID (maps to -1).
    """
    pos = jnp.searchsorted(sorted_ids, queries).astype(jnp.int32)
    pos = jnp.clip(pos, 0, sorted_ids.shape[0] - 1)
    hit = (sorted_ids[pos] == queries) & (queries != INVALID)
    return jnp.where(hit, pos, jnp.int32(-1))


@jax.jit
def contains(sorted_ids: jax.Array, queries: jax.Array) -> jax.Array:
    return lookup(sorted_ids, queries) >= 0


@partial(jax.jit, static_argnums=(2,))
def compact(ids: jax.Array, keep: jax.Array, cap: int) -> jax.Array:
    """Keep ``ids[keep]``, drop the rest; result sorted + INVALID-padded."""
    masked = jnp.where(keep, ids, INVALID)
    out = jnp.sort(masked.reshape(-1))
    return pad_to(out, cap)


@partial(jax.jit, static_argnums=(1,))
def multiplicity(sorted_ids: jax.Array, cap: int) -> jax.Array:
    """Occurrence count of each *valid* entry of a sorted padded vector.

    Used by the theory harness to measure |T^l| (eq. 5): vertices reached
    from exactly one seed.
    """
    ids = sorted_ids
    left = jnp.concatenate([jnp.full((1,), -1, ids.dtype), ids[:-1]])
    starts = (ids != left) & (ids != INVALID)
    seg = jnp.cumsum(starts) - 1  # run index per element
    seg = jnp.where(ids == INVALID, cap - 1, seg)
    counts = jnp.zeros((cap,), jnp.int32).at[seg].add(
        jnp.where(ids != INVALID, 1, 0)
    )
    return counts, starts
