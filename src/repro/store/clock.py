"""Device-resident set-associative CLOCK cache (batched, pure jnp).

True LRU is host-side control flow (an ordered dict), so it cannot live
on the accelerator; ``repro.core.cache.LRUCache`` stays the *oracle*.
This module is the device policy the paper's §4.2 bandwidth numbers need
on a real hot path: a set-associative cache with per-set CLOCK
(second-chance) eviction whose lookup *and* eviction are jittable array
ops — no host branching, no data-dependent shapes.

Layout: ``capacity = num_sets * ways`` slots per PE.  A vertex id hashes
to one set (Knuth multiplicative hash); within the set, ways are managed
by a clock hand over reference bits.  A batch access:

1. dedups the batch (``jnp.unique`` with static ``size``),
2. probes all ids against the tag array in one shot
   (:func:`repro.store.kernel.probe_ref`, XLA on every backend),
3. sets the reference bit of every hit,
4. inserts misses round-by-round (at most one insert per set per round,
   ``ways`` rounds total — a static Python loop), each round running
   CLOCK victim selection *vectorized across all sets*.

Per-PE states carry a leading ``(P, ...)`` axis so cooperative mode's
owned-vertex caches (`CooperativeCacheArray` semantics, §4.3.1) are the
same arrays with P > 1.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import INVALID
from repro.store.kernel import probe_ref

_HASH_MULT = np.uint32(2654435761)  # Knuth multiplicative hashing


class ClockState(NamedTuple):
    """Per-PE cache state; every leaf has a leading ``(P, ...)`` axis."""

    tags: jax.Array       # (P, S, W) int32 resident vertex id, INVALID = empty
    ref: jax.Array        # (P, S, W) bool CLOCK reference bits
    hand: jax.Array       # (P, S) int32 clock hand per set
    hits: jax.Array       # (P,) int32
    misses: jax.Array     # (P,) int32
    requested: jax.Array  # (P,) int32 unique valid ids seen (count_fetched)


class ClockAccess(NamedTuple):
    """Per-unique-id outcome of one batched access."""

    uniq: jax.Array       # (P, n) sorted unique ids, INVALID-padded
    hit: jax.Array        # (P, n) bool — resident before this batch
    slot: jax.Array       # (P, n) int32 flat slot of hits, -1 otherwise
    fill_slot: jax.Array  # (P, n) int32 slot a missed row was admitted to,
                          #         -1 if dropped (set conflict overflow)


def clock_init(capacity: int, ways: int = 8, num_pes: int = 1) -> ClockState:
    """Empty cache of ``capacity`` rows per PE, ``capacity % ways == 0``."""
    if ways < 1 or capacity < ways:
        raise ValueError(f"need capacity >= ways >= 1, got {capacity}/{ways}")
    if capacity % ways:
        raise ValueError(f"capacity {capacity} not a multiple of ways {ways}")
    S = capacity // ways
    P = num_pes
    return ClockState(
        tags=jnp.full((P, S, ways), INVALID, jnp.int32),
        ref=jnp.zeros((P, S, ways), bool),
        hand=jnp.zeros((P, S), jnp.int32),
        hits=jnp.zeros((P,), jnp.int32),
        misses=jnp.zeros((P,), jnp.int32),
        requested=jnp.zeros((P,), jnp.int32),
    )


def hash_set(ids: jax.Array, num_sets: int) -> jax.Array:
    """Multiplicative hash of vertex ids onto ``[0, num_sets)``."""
    h = (ids.astype(jnp.uint32) * _HASH_MULT) >> 8
    return (h % jnp.uint32(num_sets)).astype(jnp.int32)


def unique_rows(ids: jax.Array) -> jax.Array:
    """Row-wise sorted unique with static width (INVALID pads sort last)."""
    n = ids.shape[-1]
    uniq = lambda row: jnp.unique(row, size=n, fill_value=INVALID)
    return jax.vmap(uniq)(ids)


def _insert_one(tags, ref, hand, ids, sets, hit, way):
    """Insert this batch's misses into one PE's cache (CLOCK eviction).

    ``ids`` is one deduplicated row; at most one insert lands per set per
    round, so ``ways`` rounds admit every miss that can fit.  Overflowing
    conflicts (more misses than ways hashing to one set) are dropped —
    they stay misses and their rows are served straight from the fetch.
    """
    S, W = tags.shape
    n = ids.shape[0]
    valid = ids != INVALID
    miss = valid & ~hit

    # second-chance bit for every hit
    way0 = jnp.maximum(way, 0)
    ref = ref.at[sets, way0].max(hit)

    # rank of each miss within its set: argsort by set, then position
    # since the start of the equal-set run
    key = jnp.where(miss, sets, S)
    order = jnp.argsort(key)
    skey = key[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    newseg = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(newseg, idx, 0)
    )
    rank = jnp.zeros(n, jnp.int32).at[order].set(idx - seg_start)

    fill_slot = jnp.full(n, -1, jnp.int32)
    wpos = jnp.arange(W, dtype=jnp.int32)
    for r in range(W):
        sel = miss & (rank == r)
        tgt = jnp.where(sel, sets, S)  # out-of-bounds rows are dropped
        ins = jnp.full((S,), INVALID, jnp.int32).at[tgt].set(ids, mode="drop")
        do = ins != INVALID                                    # (S,)
        # CLOCK sweep, vectorized over sets: walk ways from the hand,
        # victim = first clear ref bit; if all set, clear the full circle
        # and take the hand position (classic second chance).
        ordered = (hand[:, None] + wpos[None, :]) % W          # (S, W)
        ref_ord = jnp.take_along_axis(ref, ordered, axis=1)
        k = jnp.argmin(ref_ord, axis=1)
        swept = (wpos[None, :] < k[:, None]) | ref_ord.all(1)[:, None]
        ref_ord = ref_ord & ~swept
        inv = (wpos[None, :] - hand[:, None]) % W
        ref_nat = jnp.take_along_axis(ref_ord, inv, axis=1)
        victim = jnp.take_along_axis(ordered, k[:, None], axis=1)[:, 0]
        at_victim = wpos[None, :] == victim[:, None]
        tags = jnp.where(do[:, None] & at_victim, ins[:, None], tags)
        ref = jnp.where(do[:, None], jnp.where(at_victim, True, ref_nat), ref)
        hand = jnp.where(do, (victim + 1) % W, hand)
        fill_slot = jnp.where(sel, sets * W + victim[sets], fill_slot)

    # a later round may have evicted an earlier same-batch insert (only
    # possible at W == 1): an admitted row owns its slot only if its tag
    # survived to the end of the batch
    survived = tags.reshape(-1)[jnp.maximum(fill_slot, 0)] == ids
    fill_slot = jnp.where((fill_slot >= 0) & survived, fill_slot, -1)
    return tags, ref, hand, fill_slot, miss


@jax.jit
def clock_access(
    state: ClockState, uniq: jax.Array
) -> tuple[ClockState, ClockAccess]:
    """Access one deduplicated batch per PE; returns the new state.

    ``uniq``: (P, n) row-wise *unique* sorted ids (see :func:`unique_rows`),
    INVALID-padded.  Lookup resolves against the pre-batch tags (batched
    semantics: a row evicted by this batch's own inserts still counts as
    the hit it was when the batch arrived).
    """
    P, S, W = state.tags.shape
    valid = uniq != INVALID
    sets = jnp.where(valid, hash_set(uniq, S), 0)
    # one flat probe for all PEs: offset each PE's sets into a (P*S, W)
    # tag view so the probe runs once, unbatched.  It is the XLA probe on
    # every backend: the tag_probe Pallas kernel does not compile for the
    # TPU (see docs/kernels.md)
    gsets = sets + jnp.arange(P, dtype=jnp.int32)[:, None] * S
    pids = jnp.where(valid, uniq, -1)  # -1 never matches a resident tag
    way = probe_ref(
        state.tags.reshape(P * S, W), gsets.reshape(-1), pids.reshape(-1)
    ).reshape(P, -1)
    hit = way >= 0
    slot = jnp.where(hit, sets * W + jnp.maximum(way, 0), -1)

    tags, ref, hand, fill_slot, miss = jax.vmap(_insert_one)(
        state.tags, state.ref, state.hand, uniq, sets, hit, way
    )
    new = ClockState(
        tags=tags, ref=ref, hand=hand,
        hits=state.hits + hit.sum(1, dtype=jnp.int32),
        misses=state.misses + miss.sum(1, dtype=jnp.int32),
        requested=state.requested + valid.sum(1, dtype=jnp.int32),
    )
    return new, ClockAccess(uniq=uniq, hit=hit, slot=slot, fill_slot=fill_slot)


class ClockCache:
    """Stateful replay wrapper mirroring ``LRUCache.access_batch``.

    Tracks only tags/ref/hand/counters (no feature rows) so differential
    tests and benchmarks can replay id traces through the device policy
    and compare hit rates against the exact LRU oracle.  ``num_pes > 1``
    mirrors ``CooperativeCacheArray``: row p of an access touches only
    PE p's cache.
    """

    def __init__(self, capacity: int, ways: int = 8, num_pes: int = 1):
        self.capacity = capacity
        self.ways = ways
        self.num_pes = num_pes
        self.state = clock_init(capacity, ways, num_pes)

    def access_batch(self, ids) -> int:
        """Access the unique valid ids of one batch; returns #misses."""
        ids = jnp.asarray(ids, jnp.int32)
        if self.num_pes == 1:
            ids = ids.reshape(1, -1)
        elif ids.ndim != 2 or ids.shape[0] != self.num_pes:
            raise ValueError(
                f"expected (P={self.num_pes}, n) ids, got {ids.shape}"
            )
        before = self.state.misses
        self.state, _ = clock_access(self.state, unique_rows(ids))
        return int((self.state.misses - before).sum())

    # cooperative-parity alias (CooperativeCacheArray.access)
    access = access_batch

    @property
    def hits(self) -> int:
        return int(self.state.hits.sum())

    @property
    def misses(self) -> int:
        return int(self.state.misses.sum())

    @property
    def miss_rate(self) -> float:
        total = self.hits + self.misses
        return self.misses / total if total else 0.0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def reset_stats(self) -> None:
        z = jnp.zeros((self.num_pes,), jnp.int32)
        self.state = self.state._replace(hits=z, misses=z, requested=z)
