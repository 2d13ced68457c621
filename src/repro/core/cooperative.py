"""Cooperative Minibatching (§3.1, Algorithm 1) — the paper's contribution.

One *global* minibatch of size ``B = b·P`` is processed by all ``P`` PEs
together.  The graph is 1-D partitioned (vertex + in-edges owned by one
PE).  Every sampling hop and every forward/backward layer redistributes
vertex ids / embeddings / gradients to owner PEs with an **all-to-all**.

Execution backends
------------------
The same per-PE code runs under two executors:

* :class:`SimExecutor` — PEs are a stacked leading axis ``(P, ...)``;
  per-PE compute is ``jax.vmap``; the all-to-all is an axis transpose.
  Runs on one device; used by tests/benchmarks and as the semantics
  oracle.
* :class:`ShardExecutor` — per-PE code runs inside ``shard_map`` over a
  mesh axis; the all-to-all is ``jax.lax.all_to_all`` (ICI on TPU).
  This is the production path exercised by the dry-run and the
  multi-device subprocess tests.

Exchange convention: each PE holds a buffer ``x`` of shape
``(P, cap, ...)`` whose slice ``x[q]`` is destined for PE ``q``;
``exchange`` returns ``y`` with ``y[q]`` = what PE ``q`` sent here.
``lax.all_to_all(split_axis=0, concat_axis=0, tiled=True)`` implements
exactly this, and — crucially — it has a transpose rule, so running
``jax.grad`` through the cooperative forward pass derives the paper's
backward-pass all-to-alls (Alg. 1, last loop) automatically.

Static shapes: bucket capacities are fixed; over-capacity vertices are
*dropped deterministically* (counted in ``plan_stats``) — capacities are
sized from the concavity bound so this never fires in practice.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Protocol

import jax
import jax.numpy as jnp

from repro.core import frontier
from repro.core.graph import Graph, INVALID
from repro.core.partition import Partition
from repro.core.rng import DependentRNG
from repro.core.samplers.base import Sampler


# --------------------------------------------------------------------------
# Executors
# --------------------------------------------------------------------------
class Executor(Protocol):
    num_pes: int

    def pe(self, fn: Callable, *args):
        """Run a pure per-PE function on every PE."""

    def exchange(self, x: jax.Array) -> jax.Array:
        """Bucketed all-to-all; see module docstring for the convention."""


@dataclass(frozen=True)
class SimExecutor:
    """Single-device simulation: PEs = stacked leading axis, A2A = swap."""

    num_pes: int

    def pe(self, fn, *args):
        return jax.vmap(fn)(*args)

    def exchange(self, x):
        # x: (P_src, P_dst, cap, ...) stacked over source PEs
        return jnp.swapaxes(x, 0, 1)


@dataclass(frozen=True)
class ShardExecutor:
    """shard_map backend: per-PE bodies run on their own mesh shard."""

    num_pes: int
    axis_name: str = "data"

    def pe(self, fn, *args):
        return fn(*args)

    def exchange(self, x):
        return jax.lax.all_to_all(
            x, self.axis_name, split_axis=0, concat_axis=0, tiled=True
        )


# --------------------------------------------------------------------------
# Plan structures
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class CoopLayer:
    """One cooperative layer: local block + cached exchange mappings.

    The forward pass converts owned embeddings ``H`` (rows = S^{l+1}) into
    request-side embeddings ``H~`` (rows = S~^{l+1}) via
    ``redistribute``; the bipartite compute then uses only local indices.
    """

    seeds: jax.Array          # (cap_l,) owned dst ids S_p^l
    self_idx: jax.Array       # (cap_l,) into S~^{l+1}
    nbr_idx: jax.Array        # (cap_l, w) into S~^{l+1}
    mask: jax.Array           # (cap_l, w)
    etypes: Optional[jax.Array]
    slot_to_tilde: jax.Array  # (P, cap_bucket) scatter: bucket slot -> S~ row
    req_idx: jax.Array        # (P, cap_bucket) gather: peer request -> S^{l+1} row
    tilde_ids: jax.Array      # (cap_tilde,) S~^{l+1} vertex ids (debug/tests)


@dataclass(frozen=True)
class CoopMinibatch:
    """Cooperative L-layer plan.

    Satisfies the :class:`repro.engine.Plan` protocol (``layers`` /
    ``input_ids`` / ``seed_ids`` / :meth:`gather_inputs` / :meth:`stats`)
    alongside :class:`repro.core.minibatch.Minibatch`.  Under
    :class:`SimExecutor` every leaf carries a leading ``(P, ...)`` axis.
    """

    layers: tuple[CoopLayer, ...]
    input_ids: jax.Array  # (cap_L,) owned S_p^L — features this PE fetches
    seed_ids: jax.Array

    def gather_inputs(self, store) -> jax.Array:
        """Owned input embeddings (no cross-PE duplication, Fig. 7b)."""
        with jax.named_scope("fetch.inputs"):
            return store.gather(self.input_ids)

    def stats(self) -> dict:
        """Per-PE max counts (Table 7).  Requires the stacked Sim layout."""
        if self.seed_ids.ndim != 2 or self.layers[0].slot_to_tilde.ndim != 3:
            raise ValueError(
                "CoopMinibatch.stats() needs the stacked SimExecutor layout; "
                "plans built per-PE under ShardExecutor have no global view"
            )
        return plan_stats(self, SimExecutor(self.seed_ids.shape[0]))


jax.tree_util.register_pytree_node(
    CoopLayer,
    lambda b: (
        (
            b.seeds,
            b.self_idx,
            b.nbr_idx,
            b.mask,
            b.etypes,
            b.slot_to_tilde,
            b.req_idx,
            b.tilde_ids,
        ),
        None,
    ),
    lambda _, c: CoopLayer(*c),
)
jax.tree_util.register_pytree_node(
    CoopMinibatch,
    lambda m: ((m.layers, m.input_ids, m.seed_ids), None),
    lambda _, c: CoopMinibatch(tuple(c[0]), c[1], c[2]),
)


@dataclass(frozen=True)
class CoopCapacityPlan:
    """Static capacities: owned frontier, request frontier, A2A bucket."""

    caps: tuple[int, ...]         # owned S_p^l capacity, l = 0..L
    tilde_caps: tuple[int, ...]   # S~_p^{l+1} capacity, l = 0..L-1
    bucket_caps: tuple[int, ...]  # per-peer A2A bucket, l = 0..L-1

    @staticmethod
    def geometric(
        local_batch: int,
        num_layers: int,
        fanout: int,
        num_vertices: int,
        num_pes: int,
        safety: float = 1.5,
        bucket_safety: float = 2.5,
        round_to: int = 8,
    ) -> "CoopCapacityPlan":
        rnd = lambda x: -(-int(x) // round_to) * round_to
        caps = [rnd(local_batch)]
        tilde, buckets = [], []
        for _ in range(num_layers):
            t = min(rnd(caps[-1] * (fanout + 1) * safety), num_vertices)
            tilde.append(t)
            buckets.append(rnd(t // num_pes * bucket_safety + fanout))
            caps.append(min(rnd(t * safety), num_vertices))
        return CoopCapacityPlan(tuple(caps), tuple(tilde), tuple(buckets))


# --------------------------------------------------------------------------
# Plan building (cooperative sampling — Alg. 1, first loop)
# --------------------------------------------------------------------------
def _bucketize(ids: jax.Array, owners: jax.Array, num_pes: int, cap_bucket: int):
    """Partition a padded id vector into per-owner buckets.

    Returns (bucket_ids (P, cap), slot_to_src (P, cap)) where slot_to_src
    maps each bucket slot back to its position in ``ids`` (-1 padding).
    """
    n = ids.shape[0]
    valid = ids != INVALID
    owners = jnp.where(valid, owners, num_pes)  # park padding in a ghost bucket
    order = jnp.argsort(owners, stable=True)
    sorted_owner = owners[order]
    sorted_ids = ids[order]
    group_start = jnp.searchsorted(sorted_owner, jnp.arange(num_pes + 1))
    rank = jnp.arange(n) - group_start[jnp.clip(sorted_owner, 0, num_pes)]
    ok = (sorted_owner < num_pes) & (rank < cap_bucket)
    flat_pos = jnp.where(
        ok, sorted_owner * cap_bucket + rank, num_pes * cap_bucket
    )
    bucket_ids = (
        jnp.full((num_pes * cap_bucket + 1,), INVALID, ids.dtype)
        .at[flat_pos]
        .set(jnp.where(ok, sorted_ids, INVALID))[: num_pes * cap_bucket]
        .reshape(num_pes, cap_bucket)
    )
    slot_to_src = (
        jnp.full((num_pes * cap_bucket + 1,), -1, jnp.int32)
        .at[flat_pos]
        .set(jnp.where(ok, order.astype(jnp.int32), -1))[: num_pes * cap_bucket]
        .reshape(num_pes, cap_bucket)
    )
    return bucket_ids, slot_to_src


def build_cooperative_minibatch(
    graph: Graph,
    sampler: Sampler,
    part: Partition,
    seeds: jax.Array,  # per-PE owned seed frontier (stacked (P, b) under Sim)
    rng: DependentRNG,
    num_layers: int,
    caps: CoopCapacityPlan,
    ex: Executor,
    backend: str = "reference",
) -> CoopMinibatch:
    frontier._check_backend(backend)
    P = ex.num_pes

    def local_seeds(s):
        return frontier.unique_compact(s, caps.caps[0], backend=backend)

    with jax.named_scope("plan.seed_draw"):
        S_l = ex.pe(local_seeds, seeds)
    layers = []
    for l in range(num_layers):
        cap_t, cap_b, cap_next = caps.tilde_caps[l], caps.bucket_caps[l], caps.caps[l + 1]

        def sample_and_bucket(S):
            ls = sampler.sample_layer(graph, S, rng, l)
            cat = jnp.concatenate([S, ls.nbr.reshape(-1)])
            tilde, inv = frontier.unique_with_inverse(cat, cap_t, backend=backend)
            self_idx = inv[: S.shape[0]]
            nbr_idx = inv[S.shape[0]:].reshape(ls.nbr.shape)
            owners = part.owner_of(tilde)
            bucket_ids, slot_to_tilde = _bucketize(tilde, owners, P, cap_b)
            return ls, tilde, nbr_idx, self_idx, bucket_ids, slot_to_tilde

        def next_frontier(req):
            # one fused dedup resolves BOTH the next owned frontier and
            # every peer request slot — the separate lookup pass is gone
            S_next, inv = frontier.unique_with_inverse(
                req.reshape(-1), cap_next, backend=backend
            )
            return S_next, inv.reshape(req.shape)

        with jax.named_scope(f"plan.hop{l + 1}"):
            ls, tilde, nbr_idx, self_idx, bucket_ids, slot_to_tilde = ex.pe(
                sample_and_bucket, S_l
            )
            with jax.named_scope("exchange.ids"):
                req = ex.exchange(bucket_ids)  # ids owned here, requested per peer
            S_next, req_idx = ex.pe(next_frontier, req)
            layers.append(
                CoopLayer(
                    seeds=S_l,
                    self_idx=self_idx,
                    nbr_idx=nbr_idx,
                    mask=ls.mask & (nbr_idx >= 0),
                    etypes=ls.etypes,
                    slot_to_tilde=slot_to_tilde,
                    req_idx=req_idx,
                    tilde_ids=tilde,
                )
            )
        S_l = S_next
    seed_ids = layers[0].seeds
    return CoopMinibatch(layers=tuple(layers), input_ids=S_l, seed_ids=seed_ids)


# --------------------------------------------------------------------------
# Embedding redistribution (Alg. 1 forward loop; backward via AD transpose)
# --------------------------------------------------------------------------
def redistribute(
    ex: Executor, layer: CoopLayer, H: jax.Array, cap_tilde: int
) -> jax.Array:
    """Convert owned embeddings H (rows = S^{l+1}) to H~ (rows = S~^{l+1}).

    Differentiable: reverse-mode AD through ``exchange`` yields the
    backward-pass all-to-all of Alg. 1 (gradient redistribution to owners)
    with no hand-written transpose.
    """

    def gather_send(H, req_idx):
        send = H[jnp.clip(req_idx, 0)]  # (P, cap_b, d)
        return jnp.where((req_idx >= 0)[..., None], send, 0.0)

    send = ex.pe(gather_send, H, layer.req_idx)
    with jax.named_scope("exchange.embeddings"):
        recv = ex.exchange(send)

    def scatter(recv, slot_to_tilde):
        d = recv.shape[-1]
        pos = jnp.where(slot_to_tilde >= 0, slot_to_tilde, cap_tilde).reshape(-1)
        out = (
            jnp.zeros((cap_tilde + 1, d), recv.dtype).at[pos].set(recv.reshape(-1, d))
        )
        return out[:cap_tilde]

    return ex.pe(scatter, recv, layer.slot_to_tilde)


def plan_stats(mb: CoopMinibatch, ex: Executor) -> dict:
    """Per-PE max counts (Table 7 columns): |S^l|, |E^l|, |S~^l|, c|S~^l|.

    Only meaningful under :class:`SimExecutor` (stacked PE axis).
    """
    assert isinstance(ex, SimExecutor)
    P = ex.num_pes
    off_diag = ~jnp.eye(P, dtype=bool)  # (P_src, P_owner)
    stats = {}
    for l, layer in enumerate(mb.layers):
        stats[f"S{l}"] = int(jnp.max(jnp.sum(layer.seeds != INVALID, axis=-1)))
        stats[f"E{l}"] = int(jnp.max(jnp.sum(layer.mask, axis=(-2, -1))))
        filled = layer.slot_to_tilde >= 0  # (P, P, cap_b)
        stats[f"tilde{l+1}"] = int(jnp.max(jnp.sum(filled, axis=(-2, -1))))
        cross = jnp.sum(filled & off_diag[:, :, None], axis=(-2, -1))
        stats[f"comm{l+1}"] = int(jnp.max(cross))
    stats["inputs"] = int(jnp.max(jnp.sum(mb.input_ids != INVALID, axis=-1)))
    return stats
