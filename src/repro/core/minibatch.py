"""Independent minibatching (§2.3) — the paper's baseline.

Builds a static-shape L-layer ``Minibatch`` plan from a seed frontier:
frontiers ``S^0 ⊂ S^1 ⊂ ... ⊂ S^L`` (eq. 2, self-inclusive), one padded
bipartite block per layer with neighbor indices resolved *into the next
frontier* so the forward pass is pure gathers.

Every capacity is static (see :class:`CapacityPlan`), which is what lets
the whole sampling pipeline ``jax.jit``/lower for the dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import frontier
from repro.core.graph import Graph, INVALID
from repro.core.rng import DependentRNG
from repro.core.samplers.base import Sampler


@dataclass(frozen=True)
class MinibatchLayer:
    """Bipartite block S~^{l+1} -> S^l with indices into frontier l+1."""

    seeds: jax.Array          # (cap_l,) dst vertex ids (= S^l), sorted+padded
    self_idx: jax.Array       # (cap_l,) position of each seed in S^{l+1}
    nbr_idx: jax.Array        # (cap_l, w) positions of sampled srcs in S^{l+1}
    mask: jax.Array           # (cap_l, w)
    etypes: Optional[jax.Array]  # (cap_l, w) relation ids or None

    @property
    def num_dst(self):
        return frontier.count_valid(self.seeds)

    @property
    def num_edges(self):
        return jnp.sum(self.mask)


@dataclass(frozen=True)
class Minibatch:
    """L-layer plan; ``input_ids`` = S^L (the vertices whose features load).

    Satisfies the :class:`repro.engine.Plan` protocol: uniform
    ``layers``/``input_ids``/``seed_ids`` plus :meth:`gather_inputs` and
    :meth:`stats`, so consumers can stay mode-agnostic.  Leaves may carry
    a leading PE axis when built stacked (``jax.vmap`` over seed rows).
    """

    layers: tuple[MinibatchLayer, ...]
    input_ids: jax.Array  # (cap_L,) or (P, cap_L) when stacked
    seed_ids: jax.Array   # (cap_0,) = layers[0].seeds

    @property
    def num_inputs(self):
        return frontier.count_valid(self.input_ids)

    def gather_inputs(self, store) -> jax.Array:
        """Input-layer embeddings from a :class:`FeatureStore`-like object."""
        with jax.named_scope("fetch.inputs"):
            return store.gather(self.input_ids)

    def stats(self) -> dict:
        """Uniform per-layer counts: S{l}, E{l}, inputs, comm{l+1} (=0).

        Scalars for a single plan; *max over the PE axis* for a stacked
        plan (same convention as cooperative ``plan_stats``).
        """
        stacked = self.input_ids.ndim > 1
        red = (lambda x: int(jnp.max(x))) if stacked else (lambda x: int(x))
        out = {}
        for l, layer in enumerate(self.layers):
            out[f"S{l}"] = red(jnp.sum(layer.seeds != INVALID, axis=-1))
            out[f"E{l}"] = red(jnp.sum(layer.mask, axis=(-2, -1)))
            out[f"comm{l+1}"] = 0  # independent mode never communicates
        out[f"S{len(self.layers)}"] = red(jnp.sum(self.input_ids != INVALID, axis=-1))
        out["inputs"] = out[f"S{len(self.layers)}"]
        return out


jax.tree_util.register_pytree_node(
    MinibatchLayer,
    lambda b: ((b.seeds, b.self_idx, b.nbr_idx, b.mask, b.etypes), None),
    lambda _, c: MinibatchLayer(*c),
)
jax.tree_util.register_pytree_node(
    Minibatch,
    lambda m: ((m.layers, m.input_ids, m.seed_ids), None),
    lambda _, c: Minibatch(tuple(c[0]), c[1], c[2]),
)


@dataclass(frozen=True)
class CapacityPlan:
    """Static frontier capacities cap_0..cap_L.

    Default policy: ``cap_{l+1} = min(cap_l * (fanout_growth), V)`` with a
    safety factor; concavity (Thm 3.2) means true sizes grow *slower*
    than this geometric bound, so overflow only happens when the bound
    is deliberately undersized.
    """

    caps: tuple[int, ...]

    @staticmethod
    def geometric(
        batch_size: int,
        num_layers: int,
        fanout: int,
        num_vertices: int,
        safety: float = 1.25,
        round_to: int = 8,
    ) -> "CapacityPlan":
        caps = [batch_size]
        for _ in range(num_layers):
            nxt = min(int(caps[-1] * (fanout + 1) * safety), num_vertices)
            nxt = -(-nxt // round_to) * round_to
            caps.append(nxt)
        return CapacityPlan(tuple(caps))

    def __getitem__(self, l: int) -> int:
        return self.caps[l]


def build_minibatch(
    graph: Graph,
    sampler: Sampler,
    seeds: jax.Array,
    rng: DependentRNG,
    num_layers: int,
    caps: CapacityPlan,
    backend: str = "reference",
) -> Minibatch:
    """Sample an L-layer minibatch plan (independent path, Fig. 7a).

    ``backend`` selects how the frontier hot loop lowers: ``"reference"``
    dedups and ranks each hop with one key-value sort
    (:func:`repro.core.frontier.sort_unique_with_inverse`), ``"fused"``
    routes dedup + rank resolution through the unique_compact sweep
    (Pallas on TPU).  Outputs are bit-identical.
    """
    frontier._check_backend(backend)
    with jax.named_scope("plan.seed_draw"):
        S_l = frontier.unique_compact(seeds, caps[0], backend=backend)
    layers = []
    for l in range(num_layers):
        with jax.named_scope(f"plan.hop{l + 1}"):
            ls = sampler.sample_layer(graph, S_l, rng, l)
            cat = jnp.concatenate([S_l, ls.nbr.reshape(-1)])
            S_next, inv = frontier.unique_with_inverse(
                cat, caps[l + 1], backend=backend
            )
            self_idx = inv[: S_l.shape[0]]
            nbr_idx = inv[S_l.shape[0]:].reshape(ls.nbr.shape)
            layers.append(
                MinibatchLayer(
                    seeds=S_l,
                    self_idx=self_idx,
                    nbr_idx=nbr_idx,
                    mask=ls.mask & (nbr_idx >= 0),
                    etypes=ls.etypes,
                )
            )
        S_l = S_next
    return Minibatch(layers=tuple(layers), input_ids=S_l, seed_ids=layers[0].seeds)


def layer_to_coo(
    layer: MinibatchLayer,
    cap_edges: int,
    backend: str = "reference",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Padded COO view of one bipartite block for plan-local assembly.

    Returns ``(rows, cols, indptr)``: ``indptr`` (cap_l+1,) counts valid
    edges per dst row; ``rows[e]``/``cols[e]`` give the dst row and the
    src position (into ``S^{l+1}``) of edge slot ``e`` in row-major mask
    order, ``-1`` past the total edge count.  Edges beyond ``cap_edges``
    are dropped deterministically (callers size ``cap_edges`` at
    ``cap_l * row_width`` so this never fires).
    """
    frontier._check_backend(backend)
    counts = jnp.sum(layer.mask, axis=1).astype(jnp.int32)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    if backend == "fused":
        from repro import kernels

        rows = kernels.expand_indptr(indptr, cap_edges)
    else:
        from repro.kernels.expand_indptr.ref import expand_indptr_ref

        rows = expand_indptr_ref(indptr, cap_edges)
    pos = jnp.cumsum(layer.mask, axis=1).astype(jnp.int32) - 1
    flat = indptr[:-1, None] + pos
    flat = jnp.where(layer.mask & (flat < cap_edges), flat, cap_edges)
    cols = (
        jnp.full((cap_edges + 1,), -1, jnp.int32)
        .at[flat.reshape(-1)]
        .set(jnp.where(layer.mask, layer.nbr_idx, -1).reshape(-1))[:cap_edges]
    )
    rows = jnp.where(cols >= 0, rows, -1)
    return rows, cols, indptr


def epoch_stats(mb: Minibatch) -> dict:
    """Vertex/edge counts per layer — the quantities in Fig 3 / Table 7."""
    out = {}
    for l, layer in enumerate(mb.layers):
        out[f"S{l}"] = int(layer.num_dst)
        out[f"E{l}"] = int(layer.num_edges)
    out[f"S{len(mb.layers)}"] = int(mb.num_inputs)
    return out
