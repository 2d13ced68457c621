"""Synthetic power-law graph datasets (RMAT) + features/labels.

The container is offline, so we substitute the paper's datasets
(reddit/yelp/flickr/papers100M/mag240M) with degree-capped RMAT graphs
whose *shape statistics* (power-law degrees, small diameter, avg degree)
drive the theorems — Thm 3.1/3.2/3.3 hold for every graph, so synthetic
graphs validate the claims qualitatively (DESIGN.md §8).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph


def rmat_edges(
    scale: int,
    edge_factor: int,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Classic RMAT generator: 2**scale vertices, edge_factor*V edges."""
    rng = np.random.default_rng(seed)
    V = 1 << scale
    E = edge_factor * V
    src = np.zeros(E, dtype=np.int64)
    dst = np.zeros(E, dtype=np.int64)
    for bit in range(scale):
        r = rng.random(E)
        go_src = (r >= a + b) & (r < a + b + c) | (r >= a + b + c)
        go_dst = (r >= a) & (r < a + b) | (r >= a + b + c)
        src |= go_src.astype(np.int64) << bit
        dst |= go_dst.astype(np.int64) << bit
    # permute ids to break the RMAT bit-prefix locality a little (but keep
    # some, so the BFS partitioner has structure to exploit)
    keep = src != dst  # drop self loops
    return src[keep], dst[keep]


def rmat_graph(
    scale: int = 12,
    edge_factor: int = 8,
    max_degree: int = 64,
    undirected: bool = True,
    num_edge_types: int = 1,
    seed: int = 0,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Graph:
    """Degree-capped RMAT graph.  ``a, b, c`` are the quadrant
    probabilities (Graph500's skew by default); a flatter skew lets more
    vertices reach ``max_degree``."""
    src, dst = rmat_edges(scale, edge_factor, a=a, b=b, c=c, seed=seed)
    if undirected:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    # dedup parallel edges
    key = src * (1 << scale) + dst
    _, uniq_idx = np.unique(key, return_index=True)
    src, dst = src[uniq_idx], dst[uniq_idx]
    et = None
    if num_edge_types > 1:
        rng = np.random.default_rng(seed + 1)
        et = rng.integers(0, num_edge_types, size=len(src)).astype(np.int32)
    return Graph.from_edges(
        src,
        dst,
        num_vertices=1 << scale,
        edge_types=et,
        max_degree=max_degree,
        num_edge_types=num_edge_types,
        seed=seed,
    )


@dataclass
class SyntheticGraphDataset:
    """Graph + node features + labels + train/val/test split.

    Features are a fixed random projection of the vertex id (deterministic,
    storable "on disk" conceptually) and labels come from a hidden 2-layer
    propagation so that a GNN can actually fit them (non-trivial
    convergence experiments, Fig 4/9).
    """

    graph: Graph
    feature_dim: int = 64
    num_classes: int = 16
    seed: int = 0
    features: jax.Array = field(init=False)
    labels: jax.Array = field(init=False)
    train_ids: np.ndarray = field(init=False)
    val_ids: np.ndarray = field(init=False)
    test_ids: np.ndarray = field(init=False)

    def __post_init__(self):
        V = self.graph.num_vertices
        rng = np.random.default_rng(self.seed)
        feats = rng.standard_normal((V, self.feature_dim)).astype(np.float32)
        self.features = jnp.asarray(feats)
        # hidden teacher: labels depend on own + 1-hop-mean features
        W = rng.standard_normal((self.feature_dim, self.num_classes)).astype(
            np.float32
        )
        indptr = np.asarray(self.graph.indptr)
        indices = np.asarray(self.graph.indices)
        deg = np.diff(indptr)
        # neighbor sums one degree slot at a time: each row adds its
        # neighbors in edge order, as a scatter-add over edges would, in
        # max_degree vectorized passes instead of one per edge
        agg = np.zeros_like(feats)
        for k in range(int(deg.max(initial=0))):
            rows = np.nonzero(deg > k)[0]
            agg[rows] += feats[indices[indptr[rows] + k]]
        agg /= np.maximum(deg, 1)[:, None]
        logits = (feats + agg) @ W
        self.labels = jnp.asarray(np.argmax(logits, axis=1).astype(np.int32))
        perm = rng.permutation(V)
        n_tr, n_val = int(0.6 * V), int(0.2 * V)
        self.train_ids = np.sort(perm[:n_tr]).astype(np.int32)
        self.val_ids = np.sort(perm[n_tr : n_tr + n_val]).astype(np.int32)
        self.test_ids = np.sort(perm[n_tr + n_val :]).astype(np.int32)

    def seed_batch(self, step: int, batch_size: int, split: str = "train") -> np.ndarray:
        """Deterministic epoch-shuffled seed-vertex batch (host-side)."""
        ids = {"train": self.train_ids, "val": self.val_ids, "test": self.test_ids}[
            split
        ]
        n = len(ids)
        per_epoch = max(1, n // batch_size)
        epoch, it = divmod(step, per_epoch)
        order = np.random.default_rng(self.seed + 17 * epoch).permutation(n)
        sel = order[it * batch_size : (it + 1) * batch_size]
        return ids[sel]
