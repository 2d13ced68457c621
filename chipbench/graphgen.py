"""Seeded graph datasets generated on the device, for the benchmark.

The distribution is that of ``repro.data.rmat_graph``: RMAT edges from
``a, b, c`` quadrant probabilities, self-loops dropped, symmetrised,
parallel edges removed, and every in-degree above ``max_degree`` cut to
a uniformly drawn subset of ``max_degree`` in-edges.  Everything runs
in one jitted program per configuration (sorts and scans over the edge
list), so a run pays seconds of device time instead of the minutes the
host numpy generator takes at millions of vertices.

A compiled train step is specialised to the edge count, so the count
has to be the same on every seed: ``num_edges`` (from the
configuration) is a fixed number a little below what the generator
yields, and the surplus edges are dropped uniformly at random.
``edge_count`` measures what the generator yields before that cut.

The benchmark hands the program the arrays through a plain
``Dataset``; nothing here imports the program except its ``Graph``
container, which is the program's input format.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class GraphSpec:
    """Shape of one generated dataset (all static)."""

    scale: int                 # V = 2**scale
    edge_factor: int           # RMAT draws edge_factor * V directed edges
    a: float
    b: float
    c: float
    max_degree: int
    feature_dim: int
    num_classes: int
    num_train: int
    rel_shares: tuple = (1.0,)  # relation share of each edge type

    @property
    def num_vertices(self) -> int:
        return 1 << self.scale

    @classmethod
    def from_config(cls, cfg: dict) -> "GraphSpec":
        g = cfg["graph"]
        shares = tuple(float(s) for s in g.get("relation_shares", [1.0]))
        return cls(
            scale=int(np.log2(cfg["num_vertices"])),
            edge_factor=int(g["edge_factor"]), a=float(g["a"]),
            b=float(g["b"]), c=float(g["c"]),
            max_degree=int(cfg["max_degree"]),
            feature_dim=int(cfg["feature_dim"]),
            num_classes=int(cfg["num_classes"]),
            num_train=int(cfg["num_train"]), rel_shares=shares,
        )


@dataclass
class Dataset:
    """What ``train_gnn`` reads: graph, features, labels, id splits."""

    graph: object
    features: jax.Array
    labels: jax.Array
    train_ids: np.ndarray
    val_ids: np.ndarray
    test_ids: np.ndarray


def data_key(seed: int) -> jax.Array:
    """A PRNG key that keeps all 64 bits of a large ``--seed``."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _rmat(key, spec: GraphSpec):
    """(dst, src) of the symmetrised RMAT edge list, parallel edges and
    self-loops parked at dst == V; sorted by (dst, src)."""
    V, E0 = spec.num_vertices, spec.edge_factor * spec.num_vertices
    a, b, c = spec.a, spec.b, spec.c

    def bit(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(key, i), (E0,))
        go_src = r >= a + b
        go_dst = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        return (src | (go_src.astype(jnp.int32) << i),
                dst | (go_dst.astype(jnp.int32) << i))

    zeros = jnp.zeros((E0,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, spec.scale, bit, (zeros, zeros))
    loop = src == dst
    s2 = jnp.concatenate([src, dst])
    d2 = jnp.where(jnp.concatenate([loop, loop]), V,
                   jnp.concatenate([dst, src]))
    d2, s2 = jax.lax.sort((d2, s2), num_keys=2)
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           (d2[1:] == d2[:-1]) & (s2[1:] == s2[:-1])])
    return jnp.where(dup, V, d2), s2


def _capped(key, spec: GraphSpec):
    """(dst, src, keep): edges sorted by (dst, random key); ``keep``
    marks the first ``max_degree`` valid in-edges of each vertex."""
    V = spec.num_vertices
    d, s = _rmat(jax.random.fold_in(key, 0), spec)
    rnd = jax.random.bits(jax.random.fold_in(key, 1), d.shape, jnp.uint32)
    d, _, s = jax.lax.sort((d, rnd, s), num_keys=2)
    i = jnp.arange(d.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), d[1:] != d[:-1]])
    start = jax.lax.cummax(jnp.where(first, i, 0))
    keep = (d < V) & (i - start < spec.max_degree)
    return d, s, keep


@partial(jax.jit, static_argnums=(1,))
def _count(key, spec: GraphSpec):
    return jnp.sum(_capped(jax.random.split(key, 6)[0], spec)[2])


def edge_count(key, spec: GraphSpec) -> int:
    """Edges the generator yields before the cut to a fixed count."""
    return int(_count(key, spec))


def _keep_exactly(key, keep, n: int):
    """``keep`` reduced to exactly ``n`` True entries, dropping a uniform
    random subset of the surplus (bisection on a random uint32 key)."""
    rnd = jax.random.bits(key, keep.shape, jnp.uint32)

    def count_le(t):
        return jnp.sum(keep & (rnd <= t))

    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = count_le(mid) >= n
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    lo, _ = jax.lax.fori_loop(
        0, 33, step, (jnp.uint32(0), jnp.uint32(0xFFFFFFFF)))
    below = keep & (rnd < lo)
    tie = keep & (rnd == lo)
    need = n - jnp.sum(below)
    return below | (tie & (jnp.cumsum(tie) <= need))


@partial(jax.jit, static_argnums=(1, 2))
def _generate(key, spec: GraphSpec, num_edges: int):
    V = spec.num_vertices
    k_graph, k_trim, k_rel, k_feat, k_lab, k_train = jax.random.split(key, 6)
    d, s, keep = _capped(k_graph, spec)
    yielded = jnp.sum(keep)
    keep = _keep_exactly(k_trim, keep, num_edges)
    d, s = jax.lax.sort((jnp.where(keep, d, V), s), num_keys=2)
    d, s = d[:num_edges], s[:num_edges]
    indptr = jnp.searchsorted(
        d, jnp.arange(V + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    cum = jnp.cumsum(jnp.asarray(spec.rel_shares, jnp.float32))
    u = jax.random.uniform(k_rel, (num_edges,)) * cum[-1]
    etypes = jnp.minimum(jnp.searchsorted(cum, u, side="right"),
                         len(spec.rel_shares) - 1).astype(jnp.int32)
    feats = jax.random.normal(k_feat, (V, spec.feature_dim), jnp.float32)
    labels = jax.random.randint(k_lab, (V,), 0, spec.num_classes, jnp.int32)
    train = jnp.sort(jax.random.choice(
        k_train, V, (spec.num_train,), replace=False)).astype(jnp.int32)
    return indptr, s, etypes, feats, labels, train, yielded


def generate(seed: int, spec: GraphSpec, num_edges: int):
    """``(Dataset, edges_yielded)`` for ``seed``: the graph has exactly
    ``num_edges`` in-edges; raises if the generator yields fewer."""
    from repro.core.graph import Graph

    indptr, indices, etypes, feats, labels, train, yielded = _generate(
        data_key(seed), spec, num_edges)
    yielded = int(yielded)
    if yielded < num_edges:
        raise ValueError(
            f"seed {seed}: the generator yielded {yielded} edges, fewer "
            f"than the configured num_edges={num_edges}")
    R = len(spec.rel_shares)
    graph = Graph(
        indptr=indptr, indices=indices,
        edge_types=etypes if R > 1 else None,
        max_degree=spec.max_degree, num_vertices=spec.num_vertices,
        num_edges=num_edges, num_edge_types=R,
    )
    empty = np.zeros((0,), np.int32)
    ds = Dataset(graph=graph, features=feats, labels=labels,
                 train_ids=np.asarray(train), val_ids=empty, test_ids=empty)
    return ds, yielded
