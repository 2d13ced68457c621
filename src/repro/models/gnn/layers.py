"""GNN models over padded bipartite layer blocks.

Every layer consumes ``H~`` — embeddings indexed by the *request-side*
frontier (for Independent Minibatching that's simply ``S^{l+1}``; for
Cooperative it's ``S~^{l+1}`` after the all-to-all) — plus the layer's
local indices (``self_idx``, ``nbr_idx``, ``mask``), and emits embeddings
for the layer's destination frontier ``S^l``.  The *same* model code
therefore runs under both minibatching modes; only the embedding
provider differs (DESIGN.md §2).

Models: gcn | sage | gat | rgcn — the paper evaluates GCN (papers100M),
R-GCN (mag240M) and GAT (§4.3).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class GNNConfig:
    model: str = "gcn"           # gcn | sage | gat | rgcn
    num_layers: int = 3
    in_dim: int = 64
    hidden_dim: int = 256
    num_classes: int = 16
    num_heads: int = 4           # gat
    num_relations: int = 1       # rgcn
    dtype: jnp.dtype = jnp.float32


def _glorot(key, shape, dtype):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def init_gnn(key: jax.Array, cfg: GNNConfig) -> dict:
    """Parameter pytree: params['layers'][l] is one layer's dict."""
    # plan layer l computes H^l from H^{l+1}: layer L-1 consumes raw
    # features, layer 0 emits class logits.
    layers = []
    for l in range(cfg.num_layers):
        d_in = cfg.in_dim if l == cfg.num_layers - 1 else cfg.hidden_dim
        d_out = cfg.num_classes if l == 0 else cfg.hidden_dim
        key, *ks = jax.random.split(key, 6)
        if cfg.model == "gcn":
            p = {"w": _glorot(ks[0], (d_in, d_out), cfg.dtype),
                 "b": jnp.zeros((d_out,), cfg.dtype)}
        elif cfg.model == "sage":
            p = {
                "w_self": _glorot(ks[0], (d_in, d_out), cfg.dtype),
                "w_nbr": _glorot(ks[1], (d_in, d_out), cfg.dtype),
                "b": jnp.zeros((d_out,), cfg.dtype),
            }
        elif cfg.model == "gat":
            h = cfg.num_heads
            dh = max(1, d_out // h)
            p = {
                "w": _glorot(ks[0], (d_in, h * dh), cfg.dtype),
                "a_src": _glorot(ks[1], (h, dh, 1), cfg.dtype)[..., 0],
                "a_dst": _glorot(ks[2], (h, dh, 1), cfg.dtype)[..., 0],
                "w_out": _glorot(ks[3], (h * dh, d_out), cfg.dtype),
                "b": jnp.zeros((d_out,), cfg.dtype),
            }
        elif cfg.model == "rgcn":
            p = {
                "w_self": _glorot(ks[0], (d_in, d_out), cfg.dtype),
                "w_rel": _glorot(ks[1], (cfg.num_relations, d_in, d_out), cfg.dtype),
                "b": jnp.zeros((d_out,), cfg.dtype),
            }
        else:
            raise ValueError(f"unknown gnn model {cfg.model!r}")
        layers.append(p)
    return {"layers": layers}


def _gather(Ht: jax.Array, idx: jax.Array) -> jax.Array:
    """Row gather with -1 -> zeros."""
    out = Ht[jnp.clip(idx, 0)]
    return jnp.where((idx >= 0)[..., None], out, 0.0)


def _masked_mean(x: jax.Array, mask: jax.Array) -> jax.Array:
    s = jnp.sum(jnp.where(mask[..., None], x, 0.0), axis=-2)
    n = jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1)
    return s / n


def layer_apply(
    p: dict,
    cfg: GNNConfig,
    l: int,
    Ht: jax.Array,
    self_idx: jax.Array,
    nbr_idx: jax.Array,
    mask: jax.Array,
    etypes,
) -> jax.Array:
    """One bipartite GNN layer: (cap_tilde, d_in) -> (cap_l, d_out)."""
    # plan layer 0 emits logits (no activation); deeper layers use ReLU
    act = (lambda x: x) if l == 0 else jax.nn.relu
    h_self = _gather(Ht, self_idx)              # (n, d_in)
    h_nbr = _gather(Ht, nbr_idx)                # (n, w, d_in)
    if cfg.model == "gcn":
        # mean over {self} ∪ N(s)
        deg = jnp.sum(mask, axis=-1, keepdims=True) + 1
        agg = (jnp.sum(jnp.where(mask[..., None], h_nbr, 0.0), -2) + h_self) / deg
        return act(agg @ p["w"] + p["b"])
    if cfg.model == "sage":
        agg = _masked_mean(h_nbr, mask)
        return act(h_self @ p["w_self"] + agg @ p["w_nbr"] + p["b"])
    if cfg.model == "gat":
        h = cfg.num_heads
        z_self = (h_self @ p["w"]).reshape(*h_self.shape[:-1], h, -1)   # (n,h,dh)
        z_nbr = (h_nbr @ p["w"]).reshape(*h_nbr.shape[:-1], h, -1)     # (n,w,h,dh)
        e_dst = jnp.einsum("nhd,hd->nh", z_self, p["a_dst"])           # (n,h)
        e_src = jnp.einsum("nwhd,hd->nwh", z_nbr, p["a_src"])          # (n,w,h)
        e = jax.nn.leaky_relu(e_src + e_dst[:, None, :], 0.2)
        e = jnp.where(mask[..., None], e, -1e9)
        alpha = jax.nn.softmax(e, axis=1)
        alpha = jnp.where(mask[..., None], alpha, 0.0)
        agg = jnp.einsum("nwh,nwhd->nhd", alpha, z_nbr)
        agg = agg.reshape(*agg.shape[:-2], -1)                          # (n, h*dh)
        self_part = z_self.reshape(*z_self.shape[:-2], -1)
        return act((agg + self_part) @ p["w_out"] + p["b"])
    if cfg.model == "rgcn":
        out = h_self @ p["w_self"]
        et = etypes if etypes is not None else jnp.zeros(mask.shape, jnp.int32)
        for r in range(cfg.num_relations):
            m_r = mask & (et == r)
            agg_r = _masked_mean(h_nbr, m_r)
            out = out + agg_r @ p["w_rel"][r]
        return act(out + p["b"])
    raise ValueError(cfg.model)


def gnn_apply(
    params: dict,
    cfg: GNNConfig,
    plan_layers,            # sequence of layer blocks (Minibatch or Coop)
    H_input: jax.Array,     # embeddings for the deepest frontier
    provide: Callable[[int, jax.Array], jax.Array] = lambda l, H: H,
) -> jax.Array:
    """Forward pass over an L-layer plan; returns seed logits (cap_0, C).

    ``provide(l, H)`` converts owned embeddings into request-side
    embeddings for layer ``l`` (identity for Independent Minibatching,
    ``cooperative.redistribute`` for Cooperative).
    """
    H = H_input
    for l in reversed(range(cfg.num_layers)):
        blk = plan_layers[l]
        with jax.named_scope(f"gnn.layer{l}"):
            Ht = provide(l, H)
            H = layer_apply(
                params["layers"][l], cfg, l, Ht, blk.self_idx, blk.nbr_idx,
                blk.mask, blk.etypes,
            )
    return H


def gnn_apply_cooperative(
    params: dict,
    cfg: GNNConfig,
    ex,                     # cooperative.Executor
    plan_layers,            # CoopLayer blocks
    H_input: jax.Array,     # per-PE owned input embeddings
    tilde_caps,             # static S~ capacities per layer
) -> jax.Array:
    """Cooperative forward (Alg. 1): redistribute, then per-PE compute.

    The redistribution is a *global* exchange (all PEs participate);
    the bipartite layer compute is per-PE and goes through ``ex.pe`` so
    the same code runs under SimExecutor (vmap) and ShardExecutor
    (shard_map).
    """
    from repro.core.cooperative import redistribute

    H = H_input
    for l in reversed(range(cfg.num_layers)):
        blk = plan_layers[l]
        p_l = params["layers"][l]

        def apply_one(Ht, si, ni, mk, et=None, _p=p_l, _l=l):
            return layer_apply(_p, cfg, _l, Ht, si, ni, mk, et)

        with jax.named_scope(f"gnn.layer{l}"):
            args = (redistribute(ex, blk, H, tilde_caps[l]),
                    blk.self_idx, blk.nbr_idx, blk.mask)
            if blk.etypes is not None:
                args += (blk.etypes,)
            H = ex.pe(apply_one, *args)
    return H
