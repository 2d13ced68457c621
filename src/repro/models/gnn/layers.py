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
            # GATConv: lin (no bias), att_src / att_dst, bias; plus the
            # linear skip.  Hidden layers concatenate H heads of C = d_out
            # / H, the output layer averages H heads of C = d_out.
            h = cfg.num_heads
            if l > 0 and d_out % h:
                raise ValueError(f"gat: hidden_dim {d_out} is not a multiple "
                                 f"of num_heads {h}")
            c = d_out if l == 0 else d_out // h
            p = {
                "w": _glorot(ks[0], (d_in, h * c), cfg.dtype),
                "att_src": _glorot(ks[1], (h, c), cfg.dtype),
                "att_dst": _glorot(ks[2], (h, c), cfg.dtype),
                "b": jnp.zeros((c if l == 0 else h * c,), cfg.dtype),
                "w_skip": _glorot(ks[3], (d_in, d_out), cfg.dtype),
                "b_skip": jnp.zeros((d_out,), cfg.dtype),
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
    if cfg.model == "gat":
        return _gat_layer(p, l, Ht, self_idx, nbr_idx, mask)
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
    if cfg.model == "rgcn":
        out = h_self @ p["w_self"]
        et = etypes if etypes is not None else jnp.zeros(mask.shape, jnp.int32)
        for r in range(cfg.num_relations):
            m_r = mask & (et == r)
            agg_r = _masked_mean(h_nbr, m_r)
            out = out + agg_r @ p["w_rel"][r]
        return act(out + p["b"])
    raise ValueError(cfg.model)


@jax.custom_vjp
def _slot_scores(h_nbr, a):
    """Each slot's scores from its gathered row: (n, w, k) · (k, H) -> (n, w, H)."""
    return jnp.einsum("nwk,kh->nwh", h_nbr, a)


def _slot_scores_fwd(h_nbr, a):
    return _slot_scores(h_nbr, a), (h_nbr, a)


def _slot_scores_bwd(res, g):
    # a's gradient as one contraction per destination, then a sum over
    # destinations.  Left to autodiff it is one contraction over every
    # slot, for which XLA on the TPU copies h_nbr into a transposed layout
    # (GAT on ogbn-products, layer 2 on a v5e: 4.4 ms a step and 1.4 GB
    # more step memory); the barrier keeps XLA from merging the two back.
    h_nbr, a = res
    g_a = jax.lax.optimization_barrier(jnp.einsum("nwh,nwk->nhk", g, h_nbr))
    return (jnp.einsum("nwh,kh->nwk", g, a).astype(h_nbr.dtype),
            g_a.sum(0).T.astype(a.dtype))


_slot_scores.defvjp(_slot_scores_fwd, _slot_scores_bwd)


def _gat_layer(p, l, Ht, self_idx, nbr_idx, mask):
    """GAT (Veličković et al., ICLR 2018) as PyG's ``GATConv`` with a
    linear skip, over the slots ``{i} ∪ sampled N(i)`` of each destination:

        z_j = W x_j
        α^h = softmax_j LeakyReLU_0.2(att_src^h · z_j^h + att_dst^h · z_i^h)
        o_i = concat_h Σ_j α^h_ij z_j^h + b   (layer 0: mean_h)
        out = ELU(o_i + W_skip x_i + b_skip)  (layer 0: no ELU)

    No gathered copy is ever projected.  The scores are ``att · (W x) =
    x · (W att)``, taken per slot from the rows the layer gathers anyway
    for the reduce (the self slot's from ``h_self``).  Scores computed per
    row and then gathered would put a scatter-add of every slot's score
    gradient into the backward pass; per slot, ``W att``'s gradient is a
    dense contraction over the slots (``_slot_scores``).  The raw rows are
    reduced per head with α to (n, H, k), and each head is then projected
    with its (k, C) slice of W.
    """
    k = Ht.shape[-1]
    H, C = p["att_src"].shape
    w3 = p["w"].reshape(k, H, C)
    h_self = _gather(Ht, self_idx)                                # (n, k)
    with jax.named_scope("gnn.attn.score"):
        h_nbr = _gather(Ht, nbr_idx)                              # (n, w, k)
        a_src = jnp.einsum("khc,hc->kh", w3, p["att_src"])
        s_dst = h_self @ jnp.einsum("khc,hc->kh", w3, p["att_dst"])
        e_self = jax.nn.leaky_relu(h_self @ a_src + s_dst, 0.2)
        e_nbr = jax.nn.leaky_relu(_slot_scores(h_nbr, a_src) + s_dst[:, None], 0.2)
        e_nbr = jnp.where(mask[..., None], e_nbr, -jnp.inf)      # (n, w, H)
        top = jax.lax.stop_gradient(jnp.maximum(e_self, jnp.max(e_nbr, axis=1)))
        x_self = jnp.exp(e_self - top)
        x_nbr = jnp.exp(e_nbr - top[:, None])
        den = x_self + jnp.sum(x_nbr, axis=1)
        a_self, a_nbr = x_self / den, x_nbr / den[:, None]
    with jax.named_scope("gnn.attn.aggregate"):
        agg = (a_self[..., None] * h_self[:, None]
               + jnp.einsum("nwh,nwk->nhk", a_nbr, h_nbr))
    out = jnp.einsum("nhk,khc->nhc", agg, w3)                    # (n, H, C)
    out = out.mean(axis=1) if l == 0 else out.reshape(-1, H * C)
    out = out + p["b"] + h_self @ p["w_skip"] + p["b_skip"]
    return out if l == 0 else jax.nn.elu(out)


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
