"""R-GCN (Schlichtkrull et al., ESWC 2018) in the benchmark, with ``R``
relations, one per entry of the configuration's
``graph.relation_shares``.

* program: ``num_relations = R``;
* parameters: ``w_self`` and ``w_rel`` (``R`` matrices), Glorot-uniform
  from the layer's first and second subkeys, and a zero bias ``b``;
* layer: ``h_s W_self + sum_r mean_r(N_r(s)) W_r + b`` over the sampled
  edges of each relation; ReLU on all but the output layer;
* FLOPs forward, with ``n = |S_l|``, ``e = E_l``, ``k`` input and ``m``
  output width: aggregation ``(e + R n) k`` and ``R + 1`` matmuls,
  ``2 n k m (R + 1)``.
"""
import jax
import jax.numpy as jnp

import flops
from reference import glorot


def relations(cfg: dict) -> int:
    return len(cfg["graph"]["relation_shares"])


def program_args(cfg: dict) -> dict:
    return {"num_relations": relations(cfg)}


def init_layer(ks, d_in: int, d_out: int, cfg: dict) -> dict:
    return {"w_self": glorot(ks[0], (d_in, d_out)),
            "w_rel": glorot(ks[1], (relations(cfg), d_in, d_out)),
            "b": jnp.zeros((d_out,), jnp.float32)}


def layer(p, h, L, is_out, dtype, prec, cfg):
    n = L["self_idx"].shape[0]
    w = L["w"].astype(dtype)
    h_self = h[L["self_idx"]]
    msg = h[L["src"]] * w[:, None]
    out = jnp.matmul(h_self, p["w_self"], precision=prec)
    for r in range(relations(cfg)):
        wr = jnp.where(L["etype"] == r, w, 0)
        cnt = jax.ops.segment_sum(wr, L["dst"], n)
        s = jax.ops.segment_sum(msg * (L["etype"] == r)[:, None].astype(dtype),
                                L["dst"], n)
        out = out + jnp.matmul(s / jnp.maximum(cnt, 1)[:, None], p["w_rel"][r],
                               precision=prec)
    out = out + p["b"]
    return out if is_out else jax.nn.relu(out)


def step_flops(sizes: list, edges: list, cfg: dict) -> float:
    R = relations(cfg)
    return flops.train_step_flops(
        sizes, edges, cfg,
        lambda n, e, k, m: ((e + R * n) * k, 2 * n * k * m * (R + 1)))
