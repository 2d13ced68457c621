"""GCN (Kipf & Welling, ICLR 2017) in the benchmark.

* program: no ``GNNConfig`` field beyond the common ones;
* parameters: ``w`` (Glorot-uniform from the layer's first subkey) and a
  zero bias ``b``;
* layer: the mean over ``{s} + sampled N(s)`` followed by ``x W + b``;
  ReLU on all but the output layer;
* FLOPs forward, with ``n = |S_l|``, ``e = E_l``, ``k`` input and ``m``
  output width: the mean aggregation ``(e + 2n) k`` (sum of neighbours,
  add self, divide) and the matmul ``2 n k m``.
"""
import jax
import jax.numpy as jnp

import flops
from reference import glorot


def program_args(cfg: dict) -> dict:
    return {}


def init_layer(ks, d_in: int, d_out: int, cfg: dict) -> dict:
    return {"w": glorot(ks[0], (d_in, d_out)),
            "b": jnp.zeros((d_out,), jnp.float32)}


def layer(p, h, L, is_out, dtype, prec, cfg):
    n = L["self_idx"].shape[0]
    w = L["w"].astype(dtype)
    h_self = h[L["self_idx"]]
    msg = h[L["src"]] * w[:, None]
    cnt = jax.ops.segment_sum(w, L["dst"], n)
    agg = (jax.ops.segment_sum(msg, L["dst"], n) + h_self) / (cnt + 1)[:, None]
    out = jnp.matmul(agg, p["w"], precision=prec) + p["b"]
    return out if is_out else jax.nn.relu(out)


def step_flops(sizes: list, edges: list, cfg: dict) -> float:
    return flops.train_step_flops(
        sizes, edges, cfg, lambda n, e, k, m: ((e + 2 * n) * k, 2 * n * k * m))
