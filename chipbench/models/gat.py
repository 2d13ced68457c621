"""GAT (Veličković et al., ICLR 2018, arXiv 1710.10903) in the benchmark,
as PyG's ``GATConv`` and ``examples/ogbn_products_gat.py`` run it, with
``H`` heads (the configuration's ``num_heads``).

* program: ``num_heads = H``;
* parameters: ``w`` (k, H·C) with no bias, ``att_src`` and ``att_dst``
  (H, C), ``w_skip`` (k, m), Glorot-uniform from the layer's subkeys 0-3,
  and zero biases ``b`` and ``b_skip``; hidden layers concatenate H heads
  of ``C = m / H``, the output layer averages H heads of ``C = m``;
* layer: ``z_j = W x_j``, per head ``e_ij = LeakyReLU_0.2(att_src · z_j +
  att_dst · z_i)``, a softmax over ``{i} + sampled N(i)`` and ``o_i = sum_j
  a_ij z_j``; heads concatenated (output layer: averaged) plus ``b``, plus
  the skip ``x_i W_skip + b_skip``; ELU on all but the output layer;
* FLOPs forward, with ``n = |S_l|``, ``e = E_l``, ``k`` input and ``m``
  output width, counted in the form that reduces before it projects, so
  that the count does not depend on which form runs: the scores of the
  ``e + n`` slots and of the ``n`` destinations, ``x · (W att)``, ``2 k H
  (e + 2 n)``, and the weighted reduce of ``e + n`` rows per head, ``2 k H
  (e + n)``, as the aggregation; each head's projection ``2 n k H C`` and
  the skip ``2 n k m`` as the matmuls.

``attention_bytes`` is the HBM traffic the attention (scores and
weighted reduce) cannot go below, read by ``gnn.attn_hbm_share.train``.
"""
import jax
import jax.numpy as jnp

import flops
from reference import glorot


def program_args(cfg: dict) -> dict:
    return {"num_heads": cfg["num_heads"]}


def head_width(cfg: dict, m: int, output: bool) -> int:
    """C: the output layer's heads are ``m`` wide, a hidden layer's ``m / H``."""
    return m if output else m // cfg["num_heads"]


def init_layer(ks, d_in: int, d_out: int, cfg: dict) -> dict:
    if cfg["hidden_dim"] == cfg["num_classes"]:
        raise ValueError("gat: the output layer is told by its width; "
                         "hidden_dim must differ from num_classes")
    output = d_out == cfg["num_classes"]
    H, C = cfg["num_heads"], head_width(cfg, d_out, output)
    return {"w": glorot(ks[0], (d_in, H * C)),
            "att_src": glorot(ks[1], (H, C)),
            "att_dst": glorot(ks[2], (H, C)),
            "b": jnp.zeros((C if output else H * C,), jnp.float32),
            "w_skip": glorot(ks[3], (d_in, d_out)),
            "b_skip": jnp.zeros((d_out,), jnp.float32)}


def layer(p, h, L, is_out, dtype, prec, cfg):
    """Over ``reference.pad_plan``'s edge list: padding edges (``w = 0``)
    drop out of the softmax, and each destination's self-loop is its row
    ``self_idx``."""
    n = L["self_idx"].shape[0]
    H, C = p["att_src"].shape
    real = L["w"] > 0
    z = jnp.matmul(h, p["w"], precision=prec).reshape(-1, H, C)
    z_dst = z[L["self_idx"]]
    s_src = jnp.sum(z * p["att_src"], -1)                # (n_src, H)
    s_dst = jnp.sum(z_dst * p["att_dst"], -1)            # (n, H)
    e = jax.nn.leaky_relu(s_src[L["src"]] + s_dst[L["dst"]], 0.2)
    e = jnp.where(real[:, None], e, -jnp.inf)
    e_self = jax.nn.leaky_relu(s_src[L["self_idx"]] + s_dst, 0.2)
    top = jax.lax.stop_gradient(
        jnp.maximum(jax.ops.segment_max(e, L["dst"], n), e_self))
    x = jnp.exp(e - top[L["dst"]])
    x_self = jnp.exp(e_self - top)
    den = jax.ops.segment_sum(x, L["dst"], n) + x_self
    o = (jax.ops.segment_sum(x[..., None] * z[L["src"]], L["dst"], n)
         + x_self[..., None] * z_dst) / den[..., None]   # (n, H, C)
    o = o.mean(axis=1) if is_out else o.reshape(n, H * C)
    out = (o + p["b"] + jnp.matmul(h[L["self_idx"]], p["w_skip"], precision=prec)
           + p["b_skip"])
    return out if is_out else jax.nn.elu(out)


def _widths(cfg: dict, l: int, L: int):
    k = cfg["feature_dim"] if l == L - 1 else cfg["hidden_dim"]
    m = cfg["num_classes"] if l == 0 else cfg["hidden_dim"]
    return k, m, cfg["num_heads"] * head_width(cfg, m, l == 0)


def step_flops(sizes: list, edges: list, cfg: dict) -> float:
    H = cfg["num_heads"]

    def counts(n, e, k, m):
        HC = H * head_width(cfg, m, m == cfg["num_classes"])
        return 2 * k * H * (e + 2 * n) + 2 * k * H * (e + n), 2 * n * k * (HC + m)

    return flops.train_step_flops(sizes, edges, cfg, counts)


def attention_bytes(sizes: list, edges: list, cfg: dict) -> float:
    """Bytes of one forward pass of the attention, float32, summed over
    the layers: each source row of ``S_{l+1}`` read once at ``min(k, H C)``;
    an int32 index and ``H`` weights for each of the ``E_l + |S_l|`` edge
    and self slots; each destination row written once at ``min(H k, H C)``."""
    L, H = len(edges), cfg["num_heads"]
    total = 0.0
    for l in range(L):
        k, _, HC = _widths(cfg, l, L)
        n, e, n_src = float(sizes[l]), float(edges[l]), float(sizes[l + 1])
        total += 4 * (n_src * min(k, HC) + (e + n) * (1 + H) + n * min(H * k, HC))
    return total
