"""Plain float32 reference of GAT as ``layers.py`` runs it.

GAT (Veličković et al., ICLR 2018, arXiv 1710.10903) as PyG's
``GATConv`` computes it, with the linear skip of PyG's
``examples/ogbn_products_gat.py``, written over an explicit edge list
with no padding and no reordering: every source row is projected, the
scores are taken on the projected rows, and the softmax over each
destination's in-edges is a segment max and a segment sum.  The
destination's self-loop is one more edge.

A plan here is what the program's plan means: ``x`` the input rows (the
features of ``S^L``); for each plan layer ``l``, ``self_idx`` (the row of
each destination of ``S^l`` among the layer's source rows ``S^{l+1}``)
and the sampled edges ``src -> dst`` (a source row, a destination row).
Layer ``L-1`` reads ``x``; layer ``0`` emits the logits.

Everything runs under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul otherwise rounds its inputs to bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layer(p: dict, x, self_idx, src, dst, output: bool):
    """One layer: source rows ``x`` (n_src, k) -> (len(self_idx), d_out).

    Hidden layers concatenate the H heads and apply ELU; the output layer
    (``output``) averages them and applies nothing."""
    n = self_idx.shape[0]
    H, C = p["att_src"].shape
    src = jnp.concatenate([src, self_idx])            # the self-loops
    dst = jnp.concatenate([dst, jnp.arange(n)])
    z = (x @ p["w"]).reshape(-1, H, C)                # z_j = W x_j
    z_dst = z[self_idx]
    e = jax.nn.leaky_relu(jnp.sum(z[src] * p["att_src"], -1)
                          + jnp.sum(z_dst[dst] * p["att_dst"], -1), 0.2)
    top = jax.lax.stop_gradient(jax.ops.segment_max(e, dst, n))
    ex = jnp.exp(e - top[dst])                        # (E + n, H)
    alpha = ex / jax.ops.segment_sum(ex, dst, n)[dst]
    o = jax.ops.segment_sum(alpha[..., None] * z[src], dst, n)   # (n, H, C)
    o = o.mean(axis=1) if output else o.reshape(n, H * C)
    out = o + p["b"] + x[self_idx] @ p["w_skip"] + p["b_skip"]
    return out if output else jax.nn.elu(out)


def forward(params: dict, x, layers: list):
    """Logits of the rows of ``S^0``; ``layers[l]`` holds ``self_idx``,
    ``src`` and ``dst`` of plan layer ``l``."""
    with jax.default_matmul_precision("highest"):
        h = x
        for l in reversed(range(len(layers))):
            h = layer(params["layers"][l], h, output=l == 0, **layers[l])
        return h


def loss(params: dict, x, layers: list, labels):
    """Mean softmax cross entropy over the rows of ``S^0``."""
    logits = forward(params, x, layers)
    ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    return jnp.mean(ce)


def loss_and_grad(params: dict, x, layers: list, labels):
    """``(loss, d loss / d params)`` in float32 at the highest precision."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, x, layers, labels)
