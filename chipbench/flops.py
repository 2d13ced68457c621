"""Model FLOPs of one training step, from the real (unpadded) sizes.

Plan layer ``l`` maps the rows of frontier ``S_{l+1}`` to those of
``S_l`` over ``E_l`` sampled edges; layer ``L-1`` reads the raw
features and layer ``0`` emits the logits.  Each model's forward count
of one layer, as an aggregation and a matmul term, is in its module
(``chipbench/models/<model>.py``).  The rules common to all:

* backward: the weight gradient of every matmul (as many FLOPs as its
  forward); the input gradient of the matmuls and of the aggregation
  only where the layer's input is itself trained, i.e. not in layer
  ``L-1``, whose input is the feature table.

Padding, bias, activation, loss and optimizer FLOPs are not counted.
"""
from __future__ import annotations

import byname


def step_flops(cfg: dict, sizes: list, edges: list) -> float:
    """FLOPs of forward + backward for frontier sizes ``sizes`` (S_0 ..
    S_L) and sampled edge counts ``edges`` (E_0 .. E_{L-1}) of the
    configuration ``cfg``'s model."""
    return byname.model(cfg["model"]).step_flops(sizes, edges, cfg)


def train_step_flops(sizes: list, edges: list, cfg: dict, layer_counts) -> float:
    """The rules above over every layer; ``layer_counts(n, e, k, m)``
    gives one layer's forward ``(aggregation, matmul)`` FLOPs for
    ``n = |S_l|``, ``e = E_l``, ``k`` input and ``m`` output width."""
    L = len(edges)
    total = 0.0
    for l in range(L):
        n, e = float(sizes[l]), float(edges[l])
        k = cfg["feature_dim"] if l == L - 1 else cfg["hidden_dim"]
        m = cfg["num_classes"] if l == 0 else cfg["hidden_dim"]
        agg, mm = layer_counts(n, e, k, m)
        trained_input = l < L - 1
        total += agg + mm              # forward
        total += mm                    # weight gradients
        if trained_input:
            total += mm + agg          # input gradients
    return total
