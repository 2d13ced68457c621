"""Model FLOPs of one training step, from the real (unpadded) sizes.

Plan layer ``l`` maps the rows of frontier ``S_{l+1}`` to those of
``S_l`` over ``E_l`` sampled edges; layer ``L-1`` reads the raw
features and layer ``0`` emits the logits.  Counted per layer, with
``n = |S_l|``, ``e = E_l``, ``k`` input and ``m`` output width:

* GCN forward: mean aggregation ``(e + 2n) k`` (sum of neighbours, add
  self, divide) and the matmul ``2 n k m``;
* R-GCN forward, ``R`` relations: aggregation ``(e + R n) k`` and
  ``R + 1`` matmuls, ``2 n k m (R + 1)``;
* backward: the weight gradient of every matmul (as many FLOPs as its
  forward); the input gradient of the matmuls and of the aggregation
  only where the layer's input is itself trained, i.e. not in layer
  ``L-1``, whose input is the feature table.

Padding, bias, activation, loss and optimizer FLOPs are not counted.
"""
from __future__ import annotations


def step_flops(model: str, sizes: list, edges: list, in_dim: int,
               hidden: int, classes: int, num_relations: int = 1) -> float:
    """FLOPs of forward + backward for frontier sizes ``sizes`` (S_0 ..
    S_L) and sampled edge counts ``edges`` (E_0 .. E_{L-1})."""
    L = len(edges)
    total = 0.0
    for l in range(L):
        n, e = float(sizes[l]), float(edges[l])
        k = in_dim if l == L - 1 else hidden
        m = classes if l == 0 else hidden
        if model == "gcn":
            agg, mm = (e + 2 * n) * k, 2 * n * k * m
        elif model == "rgcn":
            agg = (e + num_relations * n) * k
            mm = 2 * n * k * m * (num_relations + 1)
        else:
            raise ValueError(f"no FLOP count for model {model!r}")
        trained_input = l < L - 1
        total += agg + mm              # forward
        total += mm                    # weight gradients
        if trained_input:
            total += mm + agg          # input gradients
    return total
