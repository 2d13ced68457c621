"""GAT's edge attention as a share of its HBM roofline: the bytes its
forward pass cannot go below (``attention_bytes`` of the model's module,
from the real frontier sizes and edge counts of the window's steps, in
float32) over the device seconds of the forward ops under
``gnn.attn.score`` or ``gnn.attn.aggregate`` times the chip's HBM
bandwidth.  The bytes are a lower bound, so the share stays under 100 %.
None where no op carries an attention scope."""
import os

import byname

ATTN = byname.load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "gnn.attn_ms.train.py"), "chipbench_metric_gnn_attn_ms")


def read(ctx):
    counts = ctx.get("plan_counts")
    fwd_ms = ATTN.ms_per_step(ctx, lambda backward: not backward)
    if counts is None or not fwd_ms:
        return None
    cfg = ctx["config"]
    L = cfg["num_layers"]
    model = byname.model(cfg["model"])
    step_bytes = sum(model.attention_bytes(row[: L + 1], row[L + 1:], cfg)
                     for row in counts) / len(counts)
    return 100.0 * step_bytes / (fwd_ms / 1e3 * ctx["peaks"]["hbm_bytes_per_s"])
