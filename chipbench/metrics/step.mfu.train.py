"""Model FLOP utilisation of the window: forward and backward FLOPs of
every window step, counted from the real frontier sizes of the
program's own plans (``flops.step_flops``), over window seconds times
chips times the chip's bf16 peak."""
import flops


def read(ctx):
    counts = ctx.get("plan_counts")
    if counts is None:
        return None
    cfg = ctx["config"]
    L = cfg["num_layers"]
    total = sum(flops.step_flops(cfg, row[: L + 1], row[L + 1:]) for row in counts)
    peak = ctx["peaks"]["flops_bf16"] * ctx["chips"]
    return 100.0 * total / (ctx["window_s"] * peak)
