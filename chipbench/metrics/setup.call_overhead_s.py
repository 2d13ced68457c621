"""Seconds from entering the timed ``train_gnn`` call to its step
program being ready: engine build, trace, lowering, cache load."""


def read(ctx):
    return ctx["call_overhead_s"]
