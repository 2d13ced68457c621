"""Device milliseconds per step of gather, scatter and dynamic-slice
operations and the fusions rooted in them (feature fetch, neighbour
aggregation and their gradients)."""
import opclass


def read(ctx):
    return opclass.ms_per_step(ctx, "gather")
