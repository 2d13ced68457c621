"""Device milliseconds per step of collective operations (the
cooperative all-to-alls and the gradient all-reduce); nothing where the
step has none."""
import opclass


def read(ctx):
    return opclass.ms_per_step(ctx, "collective", none_if_zero=True)
