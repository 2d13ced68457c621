"""Device milliseconds per step of while loops: the binary searches
that resolve every sampled id's position in the next frontier
(``searchsorted`` in the frontier lookup), with their bodies."""
import opclass


def read(ctx):
    return opclass.ms_per_step(ctx, "loop")
