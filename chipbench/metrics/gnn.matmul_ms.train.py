"""Device milliseconds per step of dot and convolution operations and
the fusions that hold them (the GNN layers' matmuls, both passes)."""
import opclass


def read(ctx):
    return opclass.ms_per_step(ctx, "matmul")
