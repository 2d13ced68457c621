"""Device milliseconds per step of sort operations (seed draw and the
frontier dedup of every sampling hop)."""
import opclass


def read(ctx):
    return opclass.ms_per_step(ctx, "sort")
