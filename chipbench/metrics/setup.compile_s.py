"""Seconds of backend compilation before the window, from JAX's
monitoring events; programs read from the persistent cache count 0."""


def read(ctx):
    return ctx["compile_s"]
