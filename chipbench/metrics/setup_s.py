"""Seconds from process start to the start of the window: data
generation, the warm-up call, and the timed call's own set-up."""


def read(ctx):
    return ctx["setup_s"]
