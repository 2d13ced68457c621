"""Seeds trained per second over the whole window: global batch times
steps of the timed call, over the seconds from its step program being
ready to its return."""


def read(ctx):
    return ctx["seeds"] / ctx["window_s"]
