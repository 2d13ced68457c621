"""Device milliseconds per step under the program's ``fetch.inputs``
scope: the input feature rows of the plan."""
import scopes


def read(ctx):
    return scopes.part_ms(ctx, "fetch.inputs")
