"""Device milliseconds per step under the program's ``optim.update``
scope: the Adam update."""
import scopes


def read(ctx):
    return scopes.part_ms(ctx, "optim.update")
