"""Device milliseconds per step under the program's ``plan.seed_draw``
scope: the seed draw (pool permutation) and the seed frontier."""
import scopes


def read(ctx):
    return scopes.part_ms(ctx, "plan.seed_draw")
