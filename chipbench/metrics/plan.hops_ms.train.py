"""Device milliseconds per step under the program's ``plan.hop<n>``
scopes: every sampling hop (sampling, dedup and its lookup, bucketing,
the id all-to-all)."""
import scopes


def read(ctx):
    return scopes.part_ms(ctx, "plan.hops")
