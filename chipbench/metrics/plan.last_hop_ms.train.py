"""Device milliseconds per step under the last hop's scope,
``plan.hop<L>``: the hop that samples the input frontier, the largest."""
import scopes


def read(ctx):
    last = "plan.hop%d" % ctx["config"]["num_layers"]
    return scopes.ms_per_step(ctx, lambda s, b: bool(s) and s[0] == last)
