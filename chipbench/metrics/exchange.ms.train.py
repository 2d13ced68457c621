"""Device milliseconds per step of the cooperative exchanges: ops under
an ``exchange.ids``, ``exchange.embeddings`` or ``exchange.grads`` scope
anywhere in their scope path (the all-to-alls of every sampling hop and
layer, forward and backward, and the gradient all-reduce)."""
import scopes


def read(ctx):
    return scopes.ms_per_step(
        ctx, lambda s, backward: any(x.startswith("exchange.") for x in s))
