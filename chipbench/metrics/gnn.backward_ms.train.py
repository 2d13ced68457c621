"""Device milliseconds per step of the backward pass: ops under the
program's ``gnn.*`` scopes and a ``transpose(`` transform, and the
gradient all-reduce (``exchange.grads`` outside every layer)."""
import scopes


def read(ctx):
    return scopes.part_ms(ctx, "gnn.backward")
