"""Device milliseconds per step of the forward pass: ops under the
program's ``gnn.layer<n>`` and ``gnn.loss`` scopes and not under a
``transpose(`` transform."""
import scopes


def read(ctx):
    return scopes.part_ms(ctx, "gnn.forward")
