"""Mean device idle milliseconds between consecutive executions of the
step on each chip, where a ``train_gnn.step`` host span covers the gap:
the host's step conversion, dispatch and loss read-back."""
import scopes


def read(ctx):
    return scopes.step_gap_ms(ctx)
