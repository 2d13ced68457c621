"""Percent of the step's top-level device time in ops under no program
scope: what the scope-based metrics cannot attribute."""
import scopes


def read(ctx):
    return scopes.unscoped_share(ctx)
