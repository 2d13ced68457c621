"""Share of the traced step executions' span in which no operation ran
on the chip (1 - busy / span), averaged over the chips."""


def read(ctx):
    red = ctx.get("trace")
    return None if red is None else 100.0 * red.idle_share
