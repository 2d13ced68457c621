"""Device milliseconds per step of GAT's edge attention, forward and
backward: ops under the program's ``gnn.attn.score`` (per-row scores,
their slot gather, LeakyReLU, the edge softmax) or ``gnn.attn.aggregate``
(the weighted gather-reduce) scope, both inside ``gnn.layer<n>``.

``scopes.SCOPE`` does not know these names, so this reader matches them
itself; like ``scopes.Scopes.of``, an instruction whose own metadata
names no scope takes that of the first instruction of the computations
it calls that has one, the root first.  None where no op carries either
name (a model without attention, a program from before the names).
"""
import re

import scopes

ATTN = re.compile(r"^gnn\.attn\.(?:score|aggregate)$")


def parse(op_name: str) -> tuple:
    """(any scope, an attention scope, backward) of one ``op_name``."""
    named = attn = backward = False
    for comp in op_name.split("/"):
        backward |= comp.startswith("transpose(")
        while m := scopes.WRAPPED.match(comp):
            comp = m.group(1)
        attn |= bool(ATTN.match(comp))
        named |= bool(ATTN.match(comp) or scopes.SCOPE.match(comp))
    return named, attn, backward


class Attention:
    """(any scope, attention scope, backward) of every instruction."""

    def __init__(self, sc: "scopes.Scopes"):
        self.sc = sc
        self._memo: dict = {}
        self.any = any(parse(n)[1] for n in sc.own.values())

    def of(self, name: str) -> tuple:
        if name not in self._memo:
            self._memo[name] = (False, False, False)   # cycles: a body never calls its loop
            found = parse(self.sc.own.get(name, ""))
            if not found[0]:
                found = next((f for c in self.sc.calls.get(name, ())
                              for i in self.sc.body.get(c, ())
                              if (f := self.of(i))[0]), found)
            self._memo[name] = found
        return self._memo[name]


def attention(ctx):
    """The trace and the ``Attention`` of its HLO; None where nothing was
    traced or no op carries an attention scope."""
    red, hlo = ctx.get("trace"), ctx.get("hlo")
    if red is None or hlo is None:
        return None, None
    att = ctx.get("attention")
    if att is None:
        sc = ctx.get("scopes")
        if sc is None:
            sc = ctx["scopes"] = scopes.Scopes(hlo)
        att = ctx["attention"] = Attention(sc)
    return red, (att if att.any else None)


def ms_per_step(ctx, keep=lambda backward: True):
    red, att = attention(ctx)
    if att is None:
        return None
    s = red.category_s(lambda op: (f := att.of(op.short))[1] and keep(f[2]))
    return 1e3 * s / ctx["trace_steps"]


def read(ctx):
    return ms_per_step(ctx)
