"""The program's own scopes on the step's device ops, from its HLO.

The program names its layers with ``jax.named_scope`` (the vocabulary is
in ``docs/architecture.md``, "Tracing"): ``plan.seed_draw``,
``plan.hop1`` .. ``plan.hop<L>``, ``fetch.inputs``, ``gnn.layer0`` ..
``gnn.layer<L-1>``, ``gnn.loss``, ``optim.update`` and, around the
collectives, ``exchange.ids``, ``exchange.embeddings`` and
``exchange.grads``.  The names reach the optimized HLO as each
instruction's ``metadata={op_name="..."}``, wrapped in the transforms
that were applied to them, e.g.
``jit(train_step)/transpose(jvp(vmap(gnn.layer0)))/mul``.  A component
is unwrapped to its innermost name; an op under ``transpose(`` is part
of the backward pass.

A TPU trace names each top-level op by its instruction (``op.short``,
e.g. ``fusion.14``).  An instruction whose own metadata names no scope
(XLA drops it on many fusions) takes the scope of the first instruction
of the computations it calls that has one, the root first.

``PARTS`` partition the step: every top-level op falls in exactly one,
by its outermost scope other than ``exchange.*``: ``plan.seed_draw``,
``plan.hops`` (any ``plan.hop<n>``), ``fetch.inputs``, ``gnn.forward``
and ``gnn.backward`` (``gnn.*``, split by ``transpose(``),
``optim.update``, else ``unscoped``.  An op under ``exchange.*`` alone
is the gradient all-reduce after the backward pass and counts as
``gnn.backward``.

The program keeps the same vocabulary in ``repro/utils/scopes.py``;
this module holds its own copy because it also reads programs from
before the scopes, which have no such module.
"""
from __future__ import annotations

import re

from opclass import CALLS, COMP_HEAD

SCOPE = re.compile(r"^(?:plan\.(?:seed_draw|hop\d+)|fetch\.inputs|gnn\.(?:layer\d+|loss)"
                   r"|optim\.update|exchange\.(?:ids|embeddings|grads))$")
WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")
INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
PARTS = ("plan.seed_draw", "plan.hops", "fetch.inputs", "gnn.forward",
         "gnn.backward", "optim.update", "unscoped")


def parse(op_name: str) -> tuple:
    """(scopes outer to inner, backward) of one ``op_name``."""
    scopes, backward = [], False
    for comp in op_name.split("/"):
        backward |= comp.startswith("transpose(")
        while m := WRAPPED.match(comp):
            comp = m.group(1)
        if SCOPE.match(comp):
            scopes.append(comp)
    return tuple(scopes), backward


def part_of(scopes: tuple, backward: bool) -> str:
    for s in scopes:
        if s == "plan.seed_draw" or s == "fetch.inputs" or s == "optim.update":
            return s
        if s.startswith("plan.hop"):
            return "plan.hops"
        if s.startswith("gnn."):
            return "gnn.backward" if backward else "gnn.forward"
    return "gnn.backward" if scopes else "unscoped"


class Scopes:
    """Scopes of every instruction of the step's optimized HLO."""

    def __init__(self, hlo_text: str):
        self.own: dict = {}      # instruction -> op_name
        self.calls: dict = {}    # instruction -> called computations
        self.body: dict = {}     # computation -> instructions, root first
        comp = None
        for line in hlo_text.splitlines():
            head = COMP_HEAD.match(line)
            if head and " = " not in line:
                comp = head.group(1)
                self.body[comp] = []
                continue
            m = INSTR.match(line)
            if comp is None or m is None:
                continue
            name = m.group(2)
            meta = OP_NAME.search(line)
            self.own[name] = meta.group(1) if meta else ""
            self.calls[name] = CALLS.findall(line)
            if m.group(1):
                self.body[comp].insert(0, name)
            else:
                self.body[comp].append(name)
        self._memo: dict = {}
        self.named = any(parse(n)[0] for n in self.own.values())

    def of(self, name: str) -> tuple:
        """(scopes, backward) of an instruction."""
        if name not in self._memo:
            self._memo[name] = ((), False)   # cycles: a body never calls its loop
            found = parse(self.own.get(name, ""))
            if not found[0]:
                found = next((f for c in self.calls.get(name, ())
                              for i in self.body.get(c, ())
                              if (f := self.of(i))[0]), found)
            self._memo[name] = found
        return self._memo[name]


def _scopes(ctx):
    red, hlo = ctx.get("trace"), ctx.get("hlo")
    if red is None or hlo is None:
        return None, None
    sc = ctx.get("scopes")
    if sc is None:
        sc = ctx["scopes"] = Scopes(hlo)
    return red, (sc if sc.named else None)


def ms_per_step(ctx, keep):
    """Device milliseconds per step, per chip, of the top-level ops whose
    (scopes, backward) ``keep`` accepts; None where the program names no
    scope (a program from before the scopes) or nothing was traced."""
    red, sc = _scopes(ctx)
    if sc is None:
        return None
    s = red.category_s(lambda op: keep(*sc.of(op.short)))
    return 1e3 * s / ctx["trace_steps"]


def part_ms(ctx, *parts):
    """``ms_per_step`` of the ops in any of ``parts``."""
    return ms_per_step(ctx, lambda s, b: part_of(s, b) in parts)


def unscoped_share(ctx):
    """Percent of the top-level device time under no program scope."""
    red, sc = _scopes(ctx)
    if sc is None:
        return None
    total = red.category_s(lambda op: True)
    return 100.0 * red.category_s(lambda op: not sc.of(op.short)[0]) / total


def step_gap_ms(ctx):
    """Mean device idle time between consecutive step executions on each
    chip, over the transitions that a ``train_gnn.step`` host span
    overlaps; None where no such span was traced."""
    red = ctx.get("trace")
    if red is None:
        return None
    spans = [(h.start_ns, h.end_ns) for h in red.host if h.name == "train_gnn.step"]
    gaps = []
    for d in red.devices:
        mods = sorted(d.modules, key=lambda e: e.start_ns)
        for a, b in zip(mods, mods[1:]):
            if any(s < b.start_ns and e > a.end_ns for s, e in spans):
                gaps.append(max(0.0, b.start_ns - a.end_ns))
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
