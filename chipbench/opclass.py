"""Op categories of the step program, from its optimized HLO.

A TPU trace names each executed instruction by its text
(``%fusion.26 = ... fusion(...), kind=kCustom, calls=%fused_computation.26``)
and says nothing of what a fusion computes.  The step's HLO module
(``Compiled.as_text()``) does: every computation lists its instructions.
``Classifier`` gives each top-level trace op the set of opcodes it runs,
its own and those of every computation it calls, and one category:

* ``collective``: all-to-all, all-reduce, all-gather, reduce-scatter,
  collective-permute (and their async start/done halves);
* ``sort``: a sort;
* ``loop``: a while loop;
* ``matmul``: holds a dot or a convolution;
* ``gather``: holds a gather, scatter, dynamic-slice or
  dynamic-update-slice;
* ``other``.

The first that applies wins, in that order.
"""
from __future__ import annotations

import re

COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
OPCODE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9-]*)\(")
CALLS = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)=\{?%?([\w.-]+)")
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
GATHERS = ("gather", "scatter", "dynamic-slice", "dynamic-update-slice")
CATEGORIES = ("collective", "sort", "loop", "matmul", "gather", "other")


def opcode(text: str) -> str:
    """``while`` of ``%while.53 = (s32[]{:T(128)}, ...) while(...)``."""
    parts = text.split(" = ", 1)
    m = OPCODE.search(parts[-1])
    return m.group(1) if m else ""


class Classifier:
    def __init__(self, hlo_text: str):
        self.own: dict = {}      # computation -> opcodes
        self.calls: dict = {}    # computation -> called computations
        comp = None
        for line in hlo_text.splitlines():
            head = COMP_HEAD.match(line)
            if head and " = " not in line:
                comp = head.group(1)
                self.own[comp], self.calls[comp] = set(), set()
                continue
            if comp is None or " = " not in line:
                continue
            self.own[comp].add(opcode(line))
            self.calls[comp].update(CALLS.findall(line))
        self._memo: dict = {}

    def kinds_of(self, comp: str) -> set:
        if comp not in self._memo:
            self._memo[comp] = set()   # cycles: a body never calls its loop
            out = set(self.own.get(comp, ()))
            for c in self.calls.get(comp, ()):
                out |= self.kinds_of(c)
            self._memo[comp] = out
        return self._memo[comp]

    def kinds(self, text: str) -> set:
        """Opcodes an instruction (its trace text) runs."""
        out = {opcode(text)}
        for c in CALLS.findall(text):
            out |= self.kinds_of(c)
        return out

    def category(self, text: str) -> str:
        k = self.kinds(text)
        own = opcode(text)
        if any(own.startswith(c) for c in COLLECTIVES) or k & set(COLLECTIVES):
            return "collective"
        if own == "sort":
            return "sort"
        if own == "while":
            return "loop"
        if "dot" in k or "convolution" in k:
            return "matmul"
        if k & set(GATHERS):
            return "gather"
        if "sort" in k:
            return "sort"
        return "other"


def ms_per_step(ctx, category):
    """Device milliseconds per step, per chip, of one category."""
    red, hlo = ctx.get("trace"), ctx.get("hlo")
    if red is None or hlo is None:
        return None
    cls = ctx.setdefault("classifier", Classifier(hlo))
    s = red.category_s(lambda op: cls.category(op.name) == category)
    return 1e3 * s / ctx["trace_steps"]
