"""The names the train step gives its layers in a trace.

Device work is wrapped in ``jax.named_scope``; a name reaches the
optimized HLO as each instruction's ``metadata={op_name="..."}``, inside
the transforms applied to it: ``jit(train_step)/jvp(vmap(gnn.layer0))/
dot_general`` is forward, ``.../transpose(jvp(vmap(gnn.layer0)))/mul``
backward.  docs/architecture.md ("Tracing") says where each scope sits.
"""
from __future__ import annotations

import re

# plan.seed_draw, plan.hop1..plan.hop<L>, fetch.inputs,
# gnn.layer0..gnn.layer<L-1> (GAT's gnn.attn.score and gnn.attn.aggregate
# inside them), gnn.loss, optim.update and, around the collectives alone,
# exchange.ids / exchange.embeddings / exchange.grads
SCOPE = re.compile(r"^(?:plan\.(?:seed_draw|hop\d+)|fetch\.inputs"
                   r"|gnn\.(?:layer\d+|loss|attn\.(?:score|aggregate))"
                   r"|optim\.update|exchange\.(?:ids|embeddings|grads))$")
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def scopes_of(op_name: str) -> list:
    """The program scopes in one ``op_name``, outer to inner, with the
    transforms (``jvp(…)``, ``vmap(…)``, ``transpose(…)``) unwrapped."""
    out = []
    for comp in op_name.split("/"):
        while m := _WRAPPED.match(comp):
            comp = m.group(1)
        if SCOPE.match(comp):
            out.append(comp)
    return out
