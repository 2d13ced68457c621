"""Tracing: the train step's named scopes and ``train_gnn``'s host spans.

The scopes (``jax.named_scope``) reach the optimized HLO as each
instruction's ``metadata={op_name=...}``, wrapped in the transforms
applied to them (``jvp(vmap(gnn.layer0))``, ``transpose(...)`` in the
backward pass).  The host spans (``jax.profiler`` annotations) land in
the profiler's trace.  docs/architecture.md ("Tracing") lists both.
"""
import glob
import importlib.util
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.data.synthetic import SyntheticGraphDataset, rmat_graph
from repro.models.gnn import GNNConfig
from repro.train import loop
from repro.utils.scopes import scopes_of

L = 3
STEPS = 3
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
OP_NAME = re.compile(r'op_name="([^"]*)"')
OPCODE = re.compile(r" = (?:\(.*?\)|\S+) ([a-z][a-z0-9-]*)\(")


def step_recorder():
    """The benchmark's ``StepRecorder`` over ``loop``: it keeps the step
    program and its first arguments while a ``train_gnn`` call runs."""
    path = os.path.join(ROOT, "chipbench", "capture.py")
    spec = importlib.util.spec_from_file_location("chipbench_capture", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.StepRecorder(loop, keep_args=True)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A 3-step independent GCN ``train_gnn`` call under the profiler:
    (result, optimized step HLO, profile planes)."""
    from jax.profiler import ProfileData

    g = rmat_graph(scale=9, edge_factor=8, max_degree=32, seed=0)
    ds = SyntheticGraphDataset(g, feature_dim=8, num_classes=4, seed=0)
    cfg = GNNConfig(model="gcn", num_layers=L, in_dim=8, hidden_dim=16,
                    num_classes=4)
    tc = loop.TrainConfig(mode="independent", num_pes=1, local_batch=16,
                          num_steps=STEPS, fanout=4, eval_every=0)
    d = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(d), step_recorder() as cap:
        res = loop.train_gnn(ds, cfg, tc)
    [path] = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    return res, cap.hlo(), ProfileData.from_file(path).planes


def test_step_hlo_carries_every_scope(traced):
    _, hlo, _ = traced
    names = OP_NAME.findall(hlo)
    found = {s for n in names for s in scopes_of(n)}
    want = ({"plan.seed_draw", "fetch.inputs", "gnn.loss", "optim.update"}
            | {f"plan.hop{h}" for h in range(1, L + 1)}
            | {f"gnn.layer{l}" for l in range(L)})
    assert want <= found, want - found
    # every layer runs forward (under jvp) and backward (under transpose)
    for l in range(L):
        fwd = [n for n in names if f"jvp(vmap(gnn.layer{l}))" in n
               and "transpose(" not in n]
        bwd = [n for n in names if f"transpose(jvp(vmap(gnn.layer{l})))" in n]
        assert fwd and bwd, l


@pytest.mark.parametrize("opcode", ["dot", "sort", "while", "gather"])
def test_heavy_ops_carry_a_program_scope(traced, opcode):
    _, hlo, _ = traced
    ops = [ln for ln in hlo.splitlines()
           if (m := OPCODE.search(ln)) and m.group(1) == opcode]
    # the frontier dedup ranks by a sort, not a binary search: no while loop
    assert bool(ops) == (opcode != "while"), (opcode, ops[:3])
    bare = [ln.strip()[:160] for ln in ops
            if not scopes_of((OP_NAME.search(ln) or [None, ""])[1])]
    assert not bare, bare


def test_profile_holds_one_host_span_per_step(traced):
    _, _, planes = traced
    host = [e for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    steps = [e for e in host if e.name == "train_gnn.step"]
    assert sorted(dict(e.stats)["step_num"] for e in steps) == list(range(STEPS))
    [setup] = [e for e in host if e.name == "train_gnn.setup"]
    assert setup.start_ns + setup.duration_ns <= min(e.start_ns for e in steps)


def test_step_seconds_and_one_trace(traced):
    res, _, _ = traced
    assert len(res.step_s) == STEPS and all(s > 0 for s in res.step_s)
    assert res.step_traces == 1
    assert len(res.losses) == STEPS


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/optimizer/jvp(fetch.inputs)/vmap(gnn.layer0)/dot_general",
     ["fetch.inputs", "gnn.layer0"]),
    ("jit(train_step)/transpose(jvp(vmap(gnn.layer2)))/mul", ["gnn.layer2"]),
    ("jit(train_step)/jvp(jit(build))/plan.hop3/exchange.ids/all_to_all",
     ["plan.hop3", "exchange.ids"]),
    ("jit(train_step)/jvp(jit(build))/vmap(jit(_neighbor_table))/gather", []),
    ("jit(train_step)/transpose(jvp(gnn.layer1))/vmap(gnn.attn.score)/exp",
     ["gnn.layer1", "gnn.attn.score"]),
    ("jit(train_step)/jvp(gnn.layer2)/vmap(gnn.attn.aggregate)/nwh,nwk->nhk/dot_general",
     ["gnn.layer2", "gnn.attn.aggregate"]),
    ("jit(train_step)/jvp(gnn.layer0)/gnn.attn/gnn.attn.scores", ["gnn.layer0"]),
    ("jit(train_step)/plan.hop/optim.update.x/gnn.layer", []),
])
def test_scopes_of_unwraps_transforms(op_name, want):
    assert scopes_of(op_name) == want


_COOP = textwrap.dedent(
    """
    import json, os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "tests")
    from test_tracing import OP_NAME, step_recorder
    from repro.utils.scopes import scopes_of
    from repro.data.synthetic import SyntheticGraphDataset, rmat_graph
    from repro.models.gnn import GNNConfig
    from repro.train import loop

    g = rmat_graph(scale=9, edge_factor=8, max_degree=32, seed=0)
    ds = SyntheticGraphDataset(g, feature_dim=8, num_classes=4, seed=0)
    cfg = GNNConfig(model="gcn", num_layers=2, in_dim=8, hidden_dim=16,
                    num_classes=4)
    tc = loop.TrainConfig(mode="cooperative", num_pes=4, local_batch=8,
                          num_steps=1, fanout=4, eval_every=0,
                          executor="shard")
    with step_recorder() as cap:
        res = loop.train_gnn(ds, cfg, tc)
    hlo = cap.hlo()
    coll = [ln for ln in hlo.splitlines()
            if re.search(r" (all-to-all|all-reduce)(-start)?\\(", ln)]
    print(json.dumps({
        "scopes": sorted({s for n in OP_NAME.findall(hlo) for s in scopes_of(n)}),
        "collectives": len(coll),
        "bare": [ln.strip()[:160] for ln in coll
                 if not any(s.startswith("exchange.") for s in
                            scopes_of((OP_NAME.search(ln) or [None, ""])[1]))],
        "traces": res.step_traces,
    }))
    """
)


def test_shard_path_scopes_its_exchanges():
    """Cooperative shard path on 4 host devices, in a subprocess so this
    process keeps its one device: the id and embedding all-to-alls and
    the all-reduces carry their ``exchange.*`` scopes."""
    out = subprocess.run(
        [sys.executable, "-c", _COOP], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH="src"),
        cwd=ROOT, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert {"exchange.ids", "exchange.embeddings", "exchange.grads",
            "plan.seed_draw", "plan.hop1", "plan.hop2", "gnn.layer0",
            "gnn.layer1", "fetch.inputs", "gnn.loss",
            "optim.update"} <= set(got["scopes"]), got["scopes"]
    assert got["collectives"] > 0 and not got["bare"], got
    assert got["traces"] == 1
