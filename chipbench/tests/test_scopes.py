"""The scope-based readers (``scopes.py``) on recorded TPU steps of
``gcn-papers100m.indep1``: one of the program with its named scopes
(``gcn_indep1_scoped_step.*``) and one from before them
(``gcn_indep1_step.*``), on which every such reader reads nothing."""
import gzip
import os

import pytest

import opclass
import run
import scopes
import trace_reduce as tr
from conftest import BENCH

FIX = os.path.join(BENCH, "tests", "fixtures")
METRICS = os.path.join(BENCH, "metrics")
READERS = ["plan.seed_draw_ms.train", "plan.hops_ms.train",
           "plan.last_hop_ms.train", "fetch.inputs_ms.train",
           "gnn.forward_ms.train", "gnn.backward_ms.train",
           "optim.update_ms.train", "host.step_gap_ms.train",
           "trace.unscoped_share.train", "exchange.ms.train"]
PARTS = {"plan.seed_draw": "plan.seed_draw_ms.train",
         "plan.hops": "plan.hops_ms.train",
         "fetch.inputs": "fetch.inputs_ms.train",
         "gnn.forward": "gnn.forward_ms.train",
         "gnn.backward": "gnn.backward_ms.train",
         "optim.update": "optim.update_ms.train"}


def load(name):
    planes = tr.load_json(os.path.join(FIX, name + ".json.gz"))
    with gzip.open(os.path.join(FIX, name + ".hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    return {"trace": tr.reduce(planes), "hlo": hlo, "trace_steps": 1,
            "config": {"num_layers": 3}}


@pytest.fixture(scope="module")
def scoped_step():
    return load("gcn_indep1_scoped_step")


@pytest.fixture(scope="module")
def unscoped_step():
    return load("gcn_indep1_step")


def test_every_reader_reads_the_scoped_step(scoped_step):
    ctx = scoped_step
    got = {n: run.read_metric(METRICS, n, ctx) for n in READERS}
    # one execution: no step-to-step gap
    assert got.pop("host.step_gap_ms.train") is None
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    assert got["plan.last_hop_ms.train"] <= got["plan.hops_ms.train"]
    assert got["plan.seed_draw_ms.train"] > 0 and got["gnn.backward_ms.train"] > 0
    # one PE: nothing is exchanged
    assert got["exchange.ms.train"] == 0


def test_parts_sum_to_the_step(scoped_step):
    ctx = scoped_step
    red = ctx["trace"]
    top_ms = 1e3 * red.category_s(lambda op: True)
    parts = sum(run.read_metric(METRICS, m, ctx) for m in PARTS.values())
    unscoped = run.read_metric(METRICS, "trace.unscoped_share.train", ctx)
    assert parts + unscoped / 100 * top_ms == pytest.approx(top_ms, rel=1e-3)
    assert 0 < unscoped <= 5


def test_the_last_hop_holds_the_searches(scoped_step):
    """The frontier lookups' while loops (binary searches until the
    program's dedup became one sort) are almost all in hop 3, as read
    from the shapes by hand."""
    ctx = scoped_step
    sc = scopes.Scopes(ctx["hlo"])
    cls = opclass.Classifier(ctx["hlo"])
    red = ctx["trace"]
    loops = red.category_s(lambda op: cls.category(op.name) == "loop")
    hop3 = red.category_s(lambda op: cls.category(op.name) == "loop"
                          and sc.of(op.short)[0][:1] == ("plan.hop3",))
    assert hop3 > 0.9 * loops


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/transpose(jvp(vmap(gnn.layer0)))/mul",
     (("gnn.layer0",), True)),
    ("jit(train_step)/jvp(jit(build))/vmap(plan.hop3)/jit(lookup)/while",
     (("plan.hop3",), False)),
    ("jit(train_step)/jvp(jit(build))/plan.hop2/exchange.ids/all_to_all",
     (("plan.hop2", "exchange.ids"), False)),
    ("jit(train_step)/jvp(jit(build))/vmap(jit(_neighbor_table))/gather",
     ((), False)),
    ("reduce_window_sum", ((), False)),
])
def test_op_names_unwrap_to_scopes(op_name, want):
    assert scopes.parse(op_name) == want


@pytest.mark.parametrize("found,part", [
    ((("plan.seed_draw",), False), "plan.seed_draw"),
    ((("plan.hop1", "exchange.ids"), False), "plan.hops"),
    ((("gnn.layer2", "exchange.embeddings"), True), "gnn.backward"),
    ((("gnn.loss",), False), "gnn.forward"),
    ((("exchange.grads",), False), "gnn.backward"),
    ((("optim.update",), False), "optim.update"),
    (((), False), "unscoped"),
])
def test_each_op_falls_in_one_part(found, part):
    assert scopes.part_of(*found) == part and part in scopes.PARTS


def test_a_fusion_without_metadata_takes_its_root_scope():
    hlo = "\n".join([
        "%fused_computation.1 (p: s32[4]) -> s32[4] {",
        '  %p = s32[4]{0} parameter(0), metadata={op_name="x"}',
        '  ROOT %g = s32[4]{0} gather(%p), metadata={op_name='
        '"jit(train_step)/jvp(vmap(gnn.layer1))/gather"}',
        "}",
        "ENTRY %main (a: s32[4]) -> s32[4] {",
        "  %a = s32[4]{0} parameter(0)",
        "  ROOT %fusion.7 = s32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1",
        "}",
    ])
    sc = scopes.Scopes(hlo)
    assert sc.of("fusion.7") == (("gnn.layer1",), False)
    assert scopes.part_of(*sc.of("a")) == "unscoped" and sc.named


def test_a_program_without_scopes_reads_nothing(unscoped_step):
    ctx = unscoped_step
    assert not scopes.Scopes(ctx["hlo"]).named
    for name in READERS:
        assert run.read_metric(METRICS, name, ctx) is None, name


def test_step_gap_counts_gaps_under_step_spans():
    def ev(name, start, end):
        return tr.Event(name, start, end - start)

    dev = tr.Device(index=0, modules=[ev("jit_train_step(1)", 0, 100),
                                      ev("jit_train_step(1)", 104, 200),
                                      ev("jit_train_step(1)", 210, 300)],
                    ops=[], busy=[], start_ns=0, end_ns=300)
    spans = [ev("train_gnn.step", 98, 150), ev("train_gnn.step", 195, 290)]
    ctx = {"trace": tr.Reduced(devices=[dev], host=spans)}
    assert scopes.step_gap_ms(ctx) == pytest.approx((4 + 10) / 2 / 1e6)
    ctx = {"trace": tr.Reduced(devices=[dev], host=[ev("other", 0, 300)])}
    assert scopes.step_gap_ms(ctx) is None


def test_exchange_counts_every_exchange_scope():
    """``exchange.ms.train``: ops under any ``exchange.*`` scope, wherever
    it sits in the path (inside a hop or a layer, or alone)."""
    names = {"a2a.1": "jit(train_step)/jvp(jit(build))/plan.hop2/exchange.ids/all_to_all",
             "a2a.2": "jit(train_step)/transpose(jvp(gnn.layer1))/exchange.embeddings/all_to_all",
             "ar.3": "jit(train_step)/exchange.grads/psum",
             "fusion.4": "jit(train_step)/jvp(gnn.layer1)/dot_general"}
    hlo = "\n".join(["ENTRY %main (a: f32[4]) -> f32[4] {"] + [
        f'  %{n} = f32[4]{{0}} add(%a, %a), metadata={{op_name="{o}"}}'
        for n, o in names.items()] + ["}"])
    ops = [tr.Event(f"%{n} = f32[4]{{0}} add()", 10 * i, 5 * (i + 1))
           for i, n in enumerate(names)]
    dev = tr.Device(index=0, modules=[], ops=ops, busy=[], start_ns=0, end_ns=40)
    ctx = {"trace": tr.Reduced(devices=[dev], host=[]), "hlo": hlo, "trace_steps": 2}
    got = run.read_metric(METRICS, "exchange.ms.train", ctx)
    assert got == pytest.approx((5 + 10 + 15) / 2 / 1e6)
