"""The trace reduction on one recorded step of ``gcn-papers100m.indep1``
(TPU v5 lite): the planes and op names it relies on, the idle share,
the op categories from the step's HLO, the breakdown and the readers."""
import gzip
import os

import pytest

import opclass
import run
import trace_reduce as tr
from conftest import BENCH

FIX = os.path.join(BENCH, "tests", "fixtures")


@pytest.fixture(scope="module")
def step():
    planes = tr.load_json(os.path.join(FIX, "gcn_indep1_step.json.gz"))
    with gzip.open(os.path.join(FIX, "gcn_indep1_step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    return planes, tr.reduce(planes), hlo


def test_planes_and_lines_it_relies_on(step):
    planes, red, _ = step
    names = {p.name for p in planes}
    assert "/device:TPU:0" in names and "/host:CPU" in names
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    assert {"XLA Modules", "XLA Ops"} <= {ln.name for ln in dev.lines}
    assert red.chips == 1
    [mod] = red.devices[0].modules
    assert mod.name.startswith("jit_train_step(")
    assert red.host, "host events for naming idle gaps"


def test_busy_window_and_idle_share(step):
    _, red, _ = step
    d = red.devices[0]
    assert 1.6 < red.window_s < 1.7
    assert 0 < red.busy_s <= red.window_s
    assert 0 <= red.idle_share < 0.01
    top = d.top
    assert all(a.end_ns <= b.start_ns for a, b in zip(top, top[1:]))
    assert len(top) < len(d.ops), "while bodies are nested, not top-level"


def test_categories_from_the_hlo(step):
    _, red, hlo = step
    cls = opclass.Classifier(hlo)
    secs = {c: red.category_s(lambda op, c=c: cls.category(op.name) == c)
            for c in opclass.CATEGORIES}
    top = sum(o.dur_ns for o in red.devices[0].top) / 1e9
    assert sum(secs.values()) == pytest.approx(top)
    # the frontier lookups' binary searches lead, then gathers, sorts
    assert secs["loop"] > secs["gather"] > secs["sort"] > secs["matmul"] > 0
    assert secs["collective"] == 0
    assert cls.category("%while.53 = (s32[]{:T(128)}, s32[1,8]{1,0:T(1,128)}) "
                        "while((s32[]{:T(128)}) %tuple.176), "
                        "condition=%wide.region_33.136.clone, "
                        "body=%wide.region_32.135.clone.sunk") == "loop"


def test_breakdown_lists_top_ops_and_gaps(step):
    _, red, hlo = step
    cls = opclass.Classifier(hlo)
    b = tr.breakdown(red, label=lambda op: f"{op.short} {cls.category(op.name)}")
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0] == "while.53 loop"
    assert all(isinstance(n, str) and s >= 0 for n, s in b["idle_gaps"])


@pytest.mark.parametrize("metric,category", [
    ("plan.sort_ms.train", "sort"),
    ("fetch.gather_ms.train", "gather"), ("gnn.matmul_ms.train", "matmul")])
def test_readers(step, metric, category):
    _, red, hlo = step
    ctx = {"trace": red, "hlo": hlo, "trace_steps": 1}
    got = run.read_metric(os.path.join(BENCH, "metrics"), metric, ctx)
    cls = opclass.Classifier(hlo)
    want = 1e3 * red.category_s(lambda op: cls.category(op.name) == category)
    assert got == pytest.approx(want) and got > 0
    idle = run.read_metric(os.path.join(BENCH, "metrics"),
                           "device.idle_share.train", ctx)
    assert idle == pytest.approx(100 * red.idle_share)
