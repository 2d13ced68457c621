"""The cell ``gat-products.indep1`` at a test size on the CPU: a sound run
is ``correct``, each planted fault and the bfloat16 control are not, the
cooperative traffic on four host devices is ``correct`` too; the FLOP
count by hand; and the two attention readers on a recorded tiny step."""
import gzip
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import flops
import peaks
import run
import trace_reduce as tr
import verdict
from conftest import BENCH, ROOT, tiny_spec

CELL = "gat-products.indep1"
METRICS = os.path.join(BENCH, "metrics")
READERS = ["gnn.attn_ms.train", "gnn.attn_hbm_share.train"]


def run_tiny(spec=None):
    import jax

    return run.run_cell(jax, spec or tiny_spec(CELL), 2**31 + 91, 0.5, False,
                        time.perf_counter())


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"], res["checks"]
    assert {"train_seeds_per_s", "setup_s"} <= set(res["metrics"])


def test_step_that_returns_its_state_unchanged_fails(monkeypatch):
    from repro.train import loop

    monkeypatch.setattr(loop, "adam_update",
                        lambda params, grads, opt, lr: (params, opt))
    res = run_tiny()
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] >= 0.99


def test_half_the_batch_left_out_fails(monkeypatch):
    from repro.train import loop

    real = loop.masked_softmax_xent

    def half(logits, labels, valid):
        keep = np.arange(valid.shape[0]) < valid.shape[0] // 2
        return real(logits, labels, valid & keep)

    monkeypatch.setattr(loop, "masked_softmax_xent", half)
    assert not run_tiny()["correct"]


def test_control_in_bfloat16_fails():
    spec = tiny_spec(CELL)
    spec["config"]["dtype"] = "bfloat16"
    res = run_tiny(spec)
    assert not res["correct"], res["checks"]


# The chip readings the cell's limits were set from (PERF.md §2): each
# number's largest over the sound seeds, and each fault's least over its
# seeds.  ``judge`` only grows stricter as a number grows, so a fault
# whose least readings fail fails on every seed.
SOUND_MOST = {"loss_gap": 9.70e-5, "grad_gap": 4.13e-3, "change_gap": 1.21e-2}
FAULT_LEAST = {
    "control_program_bf16": {"loss_gap": 5.95e-3, "grad_gap": 7.63e-2,
                             "change_gap": 8.56e-2},
    "reference_bf16": {"loss_gap": 5.31e-5, "grad_gap": 2.20e-3,
                       "change_gap": 5.33e-2},
    "fault_half_batch": {"loss_gap": 6.06e-3, "grad_gap": 0.414,
                         "change_gap": 5.62e-2},
}


@pytest.mark.parametrize("number", sorted(SOUND_MOST))
def test_each_limit_lies_between_the_sound_runs_and_the_control(number):
    """The control, the program's own bfloat16 path, reads 3× the sound
    runs' largest or more on every number, so it sets each number's
    upper reading, and each limit lies between the two."""
    limit = run.load_spec(ROOT, CELL)["limits"][number]
    assert SOUND_MOST[number] < limit < FAULT_LEAST["control_program_bf16"][number]


@pytest.mark.parametrize("fault", sorted(FAULT_LEAST))
def test_limits_fail_each_recorded_fault(fault):
    assert not verdict.judge(FAULT_LEAST[fault], run.load_spec(ROOT, CELL)["limits"])


COOP = r"""
import sys, time
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
import jax
from conftest import tiny_spec
import run

res = run.run_cell(jax, tiny_spec({cell!r}, traffic="coop4"), 12345, 0.5,
                   False, time.perf_counter())
print("CORRECT", res["correct"], res["checks"])
"""


def test_cooperative_traffic_is_correct():
    """The same configuration under ``coop4`` on four forced host
    devices: the GAT layer on the ``shard`` executor's path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = COOP.format(tests=os.path.join(BENCH, "tests"), bench=BENCH,
                       src=os.path.join(ROOT, "src"), cell=CELL)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("CORRECT")][-1]
    assert line.startswith("CORRECT True"), line


def test_gat_two_layers_by_hand():
    # plan: S0 = 2 seeds, S1 = 5, S2 = 9; E0 = 4, E1 = 11; in 3, hidden 8
    # (H = 2 heads of C = 4), classes 3 (H = 2 heads of C = 3).
    # Layer 1 reads the features (k 3 -> m 8): scores 2*3*2*(11 + 2*5) = 252
    # and reduce 2*3*2*(11 + 5) = 192; per-head projection 2*5*3*8 = 240
    # and skip 2*5*3*8 = 240; forward 444 + 480, weight grads 480.
    # Layer 0 (k 8 -> m 3, H C = 6): scores 2*8*2*(4 + 2*2) = 256, reduce
    # 2*8*2*(4 + 2) = 192; projection 2*2*8*6 = 192, skip 2*2*8*3 = 96;
    # forward 448 + 288, weight grads 288, input grads 288 + 448.
    want = (444 + 480 + 480) + (448 + 288 + 288 + 288 + 448)
    cfg = {"model": "gat", "feature_dim": 3, "hidden_dim": 8, "num_classes": 3,
           "num_heads": 2}
    assert flops.step_flops(cfg, [2, 5, 9], [4, 11]) == want


# --------------------------------------------------------------------------
# The readers, on a tiny scoped step: its HLO and a trace giving every
# top-level instruction one microsecond
# --------------------------------------------------------------------------
ENTRY = re.compile(r"^ENTRY ")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")


def one_microsecond_each(hlo: str):
    lines = hlo.splitlines()
    start = next(i for i, ln in enumerate(lines) if ENTRY.match(ln))
    ops = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            break
        m = INSTR.match(ln)
        if m:
            ops.append(tr.Event(m.group(1), 1000.0 * len(ops), 1000.0))
    dev = tr.Device(index=0, modules=[], ops=ops, busy=[], start_ns=0,
                    end_ns=1000.0 * len(ops))
    return tr.Reduced(devices=[dev], host=[])


@pytest.fixture(scope="module")
def tiny_step():
    import jax

    from capture import CompileLog
    from repro.train import loop

    spec = tiny_spec(CELL)
    ds, _ = run.make_dataset(jax, spec, 5)
    gnn_cfg = run.gnn_config(spec["config"])
    tc = run.train_config(spec, 1)
    _, rec, _, _, _ = run.timed_call(jax, loop, CompileLog(jax), ds, gnn_cfg, tc,
                                     True, keep_args=True)
    hlo = rec.hlo()
    return {"trace": one_microsecond_each(hlo), "hlo": hlo, "trace_steps": 1,
            "config": spec["config"], "peaks": peaks.peaks_for("TPU v5 lite"),
            "plan_counts": run.plan_counts(jax, ds, gnn_cfg, tc, 2)}


def test_readers_read_the_scoped_step(tiny_step):
    ms = run.read_metric(METRICS, "gnn.attn_ms.train", dict(tiny_step))
    share = run.read_metric(METRICS, "gnn.attn_hbm_share.train", dict(tiny_step))
    top_ms = 1e3 * tiny_step["trace"].category_s(lambda op: True)
    assert 0 < ms < top_ms
    assert share > 0


def test_readers_split_forward_and_backward(tiny_step):
    att = run.read_metric(METRICS, "gnn.attn_ms.train", dict(tiny_step))
    reader = run.byname.load(os.path.join(METRICS, "gnn.attn_ms.train.py"), "attn")
    fwd = reader.ms_per_step(dict(tiny_step), lambda backward: not backward)
    bwd = reader.ms_per_step(dict(tiny_step), lambda backward: backward)
    assert fwd > 0 and bwd > 0 and fwd + bwd == pytest.approx(att)


@pytest.mark.parametrize("fixture", ["gcn_indep1_scoped_step", "gcn_indep1_step"])
def test_a_program_without_attention_reads_nothing(tiny_step, fixture):
    fix = os.path.join(BENCH, "tests", "fixtures", fixture)
    with gzip.open(fix + ".hlo.txt.gz", "rt") as f:
        hlo = f.read()
    ctx = {"trace": tr.reduce(tr.load_json(fix + ".json.gz")), "hlo": hlo,
           "trace_steps": 1, "config": tiny_step["config"],
           "peaks": tiny_step["peaks"], "plan_counts": tiny_step["plan_counts"]}
    for name in READERS:
        assert run.read_metric(METRICS, name, dict(ctx)) is None, name
