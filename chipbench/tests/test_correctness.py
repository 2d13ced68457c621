"""``correct`` at a test size on the CPU: a sound run passes, and each
fault a training cell can have, planted under the harness, fails; so
does the control, the reference computed in bfloat16 in the program's
place.  The limits are the cells' own."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT, tiny_spec

INDEP = ["gcn-papers100m.indep1", "rgcn-mag240m.indep1"]


def run_tiny(cell):
    import jax

    import run

    return run.run_cell(jax, tiny_spec(cell), 2**31 + 91, 0.5, False,
                        time.perf_counter())


@pytest.mark.parametrize("cell", INDEP)
def test_sound_run_is_correct(cell):
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert {"train_seeds_per_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("cell", INDEP)
def test_step_that_returns_its_state_unchanged_fails(cell, monkeypatch):
    from repro.train import loop

    monkeypatch.setattr(loop, "adam_update",
                        lambda params, grads, opt, lr: (params, opt))
    res = run_tiny(cell)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] >= 0.99


@pytest.mark.parametrize("cell", INDEP)
def test_half_the_batch_left_out_fails(cell, monkeypatch):
    from repro.train import loop

    real = loop.masked_softmax_xent

    def half(logits, labels, valid):
        keep = np.arange(valid.shape[0]) < valid.shape[0] // 2
        return real(logits, labels, valid & keep)

    monkeypatch.setattr(loop, "masked_softmax_xent", half)
    assert not run_tiny(cell)["correct"]


@pytest.mark.parametrize("cell", INDEP)
def test_control_in_bfloat16_fails(cell):
    """The control: the program's own bfloat16 path (parameters held in
    bfloat16), in the place of the configuration's float32."""
    import jax

    import run

    spec = tiny_spec(cell)
    spec["config"]["dtype"] = "bfloat16"
    res = run.run_cell(jax, spec, 2**31 + 91, 0.5, False, time.perf_counter())
    assert not res["correct"], res["checks"]


COOP = r"""
import sys, time
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
import jax
import numpy as np
from conftest import tiny_spec
import run
from repro.train import loop, metrics

spec = tiny_spec("gcn-papers100m.coop4")
fault = {fault!r}
if fault == "exchange":
    from readings import local_only_exchange
    from repro.core import cooperative

    cooperative.ShardExecutor.exchange = local_only_exchange
elif fault == "unchanged":
    loop.adam_update = lambda params, grads, opt, lr: (params, opt)
elif fault == "half":
    real = metrics.masked_softmax_xent_parts

    def half(logits, labels, valid):
        keep = np.arange(valid.shape[0]) < valid.shape[0] // 2
        return real(logits, labels, valid & keep)

    metrics.masked_softmax_xent_parts = half
elif fault == "bf16":
    spec["config"]["dtype"] = "bfloat16"
res = run.run_cell(jax, spec, 12345, 0.5, False, time.perf_counter())
print("CORRECT", res["correct"], res["checks"])
"""


def run_coop4(fault):
    """One run of ``gcn-papers100m.coop4`` at test size on four forced
    host devices, with ``fault`` planted under the harness; the line
    ``CORRECT <bool> <checks>``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = COOP.format(tests=os.path.join(BENCH, "tests"), bench=BENCH,
                       src=os.path.join(ROOT, "src"), fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return [ln for ln in out.stdout.splitlines() if ln.startswith("CORRECT")][-1]


@pytest.mark.parametrize("broken", [False, True])
def test_cooperative_exchange_left_out_fails(broken):
    """The cooperative cell: sound, then with every all-to-all delivering
    only what a PE addressed to itself."""
    line = run_coop4("exchange" if broken else None)
    assert line.startswith(f"CORRECT {not broken}"), line


@pytest.mark.parametrize("fault", ["unchanged", "half", "bf16"])
def test_cooperative_fault_fails(fault):
    """The cooperative cell with a step that returns its state unchanged,
    half of every PE's seeds left out of the loss, or the program's own
    bfloat16 path (the control)."""
    line = run_coop4(fault)
    assert line.startswith("CORRECT False"), line
