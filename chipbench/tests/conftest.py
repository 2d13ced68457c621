"""Shared set-up of the benchmark's own tests: they run on the CPU, at
sizes a test run holds, with ``src`` and ``chipbench`` importable."""
import copy
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]

import pytest  # noqa: E402


def tiny_spec(cell: str, traffic: str | None = None) -> dict:
    """The cell as BENCHMARK.json defines it, with its configuration cut
    to V = 4096 and narrow layers; metrics and limits as they stand, and
    its traffic, or ``traffic`` from ``chipbench/traffic/`` in its place."""
    import run

    spec = run.load_spec(ROOT, cell)
    if traffic is not None:
        with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
            spec["traffic"] = json.load(f)
        if spec["traffic"]["mode"] == "cooperative":
            spec["cell"] = dict(spec["cell"], chips=spec["traffic"]["num_pes"])
    cfg = copy.deepcopy(spec["config"])
    cfg.update(num_vertices=4096, num_train=300, feature_dim=16,
               hidden_dim=32, num_classes=7)
    cfg["local_batch"] = min(cfg["local_batch"], 32)
    cfg["num_edges"] = 110000 if cfg["model"] == "gcn" else 55000
    spec["config"] = cfg
    return spec


@pytest.fixture
def spec_of():
    return tiny_spec


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
