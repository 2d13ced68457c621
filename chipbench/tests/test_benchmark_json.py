"""BENCHMARK.json against the rules the harness and the check rely on."""
import json
import os
import re
import shutil

from conftest import BENCH, ROOT, load_benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_and_units():
    b = load_benchmark()
    metrics = b["end_to_end"] + b["per_layer"]
    for entry in b["configs"] + b["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in b["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
    names = [e["name"] for e in metrics]
    assert len(names) == len(set(names))


def test_every_moves_is_reported_by_every_listed_cell():
    b = load_benchmark()
    cells = [w["name"] for w in b["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", e2e[m["moves"]]):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert any(cell in s for k, s in e2e.items() if k != "setup_s")


def test_at_most_half_the_cells_take_four_chips():
    cells = load_benchmark()["workloads"]
    four = sum(w["chips"] == 4 for w in cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    assert four <= max(1, len(cells) // 2)


def test_every_piece_is_a_file_of_its_own():
    import run
    import verdict

    b = load_benchmark()
    for w in b["workloads"]:
        spec = run.load_spec(ROOT, w["name"])
        assert spec["limits"] and set(spec["limits"]) <= set(verdict.NAMES)
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert os.path.isfile(os.path.join(ROOT, f)) and f.startswith("chipbench/")
        with open(os.path.join(ROOT, f)) as fh:
            model = json.load(fh)["model"]
        assert os.path.isfile(os.path.join(BENCH, "models", model + ".py")), model


def test_a_cell_dropped_into_a_copy_is_found(tmp_path):
    """Adding a cell is adding files and an entry: no other edit."""
    import run

    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = load_benchmark()
    new = "gcn-papers100m.indep1-b4096"
    b["workloads"].append({"name": new, "config": "gcn-papers100m",
                           "traffic": "indep1-b4096", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    traffic = json.loads((root / "chipbench/traffic/indep1.json").read_text())
    traffic["warmup_steps"] = 2
    (root / "chipbench/traffic/indep1-b4096.json").write_text(json.dumps(traffic))
    limits = json.loads(
        (root / "chipbench/cells/gcn-papers100m.indep1.json").read_text())
    (root / f"chipbench/cells/{new}.json").write_text(json.dumps(limits))
    spec = run.load_spec(str(root), new)
    assert spec["traffic"]["warmup_steps"] == 2
    assert spec["config"]["model"] == "gcn"
    assert {m["name"] for m in spec["per_layer"]} >= {"step.mfu.train"}
