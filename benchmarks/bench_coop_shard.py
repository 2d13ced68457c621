"""Fig. 7b on real devices: cooperative shard_map vs replicated gather.

The paper's multi-GPU speedup comes from PEs cooperating on one global
minibatch: each PE fetches only its *owned* input rows from storage and
the first forward layer redistributes them with an all-to-all, instead
of every PE gathering its full request frontier itself (the replicated
baseline Independent Minibatching pays, Fig. 7a vs 7b).

This section measures that on an actual P-device mesh: plans are built
by :class:`repro.engine.shard.ShardRunner` under ``shard_map`` (the id
all-to-alls really cross device boundaries) and the snapshot records

* per-PE edge counts (compute balance across partitioners),
* storage bytes fetched + first-layer all-to-all bytes for the
  cooperative path vs the replicated-gather bytes of independent mode
  at the SAME global batch size,
* wall-clock per plan build (shard vs sim, informational — forced-host
  CPU devices share one socket, so bytes are the gated metric).

The ``wins`` map is deterministic given the seeds, so CI gates it with
``benchmarks/compare_snapshots.py`` against the committed baseline:
``fetch/<key>`` = modeled data-movement time of the replicated baseline
over the cooperative path, using the paper's Table 1 bandwidths (fetch
at BETA=8 GB/s, all-to-all over the fast interconnect at ALPHA=50 GB/s,
same constants as ``bench_coop_vs_indep``) — must stay > 1 and not
regress; ``balance/<key>`` = mean/max per-PE edge load (1.0 = perfectly
balanced).

Device mesh: a CPU run (``JAX_PLATFORMS=cpu``, set explicitly) re-execs
the worker with ``XLA_FLAGS=--xla_force_host_platform_device_count=P``
so the parent benchmark process keeps its single device.  Any other run
builds the mesh from the real devices in this process: a chip belongs
to one process, and the parent already holds it.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.run --only coop_shard
    PYTHONPATH=src python -m benchmarks.bench_coop_shard --worker  # in-proc
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

from benchmarks.common import Csv

OUT_JSON = "BENCH_coop_shard.json"
P = 4
FEAT_DIM = 128          # modeled feature width for byte counts
ALPHA = 50e9            # fast-interconnect all-to-all B/s (paper Table 1)
BETA = 8e9              # feature-fetch B/s from storage (paper Table 1)
STEPS = 4
# (global batch, fanout, layers)
SHAPES = [(256, 5, 2), (512, 5, 3)]
PARTITIONS = ("hash", "degree")


def _worker(fast: bool) -> dict:
    """Runs with P forced host devices; builds plans under shard_map."""
    import jax
    import numpy as np

    from benchmarks.common import bench_graph
    from repro.core import INVALID
    from repro.core.partition import ownership_balance
    from repro.engine import EngineConfig, MinibatchEngine

    if len(jax.devices()) < P:
        raise RuntimeError(
            f"coop_shard needs {P} devices, jax sees {len(jax.devices())}"
        )
    g = bench_graph()
    shapes = SHAPES[:1] if fast else SHAPES
    payload = {
        "graph": {"V": g.num_vertices, "E": g.num_edges},
        "num_pes": P,
        "feat_dim": FEAT_DIM,
        "steps": STEPS,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "rows": [],
        "wins": {},      # gated: fetch/<key> byte ratio, balance/<key>
        "plan_ms": {},   # informational wall clocks
    }
    for partition in PARTITIONS:
        for batch, fanout, layers in shapes:
            key = f"{partition}/b{batch}_f{fanout}_l{layers}"
            cfg = EngineConfig(
                mode="cooperative", num_pes=P, local_batch=batch // P,
                num_layers=layers, fanout=fanout, sampler="labor0",
                schedule="smoothed", kappa=4, seed=0,
                partition=partition, partition_seed=0,
            )
            coop = MinibatchEngine.from_config(
                g, dataclasses.replace(cfg, executor="shard"))
            sim = MinibatchEngine.from_config(g, cfg)
            indep = MinibatchEngine.from_config(g, cfg.with_mode("independent"))

            edges_pe = np.zeros(P)
            coop_fetch = a2a_first = a2a_all = indep_fetch = 0
            off_diag = ~np.eye(P, dtype=bool)
            for s in range(STEPS):
                cp = coop.plan_at(s)       # built under shard_map
                ip = indep.plan_at(s)
                edges_pe += sum(
                    np.asarray(l.mask).sum(axis=(-2, -1)) for l in cp.layers
                ) / STEPS
                coop_fetch += int((np.asarray(cp.input_ids) != INVALID).sum())
                indep_fetch += int((np.asarray(ip.input_ids) != INVALID).sum())
                for li, layer in enumerate(cp.layers):
                    filled = np.asarray(layer.slot_to_tilde) >= 0  # (P,Q,cap)
                    cross = int((filled & off_diag[:, :, None]).sum())
                    a2a_all += cross
                    if li == layers - 1:   # input-layer redistribution
                        a2a_first += cross

            # wall clock per plan build (compile excluded), shard vs sim
            for name, eng in (("shard", coop), ("sim", sim)):
                jax.block_until_ready(eng.plan_at(0))
                t0 = time.perf_counter()
                for s in range(STEPS):
                    plan = eng.plan_at(s)
                jax.block_until_ready(plan)
                payload["plan_ms"][f"{name}/{key}"] = round(
                    (time.perf_counter() - t0) / STEPS * 1e3, 3)

            row_bytes = FEAT_DIM * 4
            fetch_bytes = coop_fetch * row_bytes
            a2a_bytes = a2a_first * row_bytes
            repl_bytes = indep_fetch * row_bytes
            # Table 1 model: fetch pays BETA, A2A rides the fast interconnect
            coop_s = fetch_bytes / BETA + a2a_bytes / ALPHA
            repl_s = repl_bytes / BETA
            bal = ownership_balance(g, coop.part)
            payload["rows"].append({
                "key": key,
                "edges_per_pe": [round(e, 1) for e in edges_pe],
                "coop_fetch_rows": coop_fetch // STEPS,
                "indep_fetch_rows": indep_fetch // STEPS,
                "a2a_first_layer_rows": a2a_first // STEPS,
                "a2a_all_layers_rows": a2a_all // STEPS,
                "coop_fetch_bytes": fetch_bytes // STEPS,
                "a2a_first_layer_bytes": a2a_bytes // STEPS,
                "replicated_bytes": repl_bytes // STEPS,
                "ownership_balance": bal,
            })
            payload["wins"][f"fetch/{key}"] = round(repl_s / coop_s, 4)
            payload["wins"][f"balance/{key}"] = round(
                float(edges_pe.mean() / edges_pe.max()), 4)
    return payload


def _forced_host_worker(fast: bool) -> dict:
    """CPU only: run the worker in a child with P forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={P}"
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, "-m", "benchmarks.bench_coop_shard", "--worker"]
    if fast:
        cmd.append("--fast")
    proc = subprocess.run(
        cmd, env=env, cwd=repo, capture_output=True, text=True, timeout=1800
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"coop_shard worker failed:\n{proc.stderr[-4000:]}"
        )
    with open(os.path.join(repo, OUT_JSON)) as f:
        return json.load(f)


def run(fast: bool = False) -> Csv:
    """Collect the snapshot: forced host devices in a child on the CPU,
    the real devices in this process everywhere else."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        payload = _forced_host_worker(fast)
    else:
        payload = _worker(fast)
        with open(OUT_JSON, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
    csv = Csv(["key", "coop_fetch_rows", "indep_fetch_rows",
               "a2a_first_layer_rows", "fetch_win", "edge_balance"],
              snapshot=payload)
    for row in payload["rows"]:
        csv.add(row["key"], row["coop_fetch_rows"], row["indep_fetch_rows"],
                row["a2a_first_layer_rows"],
                payload["wins"][f"fetch/{row['key']}"],
                payload["wins"][f"balance/{row['key']}"])
    worst = min(
        (v for k, v in payload["wins"].items() if k.startswith("fetch/")),
    )
    print(f"# coop_shard: modeled data-movement win min {worst}x "
          f"-> {OUT_JSON}", flush=True)
    return csv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true",
                    help="run in-process (expects forced device count)")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.worker:
        payload = _worker(fast=args.fast)
        with open(OUT_JSON, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {OUT_JSON}", flush=True)
    else:
        run(fast=args.fast).emit()


if __name__ == "__main__":
    main()
