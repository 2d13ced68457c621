"""Benchmark orchestrator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <name>] [--fast]

On a CPU host set ``JAX_PLATFORMS=cpu`` explicitly: ``coop_shard`` then
runs its 4-PE mesh on forced host devices in a child process; anywhere
else every section runs in this one process on the real devices.

Sections:
    monotonicity   Fig 3/6  (Thm 3.1/3.2 work curves per sampler)
    density        Thm 3.3  (induced-subgraph density vs batch)
    cache_kappa    Fig 5a/5b + Table 6 (LRU miss vs dependency kappa)
    plan_build     device-resident plan_at vs sort-based host baseline
    feature_store  Fig 5 shape through the device CLOCK tier (+ oracle gap)
    coop_shard     Fig 7b on devices: shard_map A2A bytes vs replicated gather
    coop_vs_indep  Tables 4/5/7 (per-PE counts + bandwidth-model times)
    serve          coalescing inference server vs per-request baseline
    convergence    Fig 4/9  (coop vs indep; kappa parity)
    kernels        per-kernel shape sweep

Every section persists a machine-readable ``BENCH_<section>.json``
snapshot (see docs/benchmarks.md for the snapshot/gate workflow).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _section(name):
    print(f"\n### {name}", flush=True)


def run_analysis_gate(out_path="BENCH_analysis.json"):
    """Run the static analyzer over src/ and persist rule counts + wall time.

    Runs first so a benchmark snapshot is never recorded against a tree
    the invariant checker rejects.
    """
    from repro.analysis import Severity, run_analysis

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = run_analysis([os.path.join(repo, "src")])
    payload = {
        "rule_counts": report.rule_counts(),
        "files_scanned": report.files_scanned,
        "passes_run": list(report.passes_run),
        "wall_s": round(report.wall_s, 3),
        "errors": report.count_at_least(Severity.ERROR),
        "warnings": report.count_at_least(Severity.WARNING),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# analysis: {payload['errors']} errors, "
          f"{payload['warnings']} warnings in {payload['wall_s']}s "
          f"-> {out_path}", flush=True)
    if payload["errors"]:
        for fi in report.findings:
            if fi.severity >= Severity.ERROR:
                print(fi.render(), file=sys.stderr)
        raise SystemExit("repro.analysis found errors; fix before benchmarking")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="skip the static-analysis gate / BENCH_analysis.json")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    enable_compile_cache(repo)

    if not args.skip_analysis:
        _section("analysis")
        t0 = time.time()
        run_analysis_gate()
        print(f"# analysis done in {time.time()-t0:.1f}s", flush=True)

    sections = {}

    def register(name, fn):
        sections[name] = fn

    from benchmarks import (
        bench_cache_kappa,
        bench_convergence,
        bench_coop_shard,
        bench_coop_vs_indep,
        bench_density,
        bench_feature_store,
        bench_kernels,
        bench_monotonicity,
        bench_plan_build,
        bench_serve,
    )

    register("monotonicity", lambda: bench_monotonicity.run(trials=3 if args.fast else 6))
    register("density", lambda: bench_density.run(trials=4 if args.fast else 8))
    register("cache_kappa", lambda: bench_cache_kappa.run(coop=not args.fast))
    register("feature_store", lambda: bench_feature_store.run(
        coop=not args.fast, fast=args.fast))
    register("plan_build", lambda: bench_plan_build.run(fast=args.fast))
    register("coop_shard", lambda: bench_coop_shard.run(fast=args.fast))
    register("coop_vs_indep", lambda: bench_coop_vs_indep.run(fast=args.fast))
    register("serve", lambda: bench_serve.run(fast=args.fast))
    register("convergence", bench_convergence.run)
    register("kernels", bench_kernels.run)

    todo = [args.only] if args.only else list(sections)
    for name in todo:
        t0 = time.time()
        _section(name)
        try:
            csv = sections[name]()
            csv.emit()
            # every section leaves a snapshot: the perf trajectory needs a
            # baseline to beat even for sections without a gate metric yet
            out = f"BENCH_{name}.json"
            with open(out, "w") as f:
                json.dump(csv.to_payload(name), f, indent=2, sort_keys=True)
            print(f"# {name} done in {time.time()-t0:.1f}s -> {out}",
                  flush=True)
        except Exception as e:  # keep the suite going; report at the end
            print(f"# {name} FAILED: {e!r}", file=sys.stderr, flush=True)
            raise


if __name__ == "__main__":
    main()
