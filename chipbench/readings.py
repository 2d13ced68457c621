"""Readings that the correctness limits are set from, at a cell's size.

    python3 chipbench/readings.py --workload <cell> --seeds 1 2 3 ...

For each seed, in one process: the dataset, one ``train_gnn`` call of
three steps recorded as in a benchmark run, and the reference three
times: in float32 (what the program is compared with), in bfloat16
(put in the program's place) and with the loss averaged over half the
seeds (a planted fault); and the program again with its own bfloat16
path switched on (``GNNConfig.dtype``: parameters held in bfloat16),
the control.  Prints one JSON line per seed
with ``verdict.gaps`` of the program, the control and the fault, each
against the float32 reference.  ``--exchange-fault`` (cooperative
cells) runs the program a second time with every all-to-all delivering
only what a PE addressed to itself, and reads that too.  The benchmark's own runs do not run
this; its readings and the limits chosen from them are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def local_only_exchange(self, x):
    """All-to-all in which nothing crosses chips."""
    import jax
    import jax.numpy as jnp

    me = jax.lax.axis_index(self.axis_name)
    keep = (jnp.arange(x.shape[0]) == me).reshape((-1,) + (1,) * (x.ndim - 1))
    empty = jnp.iinfo(x.dtype).max if jnp.issubdtype(x.dtype, jnp.integer) else 0
    return jnp.where(keep, x, jnp.asarray(empty, x.dtype))


def program_run(jax, spec, ds, seed, clog) -> dict:
    import gc

    import run
    from repro.train import loop

    gnn_cfg = run.gnn_config(spec["config"])
    res, rec, _, _, _ = run.timed_call(
        jax, loop, clog, ds, gnn_cfg, run.train_config(spec, 3), True)
    prog = run.program_state(jax, rec, res)
    del res, rec
    gc.collect()
    return prog


def readings(jax, spec: dict, seed: int, exchange_fault: bool = False) -> dict:
    import run
    import verdict
    from capture import CompileLog
    from repro.core import cooperative

    clog = CompileLog(jax)
    ds, _ = run.make_dataset(jax, spec, seed)
    prog = program_run(jax, spec, ds, seed, clog)
    low = dict(spec, config=dict(spec["config"], dtype="bfloat16"))
    ctrl_prog = program_run(jax, low, ds, seed, clog)
    faulty = None
    if exchange_fault:
        real = cooperative.ShardExecutor.exchange
        cooperative.ShardExecutor.exchange = local_only_exchange
        try:
            faulty = program_run(jax, spec, ds, seed, clog)
        finally:
            cooperative.ShardExecutor.exchange = real
    out = {"seed": seed}
    ref = run.reference_state(jax, spec, ds)
    out["program"] = verdict.gaps(prog, ref)
    out["control_program_bf16"] = verdict.gaps(ctrl_prog, ref)
    if faulty is not None:
        out["fault_exchange"] = verdict.gaps(faulty, ref)
    ctrl = run.reference_state(jax, spec, ds, variant="bf16")
    half = run.reference_state(jax, spec, ds, variant="half")
    out["reference_bf16"] = verdict.gaps(ctrl, ref)
    out["fault_half_batch"] = verdict.gaps(half, ref)
    out["leaves"] = {"program": verdict.leaf_gaps(prog, ref),
                     "control_program_bf16": verdict.leaf_gaps(ctrl_prog, ref),
                     "reference_bf16": verdict.leaf_gaps(ctrl, ref),
                     "fault_half_batch": verdict.leaf_gaps(half, ref)}
    if faulty is not None:
        out["leaves"]["fault_exchange"] = verdict.leaf_gaps(faulty, ref)
    out["losses"] = {"program": prog["losses"], "reference": ref["losses"],
                     "control_program_bf16": ctrl_prog["losses"],
                     "reference_bf16": ctrl["losses"], "fault_half_batch": half["losses"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None, help="also append the lines here")
    ap.add_argument("--exchange-fault", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

    import jax

    import run

    spec = run.load_spec(ROOT, args.workload)
    if len(jax.devices()) < spec["cell"]["chips"]:
        print("readings: not enough devices", file=sys.stderr)
        return 2
    cache = os.path.join(ROOT, ".jax_cache")
    run.repair_cache(cache)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    for seed in args.seeds:
        t = time.perf_counter()
        line = readings(jax, spec, seed, args.exchange_fault)
        line.update(workload=args.workload, seconds=time.perf_counter() - t,
                    device=jax.devices()[0].device_kind)
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
