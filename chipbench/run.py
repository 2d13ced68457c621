"""Benchmark harness: one run of one cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json`` names its configuration (``chipbench/configs/``),
its traffic (``chipbench/traffic/<traffic>.json``) and has its
correctness limits in ``chipbench/cells/<cell>.json``; each metric is
read by ``chipbench/metrics/<metric>.py``, a module with
``read(ctx) -> float | None``; the configuration's model is
``chipbench/models/<model>.py`` (``byname.py``).

A run generates the dataset on the device from ``--seed``, makes one
warm-up call of the training loop ``repro.train.loop.train_gnn``
(which compiles or loads the step program from the persistent cache in
``.jax_cache/`` at the root of the checkout), then times one call of it
sized to fill ``--seconds``.  The window runs from the moment that
call's step program is ready to its return, after ``block_until_ready``
on the parameters.  With ``--trace 1`` a short further call runs under
the profiler.  Then the program's state is freed and the plain
reference (``reference.py``) replays the call's first three steps;
``verdict.py`` compares them.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its
limit); the last lines of standard error repeat the checks.  Without a
TPU, or with fewer chips than the cell asks for, it exits with 2 and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import byname  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec(root: str, workload: str) -> dict:
    """The cell, its configuration, traffic, limits and metrics."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    here = os.path.join(root, os.path.relpath(HERE, ROOT))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {
        "name": workload,
        "cell": cell,
        "config": _json(os.path.join(root, files[cell["config"]])),
        "traffic": _json(os.path.join(here, "traffic", cell["traffic"] + ".json")),
        "limits": _json(os.path.join(here, "cells", workload + ".json"))["limits"],
        "end_to_end": e2e,
        "per_layer": per_layer,
        "metrics_dir": os.path.join(here, "metrics"),
    }


def read_metric(metrics_dir: str, name: str, ctx: dict):
    path = os.path.join(metrics_dir, name + ".py")
    label = "chipbench_metric_" + name.replace(".", "_").replace("-", "_")
    return byname.load(path, label).read(ctx)


def gnn_config(cfg: dict):
    """The program's model: the fields every model has, and those its
    module (``chipbench/models/<model>.py``) adds."""
    import jax.numpy as jnp

    from repro.models.gnn import GNNConfig

    return GNNConfig(
        model=cfg["model"], num_layers=cfg["num_layers"],
        in_dim=cfg["feature_dim"], hidden_dim=cfg["hidden_dim"],
        num_classes=cfg["num_classes"], dtype=jnp.dtype(cfg["dtype"]),
        **byname.model(cfg["model"]).program_args(cfg),
    )


# The program's own seed (``TrainConfig.seed``: initial weights and the
# hash keys of the seed draw and the sampler) is a constant inside the
# compiled step, so every new value compiles the step again (42-46 s on
# a v5e).  Runs therefore share this one; ``--seed`` makes the data.
PROGRAM_SEED = 0


def train_config(spec: dict, steps: int):
    from repro.train.loop import TrainConfig

    cfg, tr = spec["config"], spec["traffic"]
    return TrainConfig(
        mode=tr["mode"], num_pes=tr["num_pes"], local_batch=cfg["local_batch"],
        num_steps=steps, lr=cfg["lr"], sampler=cfg["sampler"],
        fanout=cfg["fanout"], schedule=tr["schedule"], kappa=tr["kappa"],
        partition=tr["partition"], seed=PROGRAM_SEED, eval_every=0,
        plan_backend=tr["plan_backend"], executor=tr["executor"],
    )


def make_dataset(jax, spec: dict, seed: int):
    import graphgen

    cfg = spec["config"]
    ds, yielded = graphgen.generate(
        seed, graphgen.GraphSpec.from_config(cfg), cfg["num_edges"])
    jax.block_until_ready((ds.graph.indptr, ds.features))
    return ds, yielded


def timed_call(jax, loop, clog, ds, gnn_cfg, tc, record: bool,
               keep_args: bool = False):
    """One ``train_gnn`` call: (result, recorder, call start, ready, end)."""
    from capture import StepRecorder

    mark = clog.mark()
    t_call = time.perf_counter()
    rec = StepRecorder(loop, keep_args)
    if record:
        with rec:
            res = loop.train_gnn(ds, gnn_cfg, tc)
            jax.block_until_ready(res.params)
    else:
        res = loop.train_gnn(ds, gnn_cfg, tc)
        jax.block_until_ready(res.params)
    t_end = time.perf_counter()
    ready = clog.ready_at(mark) or t_call
    late = [e for e in clog.since(mark) if e[0] > ready]
    if late:
        log(f"{len(late)} programs compiled or loaded after the step was "
            f"ready: {[e[1] for e in late]}")
    return res, rec, t_call, ready, t_end


def program_state(jax, rec, res) -> dict:
    """The program's first three steps as host arrays (see verdict.py)."""
    import numpy as np

    if rec.unrecognised or rec.p3 is None:
        raise RuntimeError(rec.unrecognised or "fewer than three steps recorded")
    host = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float32), t)
    # Adam's first moment after one step is (1 - beta1) * g
    return {"losses": list(res.losses[:3]),
            "g1": jax.tree.map(lambda m: np.asarray(m, np.float32) / np.float32(0.1),
                               rec.opt1.mu),
            "p0": host(rec.p0), "p_end": host(rec.p3)}


def reference_state(jax, spec: dict, ds, variant: str = "f32") -> dict:
    import numpy as np
    import reference

    cfg, tr = spec["config"], spec["traffic"]
    g = ds.graph
    hg = reference.HostGraph(
        indptr=np.asarray(g.indptr), indices=np.asarray(g.indices),
        etypes=None if g.edge_types is None else np.asarray(g.edge_types),
        num_vertices=g.num_vertices, max_degree=g.max_degree)
    return reference.run(
        hg, ds.features, np.asarray(ds.labels), ds.train_ids,
        seed=PROGRAM_SEED, cfg=cfg, mode=tr["mode"], num_pes=tr["num_pes"],
        steps=3, variant=variant)


def plan_counts(jax, ds, gnn_cfg, tc, steps: int):
    """Real frontier sizes S_0..S_L and sampled edges E_0..E_{L-1} of the
    window's steps, summed over PEs, from the program's own plans."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.engine import MinibatchEngine
    from repro.engine.shard import ShardRunner

    INVALID = np.iinfo(np.int32).max
    eng = MinibatchEngine.from_config(
        ds.graph, tc.engine_config(gnn_cfg.num_layers), dataset=ds)
    arrays = eng.device_arrays()
    shard = tc.mode == "cooperative" and tc.executor == "shard"
    if shard:
        mesh = eng.shard_runner.mesh
        arrays = jax.device_put(arrays, NamedSharding(mesh, PartitionSpec()))

    @jax.jit
    def counts(arrays, step):
        e = eng.with_arrays(*arrays)
        plan = (ShardRunner.for_engine(e, mesh).plan_at(step) if shard
                else e.plan_at(step))
        S = [jnp.sum(ly.seeds != INVALID) for ly in plan.layers]
        S.append(jnp.sum(plan.input_ids != INVALID))
        E = [jnp.sum(ly.mask) for ly in plan.layers]
        return jnp.stack(S + E)

    rows = [counts(arrays, jnp.int32(s)) for s in range(steps)]
    return np.asarray(jax.device_get(jnp.stack(rows)))


def traced_call(jax, loop, clog, ds, gnn_cfg, tc, dump_to=None):
    """A short call under the profiler: (reduced trace, step HLO text)."""
    import trace_reduce

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tmp):
            res, rec, _, _, _ = timed_call(jax, loop, clog, ds, gnn_cfg, tc,
                                           True, keep_args=True)
            del res
        hlo = rec.hlo()
        del rec
        path = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                             "*.xplane.pb")))[-1]
        planes = trace_reduce.load_xplane(path)
        red = trace_reduce.reduce(planes)
        if dump_to:
            d0 = red.devices[0]
            one_step = d0.modules[-1]
            trace_reduce.dump(planes, dump_to + ".json.gz", one_step.start_ns,
                              one_step.end_ns, min_host_ns=2e5)
            with open(dump_to + ".hlo.txt", "w") as f:
                f.write(hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return red, hlo


def run_cell(jax, spec: dict, seed: int, seconds: float, trace: bool,
             t0: float, dump_trace: str | None = None) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    import peaks
    import verdict
    from capture import CompileLog
    from repro.train import loop

    clog = CompileLog(jax)
    cfg, tr = spec["config"], spec["traffic"]
    chips = spec["cell"]["chips"]
    devices = jax.devices()[:chips]
    gnn_cfg = gnn_config(cfg)

    t = time.perf_counter()
    log(f"started: {t - t0:.3f} s after the process")
    ds, yielded = make_dataset(jax, spec, seed)
    log(f"dataset: V={ds.graph.num_vertices} E={ds.graph.num_edges} "
        f"(generator yielded {yielded}), {time.perf_counter() - t:.3f} s")

    warm_steps = tr["warmup_steps"]
    warm, _, t_call, ready, t_end = timed_call(
        jax, loop, clog, ds, gnn_cfg, train_config(spec, warm_steps), False)
    step_s = (t_end - ready) / warm_steps
    del warm
    steps = max(3, math.ceil(seconds / step_s))
    log(f"warm-up: {ready - t_call:.3f} s to the step program, {warm_steps} "
        f"steps at {step_s * 1e3:.2f} ms -> {steps} steps")

    tc = train_config(spec, steps)
    res, rec, t_call, ready, t_end = timed_call(jax, loop, clog, ds, gnn_cfg, tc, True)
    window_s = t_end - ready
    losses = res.losses
    failed = sum(not math.isfinite(x) for x in losses)
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    try:
        prog = program_state(jax, rec, res)
        problem = None
    except RuntimeError as e:
        prog, problem = None, str(e)
    del res, rec
    gc.collect()
    log(f"window: {steps} steps in {window_s:.4f} s; call overhead "
        f"{ready - t_call:.3f} s; set-up {ready - t0:.3f} s; losses {losses[:4]}")
    log(f"set-up programs: {clog.summary()}")

    ctx = {
        "setup_s": ready - t0, "window_s": window_s, "steps": steps,
        "seeds": steps * tc.local_batch * tc.num_pes,
        "call_overhead_s": ready - t_call,
        "compile_s": clog.compile_s(), "chips": chips, "config": cfg,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem_peak)}
    out = {}
    if trace:
        red, hlo = traced_call(jax, loop, clog, ds, gnn_cfg,
                               train_config(spec, tr["trace_steps"]),
                               dump_trace)
        ctx.update(trace=red, hlo=hlo, trace_steps=tr["trace_steps"],
                   peaks=peaks.peaks_for(devices[0].device_kind),
                   plan_counts=plan_counts(jax, ds, gnn_cfg, tc, steps))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        import opclass
        import trace_reduce

        cls = ctx.setdefault("classifier", opclass.Classifier(hlo))
        out["breakdown"] = trace_reduce.breakdown(
            red, label=lambda op: f"{op.short} {cls.category(op.name)}")
        gc.collect()
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        v = read_metric(spec["metrics_dir"], m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    limits = spec["limits"]
    if prog is not None:
        ref = reference_state(jax, spec, ds)
        numbers = verdict.gaps(prog, ref)
        log(f"reference losses {ref['losses']}; program {prog['losses']}")
        log(f"per leaf: {verdict.leaf_gaps(prog, ref)}")
        correct = verdict.judge(numbers, limits) and failed == 0
    else:
        log(f"not correct: {problem}")
        numbers = {k: float("inf") for k in verdict.NAMES}
        correct = False
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    log("not compared: " + ", ".join(
        f"{k} {numbers[k]!r}" for k in verdict.NAMES if k not in limits))
    result = {"correct": bool(correct), "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    result.update(out)
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def repair_cache(path: str) -> None:
    """Give every cache entry an access-time file.  JAX's cache, where a
    size limit is set, reads one per entry before each write, and a
    directory filled without the limit has none: every write then fails."""
    if not os.path.isdir(path):
        return
    for name in os.listdir(path):
        if name.endswith("-cache"):
            atime = os.path.join(path, name[: -len("-cache")] + "-atime")
            if not os.path.exists(atime):
                with open(atime, "wb") as f:
                    f.write(time.time_ns().to_bytes(8, "little"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="path prefix: also write the traced run's last step "
                         "(<prefix>.json.gz) and the step's HLO (<prefix>.hlo.txt)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no src/repro next to {HERE}: run from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    spec = load_spec(ROOT, args.workload)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

    import jax

    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU: JAX runs on {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"chipbench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache = os.path.join(ROOT, ".jax_cache")
    repair_cache(cache)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size limit set for a shared cache would evict this
    # checkout's own programs, and every run would compile them again
    jax.config.update("jax_compilation_cache_max_size", -1)

    result = run_cell(jax, spec, args.seed, args.seconds, bool(args.trace), T0,
                      args.dump_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
