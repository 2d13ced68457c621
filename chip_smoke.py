"""Smoke test on the chip: GNN minibatch training on a TPU, end to end.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # four chips, cooperative path only

Default: trains the papers100M-width GCN (feature dim 128, hidden 1024,
172 classes, 3 layers, LABOR-0 with fanout 10, degree cap 32, b = 1024)
through ``train_gnn`` in independent mode for a few steps, on a seeded
RMAT graph with random weights.  It checks that every loss is finite,
that the parameters moved, and that the train step compiled exactly
once; then it runs the two plan kernels that compile for the TPU
(``unique_compact``, ``expand_indptr``) against their jnp oracles.

``--four-chips``: the cooperative ``executor="shard"`` path over a
4-chip mesh at the same widths, plus its comparison with the one-device
``sim`` oracle (bit-identical integer plans; loss and gradients at the
tolerances of ``tests/test_coop_shard.py``), and checks that the mesh,
the parameters and the per-PE plan shards span four distinct chips.

The script exits non-zero without a result when JAX finds no TPU or
when it does not sit at the root of this repository.  Earlier lines
report the cuts and set-up numbers (for information only); the last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# papers100M / GCN widths (paper Table 2 and A.5)
FEAT, HIDDEN, CLASSES, LAYERS = 128, 1024, 172, 3
FANOUT, DEG_CAP, BATCH = 10, 32, 1024
# Graph500's RMAT skew (a = 0.57) leaves the mean degree under 17 after
# the degree cap at any affordable edge factor; a flatter skew reaches
# papers100M's mean of ~29 under the cap of 32
RMAT = dict(edge_factor=16, a=0.30, b=0.22, c=0.22, max_degree=DEG_CAP)
# V = 2^20: host generation (graph ~45 s, features and labels ~49 s on
# the chip's host) is most of a call and doubles with V, while HBM is not
# the limit (one chip peaked at 2.8 GB at V = 2^21; on four chips the
# per-PE plans at b = 1024, not V, set the memory)
SCALE = 20
PARITY_BATCH = 32     # per PE: the one-device sim oracle holds 4 of them


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return 1


class CompileLog:
    """Counts lowerings and backend compile seconds per jitted function,
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.lowered: dict = {}
        self.compile_s: dict = {}
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        name = kw.get("fun_name", "?")
        if event.endswith("/jaxpr_to_mlir_module_duration"):
            self.lowered[name] = self.lowered.get(name, 0) + 1
        elif event.endswith("/backend_compile_duration"):
            self.compile_s[name] = self.compile_s.get(name, 0.0) + secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def summary(self) -> str:
        return (f"backend compile {sum(self.compile_s.values()):.1f} s over "
                f"{len(self.compile_s)} programs; persistent cache "
                f"{self.cache_hits} hits, {self.cache_misses} misses")


def make_dataset(scale: int, seed: int):
    from repro.data import SyntheticGraphDataset, rmat_graph

    t0 = time.perf_counter()
    g = rmat_graph(scale=scale, seed=seed, **RMAT)
    t1 = time.perf_counter()
    ds = SyntheticGraphDataset(g, feature_dim=FEAT, num_classes=CLASSES, seed=seed)
    t2 = time.perf_counter()
    log(f"graph: V=2^{scale}={g.num_vertices} E={g.num_edges} "
        f"mean degree {g.num_edges / g.num_vertices:.2f} (cap {DEG_CAP}); "
        f"RMAT {RMAT}; built in {t1 - t0:.1f} s, features+labels in "
        f"{t2 - t1:.1f} s")
    return ds


def gnn_config():
    from repro.models.gnn import GNNConfig

    return GNNConfig(model="gcn", num_layers=LAYERS, in_dim=FEAT,
                     hidden_dim=HIDDEN, num_classes=CLASSES)


def check_training(jax, result, init_params, clog, steps: int) -> list:
    """Finite losses, moved parameters, one compile of the train step."""
    import numpy as np

    errors = []
    losses = result.losses
    if len(losses) != steps or not all(math.isfinite(l) for l in losses):
        errors.append(f"losses not all finite: {losses}")
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(init_params),
                        jax.tree.leaves(result.params))
    )
    if not moved:
        errors.append("parameters did not change")
    n = clog.lowered.get("jit(train_step)", 0)
    if n != 1:
        errors.append(f"train_step was compiled {n} times, expected once")
    log(f"losses {[round(l, 4) for l in losses]}; train_step compiled "
        f"{n}x ({clog.compile_s.get('jit(train_step)', 0.0):.1f} s backend)")
    return errors


def train(jax, ds, tc, clog):
    from repro.models.gnn import init_gnn
    from repro.train.loop import train_gnn

    gnn_cfg = gnn_config()
    init_params = init_gnn(jax.random.PRNGKey(tc.seed), gnn_cfg)
    t0 = time.perf_counter()
    result = train_gnn(ds, gnn_cfg, tc)
    jax.block_until_ready(result.params)
    log(f"train_gnn({tc.mode}, executor={tc.executor}, P={tc.num_pes}, "
        f"b={tc.local_batch}): {tc.num_steps} steps in "
        f"{time.perf_counter() - t0:.1f} s wall, compile included")
    return result, init_params


def one_chip(jax, args, clog) -> list:
    import jax.numpy as jnp
    import numpy as np

    from repro import kernels
    from repro.core import frontier
    from repro.kernels.expand_indptr.ref import expand_indptr_ref
    from repro.train.loop import TrainConfig

    ds = make_dataset(SCALE, args.seed)
    tc = TrainConfig(mode="independent", num_pes=1, local_batch=BATCH,
                     num_steps=args.steps, fanout=FANOUT, seed=args.seed,
                     eval_every=0)
    result, init_params = train(jax, ds, tc, clog)
    errors = check_training(jax, result, init_params, clog, args.steps)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")

    # the compiled plan kernels against their oracles, at hop 0/1 widths
    rng = np.random.default_rng(args.seed)
    cap = 14080
    ids = rng.integers(0, ds.graph.num_vertices, BATCH * (1 + DEG_CAP))
    ids[rng.random(ids.size) < 0.3] = frontier.INVALID
    ids = jnp.asarray(ids.astype(np.int32))
    uniq, inv = kernels.unique_with_inverse(ids, cap)
    want = frontier.unique_padded(ids, cap)
    if not (np.array_equal(uniq, want)
            and np.array_equal(inv, frontier.lookup(want, ids))):
        errors.append("unique_compact kernel disagrees with its oracle")
    deg = rng.integers(0, DEG_CAP + 1, BATCH)     # hop 0's b rows
    iptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]).astype(np.int32))
    n_edges = BATCH * DEG_CAP - 7  # not a block multiple: the wrapper pads
    if not np.array_equal(kernels.expand_indptr(iptr, n_edges),
                          expand_indptr_ref(iptr, n_edges)):
        errors.append("expand_indptr kernel disagrees with its oracle")
    log("plan kernels unique_compact, expand_indptr checked against oracles")
    return errors


def four_chips(jax, args, clog) -> list:
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.engine import EngineConfig, MinibatchEngine
    from repro.models.gnn import init_gnn
    from repro.train.loop import TrainConfig, make_loss_fn

    errors = []
    P = 4
    ds = make_dataset(SCALE, args.seed)
    gnn_cfg = gnn_config()

    # shard vs the one-device sim oracle, at a batch the oracle holds;
    # float32 matmuls so the tolerances of the CPU test apply
    cfg = EngineConfig(
        mode="cooperative", num_pes=P, local_batch=PARITY_BATCH,
        num_layers=LAYERS, fanout=FANOUT, schedule="smoothed", kappa=3,
        partition="hash", seed=args.seed,
    )
    sim = MinibatchEngine.from_config(ds.graph, cfg, dataset=ds)
    sh = MinibatchEngine.from_config(
        ds.graph, dataclasses.replace(cfg, executor="shard"), dataset=ds)
    mesh_devs = list(sh.shard_runner.mesh.devices.flat)
    if (len({d.id for d in mesh_devs}) != P
            or any(d.platform != "tpu" for d in mesh_devs)):
        errors.append(f"mesh is not {P} distinct TPU chips: {mesh_devs}")
    params = init_gnn(jax.random.PRNGKey(args.seed), gnn_cfg)
    with jax.default_matmul_precision("float32"):
        lg_sim = jax.jit(jax.value_and_grad(
            make_loss_fn(sim, gnn_cfg, sim.store, ds.labels)))
        lg_sh = jax.jit(sh.shard_runner.make_loss_and_grad(
            gnn_cfg, sh.store.features, ds.labels))
        worst = {"loss": 0.0, "grad": 0.0}
        plans_equal = True
        for step in range(2):
            plan_sim, plan_sh = sim.plan_at(step), sh.plan_at(step)
            if not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                       zip(jax.tree.leaves(plan_sim), jax.tree.leaves(plan_sh))):
                plans_equal = False
                errors.append(f"step {step}: shard plan differs from sim")
            devs = {s.device.id for leaf in jax.tree.leaves(plan_sh)
                    for s in leaf.addressable_shards}
            if len(devs) != P:
                errors.append(f"plan shards live on {sorted(devs)}")
            l1, g1 = lg_sim(params, jnp.int32(step))
            l2, g2 = lg_sh(params, jnp.int32(step))
            try:
                np.testing.assert_allclose(float(l1), float(l2), rtol=5e-6)
                for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               atol=5e-6, rtol=1e-4)
            except AssertionError as e:
                errors.append(f"step {step}: shard loss/grads differ from sim: {e}")
            worst["loss"] = max(worst["loss"], abs(float(l1) - float(l2)))
            worst["grad"] = max(worst["grad"], max(
                float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2))))
    log(f"shard vs sim at b={PARITY_BATCH}/PE: integer plans "
        f"{'identical' if plans_equal else 'DIFFER'}; max |loss diff| "
        f"{worst['loss']:.3g}, max |grad diff| {worst['grad']:.3g}")
    del lg_sim, lg_sh, sim, sh

    tc = TrainConfig(mode="cooperative", executor="shard", num_pes=P,
                     local_batch=BATCH, num_steps=args.steps, fanout=FANOUT,
                     seed=args.seed, eval_every=0)
    result, init_params = train(jax, ds, tc, clog)
    errors += check_training(jax, result, init_params, clog, args.steps)
    spread = {d.id for leaf in jax.tree.leaves(result.params)
              for d in leaf.sharding.device_set}
    if len(spread) != P:
        errors.append(f"parameters live on devices {sorted(spread)}")
    for d in jax.devices()[:P]:
        stats = d.memory_stats() or {}
        log(f"device {d.id}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 4-chip cooperative shard path and its "
                         "comparison with the sim oracle, nothing else")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no src/repro next to {__file__}: run from the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found: JAX runs on {devices[0].platform!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        return fail(f"needs {need} TPU chips, JAX sees {len(devices)}")

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    clog = CompileLog(jax)
    log(f"device_kind {devices[0].device_kind!r} x{len(devices)}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    errors = (four_chips if args.four_chips else one_chip)(jax, args, clog)
    log(clog.summary())
    log(f"total {time.perf_counter() - t0:.1f} s")
    if errors:
        for e in errors:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
