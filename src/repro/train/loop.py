"""GNN training driver over the unified :class:`MinibatchEngine`.

Both minibatching modes run the *same* model code, the same loss path,
and the same global batch size — exactly the paper's controlled
comparison (§4.3, Fig. 9).  The mode lives entirely inside the engine:

* independent: P PEs × local batch b, P separate plans (vmap-stacked),
  gradients averaged across PEs (the standard data-parallel all-reduce).
* cooperative: ONE global batch of size b·P partitioned by ownership,
  all-to-all exchanges during sampling + F/B (Alg. 1), gradients
  averaged across PEs.

The training step below never branches on the mode: it builds a plan,
gathers input features through it, applies the model through the
engine, and supervises the seed frontier.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import frontier
from repro.core.graph import INVALID
from repro.engine import EngineConfig, MinibatchEngine
from repro.engine.shard import ShardRunner
from repro.models.gnn import GNNConfig, init_gnn
from repro.train.metrics import masked_softmax_xent, micro_f1
from repro.train.optim import adam_init, adam_update


@dataclass
class TrainConfig:
    mode: str = "cooperative"        # independent | cooperative
    num_pes: int = 4
    local_batch: int = 64            # b; global batch = b * P
    num_steps: int = 100
    lr: float = 1e-3
    sampler: str = "labor0"
    fanout: int = 10
    schedule: str = "smoothed"       # iid | smoothed | nested
    kappa: Optional[int] = 1         # dependent-minibatching window
    partition: str = "hash"
    seed: int = 0
    eval_every: int = 25
    plan_backend: str = "reference"  # reference | fused (Pallas on TPU)
    executor: str = "sim"            # sim | shard (real P-device mesh)

    def engine_config(self, num_layers: int) -> EngineConfig:
        return EngineConfig(
            mode=self.mode, num_pes=self.num_pes, local_batch=self.local_batch,
            num_layers=num_layers, sampler=self.sampler, fanout=self.fanout,
            schedule=self.schedule, kappa=self.kappa, partition=self.partition,
            seed=self.seed, plan_backend=self.plan_backend,
            executor=self.executor,
        )


@dataclass
class TrainResult:
    params: dict
    losses: list = field(default_factory=list)
    val_f1: list = field(default_factory=list)
    # host seconds of each step, from dispatch to the loss read-back
    step_s: list = field(default_factory=list)
    # times the step's body was traced: 1 unless something recompiled it
    step_traces: int = 0


def make_loss_fn(engine: MinibatchEngine, gnn_cfg: GNNConfig, store, labels):
    """Single mode-agnostic loss path: plan -> features -> logits -> xent.

    ``plan_at`` folds the seed draw and schedule RNG into the trace, so
    the whole step is device-resident.  Used by the sim/vmap executors;
    the shard executor's equivalent lives in
    :meth:`repro.engine.shard.ShardRunner.make_loss_and_grad` with the
    same masked-mean semantics.
    """
    V = engine.graph.num_vertices
    labels = jnp.asarray(labels)

    def loss_fn(params, step):
        plan = engine.plan_at(step)
        H = plan.gather_inputs(store)
        logits = engine.apply_model(params, gnn_cfg, plan, H)
        with jax.named_scope("gnn.loss"):
            y = labels[jnp.clip(plan.seed_ids, 0, V - 1)]
            valid = plan.seed_ids != INVALID
            return masked_softmax_xent(
                logits.reshape(-1, logits.shape[-1]), y.reshape(-1),
                valid.reshape(-1),
            )

    return loss_fn


def train_gnn(dataset, gnn_cfg: GNNConfig, tc: TrainConfig) -> TrainResult:
    """Train for ``tc.num_steps`` steps of one jitted ``train_step``.

    Under ``jax.profiler.trace`` the call leaves host spans
    ``train_gnn.setup``, ``train_gnn.step`` (one per step, with its step
    number) and ``train_gnn.eval``; the step's device ops carry the
    named scopes listed in docs/architecture.md ("Tracing").
    """
    shard = tc.executor == "shard" and tc.mode == "cooperative"
    with jax.profiler.TraceAnnotation("train_gnn.setup"):
        engine = MinibatchEngine.from_config(
            dataset.graph, tc.engine_config(gnn_cfg.num_layers), dataset=dataset
        )
        params = init_gnn(jax.random.PRNGKey(tc.seed), gnn_cfg)
        opt = adam_init(params)
        # graph, features and labels enter the step as arguments: closed
        # over, XLA would embed them in the executable as constants
        data = (engine.device_arrays(), jnp.asarray(dataset.labels))
        if shard:
            mesh = engine.shard_runner.mesh
            # start from the layout the step returns (replicated over the
            # mesh), so the first step does not compile a second program
            params, opt, data = jax.device_put(
                (params, opt, data), NamedSharding(mesh, PartitionSpec())
            )

    def loss_and_grad(params, step, data):
        arrays, labels = data
        eng = engine.with_arrays(*arrays)
        if shard:
            # real multi-device path: per-PE plan build + cooperative F/B
            # run under shard_map on a P-device mesh, and gradient sync is
            # an explicit jax.lax.psum over the same axis as the all-to-alls
            fn = ShardRunner.for_engine(eng, mesh).make_loss_and_grad(
                gnn_cfg, eng.store.features, labels
            )
        else:
            fn = jax.value_and_grad(make_loss_fn(eng, gnn_cfg, eng.store, labels))
        return fn(params, step)

    result = TrainResult(params=params)

    @jax.jit
    def train_step(params, opt, step, data):
        result.step_traces += 1
        loss, grads = loss_and_grad(params, step, data)
        with jax.named_scope("optim.update"):
            params, opt = adam_update(params, grads, opt, lr=tc.lr)
        return params, opt, loss

    for step in range(tc.num_steps):
        # `step` is a dynamic arg: seed draw and smoothed-RNG state
        # (z1, z2, c) are computed inside the compiled step, so one trace
        # serves the whole kappa schedule.
        with jax.profiler.StepTraceAnnotation("train_gnn.step", step_num=step):
            t = time.perf_counter()
            params, opt, loss = train_step(params, opt, jnp.int32(step), data)
            result.losses.append(float(loss))
            result.step_s.append(time.perf_counter() - t)
        if tc.eval_every and (step + 1) % tc.eval_every == 0:
            with jax.profiler.TraceAnnotation("train_gnn.eval"):
                result.val_f1.append(evaluate(dataset, gnn_cfg, params, tc))
        result.params = params
    return result


def evaluate(
    dataset, gnn_cfg: GNNConfig, params, tc: TrainConfig, split: str = "val",
    max_batches: int = 4,
) -> float:
    """Micro-F1 with (independent) sampled neighborhoods — Fig. 4 style."""
    eval_engine = MinibatchEngine.from_config(
        dataset.graph,
        EngineConfig(
            mode="independent", num_pes=1, local_batch=tc.local_batch,
            num_layers=gnn_cfg.num_layers, sampler=tc.sampler,
            fanout=tc.fanout, schedule="iid", seed=tc.seed + 999,
        ),
        dataset=dataset,
    )
    ids_all = {"val": dataset.val_ids, "test": dataset.test_ids}[split]
    preds, ys = [], []
    for i in range(max_batches):
        lo = i * tc.local_batch
        ids = ids_all[lo : lo + tc.local_batch]
        if len(ids) == 0:
            break
        seeds = frontier.pad_to(jnp.asarray(ids, jnp.int32), tc.local_batch)
        plan = eval_engine.build_plan(seeds, step=i)  # iid schedule @ seed+999
        h = plan.gather_inputs(eval_engine.store)
        logits = eval_engine.apply_model(params, gnn_cfg, plan, h)
        valid = np.asarray(plan.seed_ids) != INVALID
        pred = np.asarray(jnp.argmax(logits, -1))[valid]
        y = np.asarray(dataset.labels)[np.asarray(plan.seed_ids)[valid]]
        preds.append(pred)
        ys.append(y)
    return micro_f1(np.concatenate(preds), np.concatenate(ys))
