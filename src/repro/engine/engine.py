"""``MinibatchEngine`` — the unified minibatch-construction facade.

The paper's central comparison (§3.1–§3.2, Fig. 7) runs *the same*
training computation under two minibatching modes at identical global
batch size.  The engine makes that a config flag instead of two API
stacks: ``from_config`` derives capacity plans, partitions, executors,
and seed-batch generators from one :class:`EngineConfig`; ``build_plan``
returns a :class:`repro.engine.Plan` either way; ``apply_model`` owns
the single remaining mode dispatch (per-PE vmap vs all-to-all
redistribution).  The low-level builders (``build_minibatch``,
``build_cooperative_minibatch``) stay the stable kernel layer — the
engine never re-implements sampling, it only wires it.

Dependency schedules (§3.2 + A.7) are uniform too: ``iid`` (fresh seed
per step), ``smoothed`` (κ-window RNG interpolation), and ``nested``
(κ sub-batches carved from one group batch under a frozen group RNG).
``rng_state(step)`` is traceable, so one compiled train step serves the
whole schedule.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import cached_property
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cooperative import (
    CoopCapacityPlan,
    CoopMinibatch,
    Executor,
    ShardExecutor,
    SimExecutor,
    build_cooperative_minibatch,
)
from repro.core.dependent import NestedSchedule
from repro.core.feature_loader import FeatureStore
from repro.core.graph import Graph, INVALID
from repro.core.minibatch import CapacityPlan, Minibatch, build_minibatch
from repro.core.partition import Partition, make_partition
from repro.core.rng import DependentRNG, RNGState, _mix, hash_u32
from repro.core.samplers.base import Sampler, make_sampler
from repro.engine.config import EngineConfig
from repro.engine.plan import Plan
from repro.engine.stream import MinibatchStream
from repro.store.tiers import TieredFeatureStore


@jax.jit
def _hash_permute_rows(rows: jax.Array, z: jax.Array) -> jax.Array:
    """Row-wise hash-keyed permutation of an INVALID-padded pool table.

    Valid ids get uint32 keys (clamped below the sentinel key) and sort
    by them; INVALID entries pin to the key maximum so padding stays at
    every row's tail.  Stable argsort makes collisions deterministic.
    """
    salt = jnp.arange(rows.shape[0], dtype=jnp.uint32)[:, None]
    key = hash_u32(rows, z, salt)
    key = jnp.where(
        rows != INVALID,
        jnp.minimum(key, jnp.uint32(0xFFFFFFFE)),
        jnp.uint32(0xFFFFFFFF),
    )
    order = jnp.argsort(key, axis=1, stable=True)
    return jnp.take_along_axis(rows, order, axis=1)


@dataclass
class MinibatchEngine:
    """One object that turns (graph, config) into a stream of plans."""

    config: EngineConfig
    graph: Graph
    sampler: Sampler
    caps: CapacityPlan | CoopCapacityPlan
    ex: Optional[Executor] = None           # cooperative only
    part: Optional[Partition] = None        # cooperative only
    dataset: Optional[object] = None        # seeds come from train split if set
    store: Optional[FeatureStore] = None
    tiered: Optional[TieredFeatureStore] = None  # device cache tier, optional

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------
    @classmethod
    def from_config(
        cls, graph: Graph, config: EngineConfig, dataset=None
    ) -> "MinibatchEngine":
        """Derive capacities, partition, and executor from the config."""
        graph.validate()  # malformed CSR fails here, not mid-stream
        cfg, cap = config, config.capacity
        V = graph.num_vertices
        sampler = make_sampler(
            cfg.sampler, fanout=cfg.fanout, backend=cfg.plan_backend
        )
        if cfg.mode == "cooperative":
            caps = CoopCapacityPlan.geometric(
                cfg.local_batch, cfg.num_layers, cfg.fanout, V, cfg.num_pes,
                safety=cap.coop_safety, bucket_safety=cap.bucket_safety,
                round_to=cap.round_to,
            )
            pseed = cfg.seed if cfg.partition_seed is None else cfg.partition_seed
            part = make_partition(cfg.partition, graph, cfg.num_pes, seed=pseed)
            ex: Executor = (
                SimExecutor(cfg.num_pes)
                if cfg.executor == "sim"
                else ShardExecutor(cfg.num_pes, axis_name=cfg.axis_name)
            )
        else:
            caps = CapacityPlan.geometric(
                cfg.local_batch, cfg.num_layers, cfg.fanout, V,
                safety=cap.safety, round_to=cap.round_to,
            )
            part, ex = None, None
        store = FeatureStore(dataset.features) if dataset is not None else None
        tiered = None
        if dataset is not None and cfg.cache.enabled:
            cap = cfg.cache.capacity
            if cap is None:
                cap = max(cfg.cache.ways, V // 4)
            cap -= cap % cfg.cache.ways  # CLOCK sets need capacity % ways == 0
            tiered = TieredFeatureStore(
                dataset.features, capacity=cap, ways=cfg.cache.ways,
                num_pes=cfg.num_pes,
            )
        return cls(
            config=cfg, graph=graph, sampler=sampler, caps=caps, ex=ex,
            part=part, dataset=dataset, store=store, tiered=tiered,
        )

    # ------------------------------------------------------------------
    # Device arrays, as arguments of a jitted step
    # ------------------------------------------------------------------
    def device_arrays(self) -> tuple:
        """The arrays a plan and its feature fetch read: graph, owner
        map, seed pool table and features.  A jitted step should take
        them as arguments: closed over, XLA embeds them in the executable
        as constants (gigabytes at published sizes)."""
        return (
            self.graph,
            None if self.part is None else self.part.owner,
            self._seed_rows,
            None if self.store is None else self.store.features,
        )

    def with_arrays(self, graph, owner, seed_rows, features) -> "MinibatchEngine":
        """This engine over other arrays of the shapes
        :meth:`device_arrays` returns, e.g. a jitted step's tracers."""
        eng = replace(
            self, graph=graph,
            part=None if owner is None else replace(self.part, owner=owner),
            store=None if features is None else FeatureStore(features),
        )
        eng.__dict__["_seed_rows"] = seed_rows  # fills the cached_property
        return eng

    # ------------------------------------------------------------------
    # RNG schedule
    # ------------------------------------------------------------------
    def _nested_sched(self) -> NestedSchedule:
        cfg = self.config
        return NestedSchedule(
            base_seed=cfg.seed, kappa=cfg.kappa, sub_batch_size=cfg.local_batch
        )

    def rng_at(self, step: int) -> DependentRNG:
        """Host-side RNG for ``step`` under the configured schedule."""
        cfg = self.config
        if cfg.schedule == "nested":
            return self._nested_sched().rng_for_group(step)  # frozen per group
        return DependentRNG(cfg.seed, cfg.effective_kappa, step)

    @jax.named_scope("plan.seed_draw")
    def rng_state(self, step) -> RNGState:
        """Traceable RNG state — ``step`` may be a traced int32 scalar, so
        a single compiled train step covers the whole κ schedule."""
        cfg = self.config
        if cfg.schedule == "nested":
            # traced mirror of NestedSchedule.rng_for_group(step).state —
            # pinned together by test_rng_state_matches_host_schedule
            base = jnp.uint32(cfg.seed & 0xFFFFFFFF)
            w = (jnp.asarray(step, jnp.int32) // cfg.kappa).astype(jnp.uint32)
            return RNGState(base + w, base + w, jnp.float32(0.0))
        return DependentRNG(cfg.seed, cfg.effective_kappa).state_at(step)

    # ------------------------------------------------------------------
    # Seed batches (device-resident, traceable)
    # ------------------------------------------------------------------
    def _seed_pool(self) -> np.ndarray:
        if self.dataset is not None:
            return np.asarray(self.dataset.train_ids)
        return np.arange(self.graph.num_vertices, dtype=np.int32)

    @cached_property
    def _owned_pools(self) -> list[np.ndarray]:
        # cached: the owner transfer + per-PE scans are O(V + P*|pool|),
        # too expensive to redo every training step
        pool = self._seed_pool()
        owner = np.asarray(self.part.owner)
        return [pool[owner[pool] == p] for p in range(self.config.num_pes)]

    @cached_property
    def _seed_rows(self) -> jax.Array:
        """(R, C) int32 device pool table, INVALID-padded rows.

        Cooperative: row p = PE p's owned train ids.  Independent nested:
        the global pool replicated P times (each PE permutes its own
        copy).  Independent otherwise: ONE global row — the first P·b
        entries of its per-step permutation are the global batch, which
        keeps the draw without-replacement *across* PEs.
        """
        cfg = self.config
        P, b = cfg.num_pes, cfg.local_batch
        if cfg.mode == "cooperative":
            rows = self._owned_pools
        elif cfg.schedule == "nested":
            rows = [self._seed_pool()] * P
        else:
            rows = [self._seed_pool()]
        need = cfg.kappa * b if cfg.schedule == "nested" else (
            P * b if len(rows) == 1 else b
        )
        # every row pads to the whole pool, not to the largest row: a
        # cooperative row is what one PE owns of the train ids, which
        # moves with the data, and a width that moved with it would
        # compile a train step per dataset.  Padding sorts last in the
        # draw, so the seeds do not depend on the width.
        C = max(need, len(self._seed_pool()))
        out = np.full((len(rows), C), np.int32(INVALID), np.int32)
        for i, r in enumerate(rows):
            out[i, : len(r)] = np.asarray(r, np.int32)
        # first access may happen while tracing plan_at — keep the cached
        # table a concrete array, not a leaked tracer
        with jax.ensure_compile_time_eval():
            return jnp.asarray(out)

    @jax.named_scope("plan.seed_draw")
    def _seed_batch_traced(self, step) -> jax.Array:
        """(P, b) int32 seed rows for a (possibly traced) ``step``.

        Each draw is a hash-keyed permutation of the pool table: ids get
        uint32 sort keys from :func:`repro.core.rng.hash_u32` under a
        per-(step-or-group, row) salt; INVALID padding is pinned to the
        key maximum so it sorts last.  No host round-trips, so the whole
        seed schedule jits into ``plan_at`` / the train step.  Pools
        smaller than the draw pad with INVALID instead of raising.
        """
        cfg = self.config
        P, b = cfg.num_pes, cfg.local_batch
        step = jnp.asarray(step, jnp.int32)
        rows = self._seed_rows
        base = jnp.uint32(cfg.seed & 0xFFFFFFFF)
        if cfg.schedule == "nested":
            k = cfg.kappa
            g = (step // k).astype(jnp.uint32)
            perm = _hash_permute_rows(rows, _mix(g ^ base * jnp.uint32(0x9E3779B9)))
            i = step % k  # traced sub-batch index -> dynamic slice
            return jax.lax.dynamic_slice_in_dim(perm, i * b, b, axis=1)
        z = _mix(step.astype(jnp.uint32) ^ base * jnp.uint32(0x9E3779B9))
        perm = _hash_permute_rows(rows, z)
        if rows.shape[0] == 1:
            return perm[0, : P * b].reshape(P, b)
        return perm[:, :b]

    def seed_batch(self, step: int) -> np.ndarray:
        """(P, b) int32 seed rows for ``step`` (INVALID-padded short rows).

        Host-side materialization of :meth:`_seed_batch_traced` — same
        bits as the seeds ``plan_at``/the jitted train step consume.
        Independent: P·b ids drawn from the global pool without
        replacement.  Cooperative: row p holds only vertices PE p owns —
        the union is the global batch.  Nested schedules carve b-sized
        sub-batches out of a κ·b group batch redrawn every κ steps
        (§3.2).
        """
        return np.asarray(self._seed_batch_traced(int(step)))

    # ------------------------------------------------------------------
    # Plan construction
    # ------------------------------------------------------------------
    def build_plan(self, seeds, rng=None, step: int = 0) -> Plan:
        """Sample an L-layer plan from a seed frontier.

        ``seeds``: 1-D ``(b,)`` for a single independent plan (bit-equal
        to ``build_minibatch``) or stacked ``(P, b)`` for per-PE plans.
        ``rng`` defaults to the schedule's RNG at ``step``; pass a traced
        :class:`RNGState` from inside a jitted step to avoid retraces.
        ``config.plan_backend`` selects the frontier lowering (reference
        jnp algebra vs fused Pallas kernels) — outputs are bit-identical.
        """
        if rng is None:
            rng = self.rng_at(step)
        seeds = jnp.asarray(seeds, jnp.int32)
        cfg = self.config
        backend = cfg.plan_backend
        if cfg.mode == "cooperative":
            if cfg.executor == "shard":
                raise ValueError(
                    "build_plan runs per-PE bodies eagerly and cannot host "
                    "the shard executor's all_to_all outside shard_map; use "
                    "plan_at (routed through shard_runner) or executor='sim'"
                )
            return build_cooperative_minibatch(
                self.graph, self.sampler, self.part, seeds, rng,
                cfg.num_layers, self.caps, self.ex, backend=backend,
            )
        if seeds.ndim == 1:
            return build_minibatch(
                self.graph, self.sampler, seeds, rng, cfg.num_layers,
                self.caps, backend=backend,
            )
        build_one = lambda s: build_minibatch(
            self.graph, self.sampler, s, rng, cfg.num_layers, self.caps,
            backend=backend,
        )
        return jax.vmap(build_one)(seeds)

    @cached_property
    def _plan_at_compiled(self):
        def build(step):
            seeds = self._seed_batch_traced(step)
            return self.build_plan(seeds, rng=self.rng_state(step))

        return jax.jit(build)

    def plan_at(self, step) -> Plan:
        """Device-resident plan for ``step``: seed draw, schedule RNG and
        sampling compile into ONE jitted program with no host round-trip
        (``step`` is a dynamic int32, so a single trace serves the whole
        run).  Always builds the stacked ``(P, b)`` layout — identical to
        ``build_plan(seed_batch(step), rng=rng_state(step))``.

        With ``executor="shard"`` the build runs under ``shard_map`` on a
        real P-device mesh (id all-to-alls on the wire); integer plan
        state is bit-identical to the SimExecutor build.
        """
        if self.config.executor == "shard" and self.config.mode == "cooperative":
            return self.shard_runner.plan_at(step)
        return self._plan_at_compiled(jnp.asarray(step, jnp.int32))

    @cached_property
    def shard_runner(self):
        """Multi-device runner (``executor="shard"`` only): binds this
        engine to a P-device mesh and runs plan construction and the
        train-step loss under ``jax.shard_map``.  Requires ≥ P devices
        (on CPU: ``XLA_FLAGS=--xla_force_host_platform_device_count=P``
        before importing jax)."""
        from repro.engine.shard import ShardRunner

        return ShardRunner.for_engine(self)

    # ------------------------------------------------------------------
    # Feature loading — through the tiered store when configured
    # ------------------------------------------------------------------
    def gather_features(self, plan: Plan) -> jax.Array:
        """Input-layer embeddings ``H`` for ``plan``.

        With ``feature_cache`` on, the gather runs through the device
        CLOCK cache (bit-exact with the uncached path; misses fill from
        the host tier).  Dependent κ schedules drive its hit rate — the
        paper's §4.2 bandwidth saving, served rather than simulated.
        """
        if self.tiered is not None:
            with jax.named_scope("fetch.inputs"):
                return self.tiered.gather(plan.input_ids)
        if self.store is None:
            raise ValueError(
                "engine has no feature store; construct with a dataset"
            )
        return plan.gather_inputs(self.store)

    # ------------------------------------------------------------------
    # Model application — the one remaining mode dispatch
    # ------------------------------------------------------------------
    def apply_model(self, params, gnn_cfg, plan: Plan, H: jax.Array) -> jax.Array:
        """Seed logits from input embeddings ``H = plan.gather_inputs(...)``.

        Independent: per-PE bipartite compute (vmapped when stacked).
        Cooperative: Alg. 1 forward — all-to-all redistribution between
        layers; the backward all-to-alls fall out of AD.
        """
        from repro.models.gnn import gnn_apply, gnn_apply_cooperative

        if isinstance(plan, CoopMinibatch):
            return gnn_apply_cooperative(
                params, gnn_cfg, self.ex, plan.layers, H, self.caps.tilde_caps
            )
        if plan.input_ids.ndim > 1:  # stacked (P, ...) independent plans
            return jax.vmap(
                lambda layers, h: gnn_apply(params, gnn_cfg, layers, h)
            )(plan.layers, H)
        return gnn_apply(params, gnn_cfg, plan.layers, H)

    # ------------------------------------------------------------------
    # Streaming
    # ------------------------------------------------------------------
    def stream(
        self,
        num_steps: int,
        start_step: int = 0,
        prefetch: int = 2,
        fetch_features: bool = False,
    ) -> MinibatchStream:
        """Iterator over ``(plan, rng, step)`` items with host-side
        double-buffered prefetch (see :class:`MinibatchStream`).
        ``fetch_features`` loads input embeddings at dispatch time so
        tiered-cache fills overlap with the previous step's compute."""
        return MinibatchStream(
            self, num_steps, start_step, prefetch, fetch_features
        )
