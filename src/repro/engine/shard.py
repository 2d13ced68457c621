"""Multi-device cooperative execution: the engine under ``jax.shard_map``.

This is the promotion of :class:`repro.core.cooperative.ShardExecutor`
from a test-only wrapper to a first-class execution path.  A
:class:`ShardRunner` binds a cooperative :class:`MinibatchEngine` to a
real 1-D device mesh (:func:`repro.launch.mesh.make_coop_mesh`) and runs
the per-PE plan-construction and forward/backward bodies inside
``shard_map``, with ``jax.lax.all_to_all`` as the exchange primitive —
the paper's Algorithm 1 on actual devices instead of a vmap simulation.

Layout contract
---------------
Under :class:`SimExecutor` every plan leaf carries a stacked leading
``(P, ...)`` axis on ONE device.  The runner keeps that exact layout at
its boundary: :meth:`ShardRunner.plan_at` returns a stacked
:class:`CoopMinibatch` whose leaves are *device-sharded* along the mesh
axis.  Inside the ``shard_map`` body each PE sees its own ``(1, ...)``
shard, builds its local plan with :class:`ShardExecutor` (identity
``pe``, ``all_to_all`` exchange), and the runner re-attaches the leading
axis.  Because the per-PE code is byte-for-byte the same code SimExecutor
vmaps, integer plan state is **bit-identical** between the two executors
on identical κ-scheduled traces — that is the parity contract CI checks
(``tests/test_coop_shard.py``).  Floating-point loss/gradients agree to
reduction-order tolerance: the shard path sums per-PE partials and
``psum``s them, the sim path reduces one flat array.

Gradient sync is an *explicit* ``psum`` in :meth:`make_loss_and_grad`:
each PE differentiates its share of the global masked mean (its CE sum
over the psum'd valid count), then all-reduces the per-PE gradients.
The backward all-to-alls of Alg. 1 fall out of AD through
``all_to_all`` inside the body — no hand-written transposes.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.cooperative import (
    CoopMinibatch,
    ShardExecutor,
    build_cooperative_minibatch,
)
from repro.core.graph import INVALID
from repro.launch.mesh import make_coop_mesh

if TYPE_CHECKING:  # pragma: no cover - import cycle guard, typing only
    from repro.engine.engine import MinibatchEngine


@dataclass
class ShardRunner:
    """Cooperative engine bound to a device mesh; one PE per device."""

    engine: "MinibatchEngine"
    mesh: Mesh

    @classmethod
    def for_engine(
        cls, engine: "MinibatchEngine", mesh: Optional[Mesh] = None
    ) -> "ShardRunner":
        cfg = engine.config
        if cfg.mode != "cooperative":
            raise ValueError(
                "ShardRunner needs a cooperative engine; independent mode "
                "is plain data parallelism (no all-to-all) — shard it with "
                "launch.shardings instead"
            )
        if not isinstance(engine.ex, ShardExecutor):
            raise ValueError(
                "engine was built with executor="
                f"{cfg.executor!r}; construct it with executor='shard'"
            )
        if mesh is None:
            mesh = make_coop_mesh(cfg.num_pes, axis_name=cfg.axis_name)
        if mesh.shape[cfg.axis_name] != cfg.num_pes:
            raise ValueError(
                f"mesh axis {cfg.axis_name!r} has size "
                f"{mesh.shape[cfg.axis_name]}, engine expects {cfg.num_pes}"
            )
        return cls(engine=engine, mesh=mesh)

    @property
    def axis(self) -> str:
        return self.engine.config.axis_name

    # ------------------------------------------------------------------
    # Per-PE plan construction (runs inside shard_map)
    # ------------------------------------------------------------------
    def _build_local(self, seeds_row: jax.Array, rng) -> CoopMinibatch:
        eng, cfg = self.engine, self.engine.config
        return build_cooperative_minibatch(
            eng.graph, eng.sampler, eng.part, seeds_row.reshape(-1), rng,
            cfg.num_layers, eng.caps, eng.ex, backend=cfg.plan_backend,
        )

    @cached_property
    def _plan_at_compiled(self):
        eng, ax = self.engine, self.axis

        def body(seeds_p, rng):
            mb = self._build_local(seeds_p, rng)
            return jax.tree.map(lambda x: x[None], mb)

        f = jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(ax), P()), out_specs=P(ax),
            check_vma=False,
        )

        def build(step):
            return f(eng._seed_batch_traced(step), eng.rng_state(step))

        return jax.jit(build)

    def plan_at(self, step) -> CoopMinibatch:
        """Stacked ``(P, ...)`` cooperative plan for ``step``, built by P
        devices cooperatively (id all-to-alls on the wire).  Same seeds,
        same RNG schedule, same layout as the SimExecutor ``plan_at`` —
        integer leaves are bit-identical."""
        return self._plan_at_compiled(jnp.asarray(step, jnp.int32))

    # ------------------------------------------------------------------
    # Training-step pieces (loss + explicitly psum-synced gradients)
    # ------------------------------------------------------------------
    def make_loss_and_grad(self, gnn_cfg, features: jax.Array, labels):
        """Build ``(params, step) -> (loss, grads)`` under shard_map.

        Per device: build the local plan, gather *owned* input features,
        run the cooperative forward (all-to-all redistribution between
        layers), differentiate the local share of the global masked-mean
        CE, then ``psum`` loss shares and gradients — the data-parallel
        gradient sync, over the same mesh axis as the all-to-alls.
        Matches the SimExecutor loss semantics exactly (same masked mean
        over the same B = b·P seed rows).
        """
        from repro.models.gnn import gnn_apply_cooperative
        from repro.train.metrics import masked_softmax_xent_parts

        eng, ax = self.engine, self.axis
        ex = eng.ex
        V = eng.graph.num_vertices
        labels = jnp.asarray(labels)

        def local_share(params, seeds_p, rng):
            mb = self._build_local(seeds_p, rng)
            with jax.named_scope("fetch.inputs"):
                h = features[jnp.clip(mb.input_ids, 0, V - 1)]
                H = jnp.where((mb.input_ids != INVALID)[:, None], h, 0.0)
            logits = gnn_apply_cooperative(
                params, gnn_cfg, ex, mb.layers, H, eng.caps.tilde_caps
            )
            with jax.named_scope("gnn.loss"):
                y = labels[jnp.clip(mb.seed_ids, 0, V - 1)]
                valid = mb.seed_ids != INVALID
                s, n = masked_softmax_xent_parts(logits, y, valid)
                # this PE's share of the global masked mean: CE sum over
                # the *global* valid count; psum of shares == the mean
                with jax.named_scope("exchange.grads"):
                    n = jax.lax.psum(n, ax)
                return s / jnp.maximum(n, 1).astype(s.dtype)

        def body(params, seeds_p, rng):
            share, grads = jax.value_and_grad(local_share)(
                params, seeds_p, rng
            )
            with jax.named_scope("exchange.grads"):
                loss = jax.lax.psum(share, ax)   # global masked-mean CE
                grads = jax.lax.psum(grads, ax)  # explicit gradient sync
            return loss, grads

        # psum'd outputs are replicated over the axis, so they leave the
        # map unstacked (out_specs=P()).  check_vma stays off: with it on,
        # AD over the replicated params inserts its own all-reduce, which
        # the explicit sync above does not expect (the gradients came out
        # at 1/P of the sim oracle's in tests/test_coop_shard.py).
        f = jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(), P(ax), P()),
            out_specs=P(), check_vma=False,
        )

        def loss_and_grad(params, step):
            step = jnp.asarray(step, jnp.int32)
            return f(params, eng._seed_batch_traced(step), eng.rng_state(step))

        return loss_and_grad
