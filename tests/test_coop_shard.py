"""Multi-device cooperative execution: ShardRunner vs SimExecutor parity.

The parity contract (docs/cooperative_execution.md): on identical
κ-scheduled traces, the shard_map path must produce **bit-identical**
integer plan state (seeds, indices, masks, bucket slots) and
reduction-order-equal floats (loss/gradients within float32 tolerance of
the single-device reduction).

Everything device-related runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the main test
session keeps its single device (per the launch brief); one subprocess
covers plan parity, loss/grad parity, a train step, and the all-to-all
conservation invariants to amortize startup.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np

    from repro.core.graph import INVALID
    from repro.data import rmat_graph
    from repro.data.synthetic import SyntheticGraphDataset
    from repro.engine import EngineConfig, MinibatchEngine
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.train.loop import TrainConfig, make_loss_fn, train_gnn
    from repro.train.optim import adam_init, adam_update

    P, B, L = 4, 16, 2
    g = rmat_graph(scale=10, edge_factor=8, max_degree=32, seed=0)
    ds = SyntheticGraphDataset(g, feature_dim=16, num_classes=8, seed=0)
    gnn_cfg = GNNConfig(model="gcn", num_layers=L, in_dim=16, hidden_dim=32,
                        num_classes=8)
    params = init_gnn(jax.random.PRNGKey(0), gnn_cfg)

    def engines(schedule, kappa, partition):
        cfg = EngineConfig(
            mode="cooperative", num_pes=P, local_batch=B, num_layers=L,
            sampler="labor0", fanout=5, schedule=schedule, kappa=kappa,
            partition=partition, seed=7,
        )
        sim = MinibatchEngine.from_config(g, cfg, dataset=ds)
        sh = MinibatchEngine.from_config(
            g, dataclasses.replace(cfg, executor="shard"), dataset=ds)
        return sim, sh

    # ---- 1. plan bit-parity across kappa schedules -----------------------
    for schedule, kappa, partition in [
        ("smoothed", 3, "hash"), ("nested", 2, "degree"),
    ]:
        sim, sh = engines(schedule, kappa, partition)
        for step in range(3):
            leaves_sim = jax.tree.leaves(sim.plan_at(step))
            leaves_sh = jax.tree.leaves(sh.plan_at(step))
            assert len(leaves_sim) == len(leaves_sh)
            for a, b in zip(leaves_sim, leaves_sh):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("PLAN_PARITY_OK")

    # ---- 2. loss + psum-synced grads match the vmap oracle ---------------
    sim, sh = engines("smoothed", 3, "degree")
    # jitted as train_gnn runs them: one compile each instead of an
    # eager dispatch (and shard_map retrace) on every call
    lg_sim = jax.jit(jax.value_and_grad(
        make_loss_fn(sim, gnn_cfg, sim.store, ds.labels)))
    lg_sh = jax.jit(sh.shard_runner.make_loss_and_grad(
        gnn_cfg, sh.store.features, ds.labels))
    for step in range(4):
        l1, g1 = lg_sim(params, jnp.int32(step))
        l2, g2 = lg_sh(params, jnp.int32(step))
        np.testing.assert_allclose(float(l1), float(l2), rtol=5e-6)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-6, rtol=1e-4)
    print("LOSS_GRAD_PARITY_OK")

    # ---- 3. one adam step stays in lockstep ------------------------------
    def one_step(lg):
        opt = adam_init(params)
        loss, grads = lg(params, jnp.int32(0))
        new_params, _ = adam_update(params, grads, opt, lr=1e-3)
        return new_params
    for a, b in zip(jax.tree.leaves(one_step(lg_sim)),
                    jax.tree.leaves(one_step(lg_sh))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    print("TRAIN_STEP_PARITY_OK")

    # ---- 4. all-to-all conservation under shard_map ----------------------
    # Stacked layout: slot_to_tilde[p, q, s] >= 0 means PE p requested a
    # q-owned vertex at bucket slot s; req_idx[q, p, s] >= 0 means owner q
    # resolved that same slot after the wire exchange.  Conservation:
    # rows sent == rows received == rows resolved, elementwise.
    plan = sh.plan_at(0)
    owner = np.asarray(sh.part.owner)
    for l, layer in enumerate(plan.layers):
        sent = np.asarray(layer.slot_to_tilde) >= 0      # (P, Q, cap_b)
        resolved = np.asarray(layer.req_idx) >= 0        # (Q, P, cap_b)
        np.testing.assert_array_equal(sent, resolved.swapaxes(0, 1))
        # every id in PE p's bucket q really is owned by q (keyed by
        # ownership), and resolves to that id's row in q's next frontier
        tilde = np.asarray(layer.tilde_ids)              # (P, cap_t)
        s2t = np.asarray(layer.slot_to_tilde)
        for p in range(P):
            for q in range(P):
                ids = tilde[p][s2t[p, q][sent[p, q]]]
                assert (owner[ids] == q).all(), (l, p, q)
    # rows gathered: redistributing all-ones embeddings must deliver one
    # nonzero row per filled tilde slot, none elsewhere
    from repro.core.cooperative import SimExecutor, redistribute
    sim_plan = sim.plan_at(0)
    ones = jnp.ones(np.asarray(sim_plan.input_ids).shape + (4,), jnp.float32)
    Ht = redistribute(SimExecutor(P), sim_plan.layers[L - 1], ones,
                      sim.caps.tilde_caps[L - 1])
    got = np.asarray(jnp.any(Ht != 0, axis=-1))
    want = np.zeros_like(got)
    s2t = np.asarray(sim_plan.layers[L - 1].slot_to_tilde)
    for p in range(P):
        want[p][s2t[p][s2t[p] >= 0]] = True
    np.testing.assert_array_equal(got, want)
    print("A2A_CONSERVATION_OK")

    # ---- 5. train_gnn end to end: executor is a config flag --------------
    losses = {}
    for ex in ("sim", "shard"):
        tc = TrainConfig(mode="cooperative", num_pes=P, local_batch=B,
                         num_steps=4, schedule="smoothed", kappa=3,
                         partition="degree", executor=ex, eval_every=0)
        losses[ex] = train_gnn(ds, gnn_cfg, tc).losses
    np.testing.assert_allclose(losses["sim"], losses["shard"], rtol=1e-5)
    print("TRAIN_GNN_PARITY_OK")
    """
)

_MARKERS = [
    "PLAN_PARITY_OK",
    "LOSS_GRAD_PARITY_OK",
    "TRAIN_STEP_PARITY_OK",
    "A2A_CONSERVATION_OK",
    "TRAIN_GNN_PARITY_OK",
]


@pytest.mark.slow
def test_shard_runner_parity_and_conservation():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        timeout=560,
    )
    for marker in _MARKERS:
        assert marker in out.stdout, (marker, out.stdout, out.stderr[-3000:])


def test_shard_runner_needs_enough_devices(small_graph):
    """Single-device session: the mesh constructor must explain the fix."""
    from repro.engine import EngineConfig, MinibatchEngine

    eng = MinibatchEngine.from_config(
        small_graph,
        EngineConfig(mode="cooperative", num_pes=4, local_batch=8,
                     num_layers=2, executor="shard"),
    )
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        eng.shard_runner

    with pytest.raises(ValueError, match="plan_at"):
        eng.build_plan(eng.seed_batch(0))


def test_shard_runner_rejects_independent(small_graph):
    from repro.engine import EngineConfig, MinibatchEngine
    from repro.engine.shard import ShardRunner

    eng = MinibatchEngine.from_config(
        small_graph,
        EngineConfig(mode="independent", num_pes=1, local_batch=8,
                     num_layers=2),
    )
    with pytest.raises(ValueError, match="cooperative"):
        ShardRunner.for_engine(eng)
