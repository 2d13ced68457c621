"""Multi-device shard_map path: ShardExecutor == SimExecutor.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test session keeps its single device (per the launch brief).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.cooperative import (
        CoopCapacityPlan, SimExecutor, ShardExecutor,
        build_cooperative_minibatch, redistribute)
    from repro.core.partition import hash_partition
    from repro.core.rng import DependentRNG
    from repro.core.samplers import make_sampler
    from repro.data import rmat_graph

    PE, B, L = 8, 32, 2
    g = rmat_graph(scale=10, edge_factor=8, max_degree=32, seed=0)
    part = hash_partition(g.num_vertices, PE)
    owner = np.asarray(part.owner)
    rng_np = np.random.default_rng(0)
    IM = np.iinfo(np.int32).max
    seeds = np.full((PE, B), IM, np.int32)
    for p in range(PE):
        own = np.nonzero(owner == p)[0]
        seeds[p] = rng_np.choice(own, size=B, replace=False)
    seeds = jnp.asarray(seeds)
    caps = CoopCapacityPlan.geometric(B, L, 5, g.num_vertices, PE)
    sampler = make_sampler("labor0", fanout=5)
    rng = DependentRNG(3, 1, 0)
    feat = jnp.asarray(np.random.default_rng(1)
                       .standard_normal((g.num_vertices, 8)).astype(np.float32))

    # --- SimExecutor (oracle) ---
    ex_sim = SimExecutor(PE)
    mb_sim = build_cooperative_minibatch(g, sampler, part, seeds, rng, L, caps, ex_sim)
    H_sim = jax.vmap(lambda ids: jnp.where(
        (ids != IM)[:, None], feat[jnp.clip(ids, 0, g.num_vertices - 1)], 0.0
    ))(mb_sim.input_ids)
    Ht_sim = redistribute(ex_sim, mb_sim.layers[L - 1], H_sim, caps.tilde_caps[L - 1])

    # --- ShardExecutor over a real 8-device mesh ---
    mesh = jax.make_mesh((PE,), ("data",))
    ex_sh = ShardExecutor(PE, axis_name="data")

    def per_pe(seeds_p):
        mb = build_cooperative_minibatch(g, sampler, part,
                                         seeds_p.reshape(-1), rng, L, caps, ex_sh)
        H = jnp.where((mb.input_ids != IM)[:, None],
                      feat[jnp.clip(mb.input_ids, 0, g.num_vertices - 1)], 0.0)
        Ht = redistribute(ex_sh, mb.layers[L - 1], H, caps.tilde_caps[L - 1])
        return Ht[None], mb.layers[L - 1].tilde_ids[None]

    with mesh:
        f = jax.shard_map(per_pe, mesh=mesh, in_specs=(P("data", None),),
                          out_specs=(P("data", None, None), P("data", None)),
                          check_vma=False)
        Ht_sh, tid_sh = jax.jit(f)(seeds)

    # same tilde ids and same redistributed embeddings per PE
    np.testing.assert_array_equal(
        np.asarray(tid_sh), np.asarray(mb_sim.layers[L - 1].tilde_ids))
    np.testing.assert_allclose(
        np.asarray(Ht_sh), np.asarray(Ht_sim), atol=1e-6)
    print("SHARD_MAP_MATCHES_SIM")
    """
)


@pytest.mark.slow
def test_shard_executor_matches_sim_executor():
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
    assert "SHARD_MAP_MATCHES_SIM" in out.stdout, out.stderr[-3000:]
