"""Dry-run of the paper's cooperative GNN training step on the mesh.

This is the production embodiment of Algorithm 1: every mesh device is a
PE; the graph is 1-D block-partitioned (each PE holds the in-CSR of its
vertex range plus its feature/label rows — owner-partitioned storage);
cooperative sampling, feature loading and forward/backward run inside
``shard_map`` with ``lax.all_to_all`` over the PE axis.  Multi-pod uses
an outer ``pod`` axis that data-parallelizes *independent global
batches* — cooperation stays inside a fast-ICI island per the paper's
own limitation analysis (§A.11, DESIGN.md §6).

Everything is ShapeDtypeStruct-lowered: papers100M-scale array shapes,
no allocation.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import frontier
from repro.core.cooperative import (
    CoopCapacityPlan,
    ShardExecutor,
    build_cooperative_minibatch,
    redistribute,
)
from repro.core.graph import INVALID
from repro.core.rng import DependentRNG
from repro.core.samplers import LaborSampler
from repro.train.optim import adam_init, adam_update


# --------------------------------------------------------------------------
# block-local graph + partition (owner-partitioned storage)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LocalGraph:
    """Per-PE CSR block: rows are the PE's owned vertices.

    ``indices`` store GLOBAL source ids; ``v_start`` is the first owned
    vertex id, so local row = global id - v_start.  ``edge_types`` (R-GCN,
    mag240M) aligns with ``indices``.
    """

    indptr: jax.Array    # (Vp + 1,)
    indices: jax.Array   # (Ep,)
    v_start: jax.Array   # () int32
    max_degree: int
    edge_types: jax.Array | None = None  # (Ep,) relation ids

    def _row_window(self, seeds: jax.Array):
        Vp = self.indptr.shape[0] - 1
        Ep = self.indices.shape[0]
        local = jnp.where(seeds == INVALID, 0, seeds - self.v_start)
        local = jnp.clip(local, 0, Vp - 1)
        offs = self.indptr[local]
        deg = self.indptr[local + 1] - offs
        pos = jnp.arange(self.max_degree, dtype=jnp.int32)[None, :]
        idx = jnp.clip(offs[:, None] + pos, 0, max(Ep - 1, 0))
        mask = (pos < deg[:, None]) & (seeds != INVALID)[:, None]
        return idx, mask

    def neighbor_table(self, seeds: jax.Array):
        idx, mask = self._row_window(seeds)
        nbr = self.indices[idx]
        return jnp.where(mask, nbr, INVALID), mask

    def neighbor_edge_types(self, seeds: jax.Array):
        idx, mask = self._row_window(seeds)
        return jnp.where(mask, self.edge_types[idx], 0)


@dataclasses.dataclass(frozen=True)
class BlockPartition:
    """Functional owner map for contiguous blocks (no (V,) array)."""

    verts_per_pe: int
    num_parts: int

    def owner_of(self, ids: jax.Array) -> jax.Array:
        own = ids // jnp.int32(self.verts_per_pe)
        own = jnp.clip(own, 0, self.num_parts - 1)
        return jnp.where(ids == INVALID, self.num_parts - 1, own)


# --------------------------------------------------------------------------
# problem scales (Table 2) and models (A.5): papers100M/GCN, mag240M/R-GCN
# --------------------------------------------------------------------------
SCALE = dict(
    log2_v=27,          # 134M vertices (papers100M: 111M)
    avg_degree=29,      # papers100M: 29.1
    max_degree=32,      # degree-capped neighbor tables (DESIGN.md §3)
    feat_dim=128,       # papers100M feature dim
    hidden=1024,        # paper A.5
    classes=172,
    fanout=10,
    layers=3,
    local_batch=1024,   # b per PE; global batch = 1024 * P
    model="gcn",
    num_relations=1,
)

# mag240M / R-GCN (paper §4.3): heavier model M — the regime where the
# paper reports cooperation pays off even at P=2 (α/c > γ/M, Table 1).
SCALE_MAG = dict(
    log2_v=28,          # 268M vertices (mag240M: 244M)
    avg_degree=14,      # mag240M: 14.2
    max_degree=32,
    feat_dim=768,       # mag240M feature dim (fp16-stored in the paper)
    hidden=1024,
    classes=153,
    fanout=10,
    layers=3,
    local_batch=1024,
    model="rgcn",
    num_relations=4,    # author/paper/institution/field edge types
)


def _caps(P: int, bucket_safety: float = 3.0, scale: dict = None) -> CoopCapacityPlan:
    """Concavity-informed per-PE frontier capacities.

    Sized from the paper's measured cooperative per-PE frontier sizes on
    papers100M with LABOR-0, b=1024, k=10 (Table 7: |S^1|=9.3k,
    |S^2|=62k, |S^3|=318k, |S~^2|=83k, |S~^3|=463k) with ~30% headroom —
    the concave growth (Thm 3.2) is exactly why these are far below the
    geometric bound b·(k+1)^l.
    """
    scale = scale or SCALE
    assert scale["local_batch"] == 1024 and scale["fanout"] == 10
    caps = (1024, 12288, 81920, 417792)
    tilde = (16384, 106496, 606208)
    buckets = tuple(
        max(64, int(t // P * bucket_safety) // 8 * 8 + 8) for t in tilde
    )
    return CoopCapacityPlan(caps, tilde, buckets)


def _gnn_params_specs(scale: dict, dtype=jnp.float32):
    # plan layer l computes H^l from H^{l+1}: layer L-1 consumes raw
    # features, layer 0 emits class logits (models/gnn convention)
    L = scale["layers"]
    out = []
    for l in range(L):
        d_in = scale["feat_dim"] if l == L - 1 else scale["hidden"]
        d_out = scale["classes"] if l == 0 else scale["hidden"]
        lp = {
            "w": jax.ShapeDtypeStruct((d_in, d_out), dtype),
            "b": jax.ShapeDtypeStruct((d_out,), dtype),
        }
        if scale["model"] == "rgcn":
            lp["w_rel"] = jax.ShapeDtypeStruct(
                (scale["num_relations"], d_in, d_out), dtype
            )
        out.append(lp)
    return out


def _gcn_layer(p, Ht, self_idx, nbr_idx, mask, etypes, last: bool):
    h_self = Ht[jnp.clip(self_idx, 0)]
    h_nbr = Ht[jnp.clip(nbr_idx, 0)]
    valid = (nbr_idx >= 0) & mask
    h_nbr = jnp.where(valid[..., None], h_nbr, 0.0)
    deg = jnp.sum(valid, axis=-1, keepdims=True) + 1
    agg = (jnp.sum(h_nbr, axis=-2) + h_self) / deg
    out = agg @ p["w"] + p["b"]
    return out if last else jax.nn.relu(out)


def _rgcn_layer(p, Ht, self_idx, nbr_idx, mask, etypes, last: bool):
    """R-GCN (Schlichtkrull et al.): per-relation mean aggregation."""
    h_self = Ht[jnp.clip(self_idx, 0)]
    h_nbr = Ht[jnp.clip(nbr_idx, 0)]
    valid = (nbr_idx >= 0) & mask
    out = h_self @ p["w"] + p["b"]
    R = p["w_rel"].shape[0]
    et = etypes if etypes is not None else jnp.zeros(mask.shape, jnp.int32)
    for r in range(R):
        m_r = valid & (et == r)
        s = jnp.sum(jnp.where(m_r[..., None], h_nbr, 0.0), axis=-2)
        n = jnp.maximum(jnp.sum(m_r, axis=-1, keepdims=True), 1)
        out = out + (s / n) @ p["w_rel"][r]
    return out if last else jax.nn.relu(out)


def make_coop_train_step(P: int, pe_axes, caps: CoopCapacityPlan, grad_axes=None,
                         scale: dict = None):
    """Cooperative GNN train step body (runs per-PE inside shard_map)."""
    scale = scale or SCALE
    sampler = LaborSampler(fanout=scale["fanout"])
    part = BlockPartition((1 << scale["log2_v"]) // P, P)
    ex = ShardExecutor(P, axis_name=pe_axes)
    L = scale["layers"]
    grad_axes = grad_axes or pe_axes
    layer_fn = _rgcn_layer if scale["model"] == "rgcn" else _gcn_layer

    def step(params, opt, indptr, indices, v_start, feats, labels, seeds,
             rng_step, etypes=None):
        graph = LocalGraph(indptr, indices, v_start, scale["max_degree"],
                           edge_types=etypes)
        rng = DependentRNG(base_seed=0, kappa=64).state_at(rng_step)
        mb = build_cooperative_minibatch(
            graph, sampler, part, seeds, rng, L, caps, ex
        )

        def loss_fn(params):
            ids = mb.input_ids
            local = jnp.clip(
                jnp.where(ids == INVALID, 0, ids - v_start), 0, feats.shape[0] - 1
            )
            H = jnp.where(
                (ids != INVALID)[:, None], feats[local], 0.0
            )
            for l in reversed(range(L)):
                blk = mb.layers[l]
                Ht = redistribute(ex, blk, H, caps.tilde_caps[l])
                H = layer_fn(
                    params[l], Ht, blk.self_idx, blk.nbr_idx, blk.mask,
                    blk.etypes, last=(l == 0),
                )
            seed_ids = mb.seed_ids
            lab_local = jnp.clip(
                jnp.where(seed_ids == INVALID, 0, seed_ids - v_start),
                0,
                labels.shape[0] - 1,
            )
            y = labels[lab_local]
            valid = seed_ids != INVALID
            logits = H.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            n = jnp.maximum(jnp.sum(valid), 1)
            loss = jnp.sum(jnp.where(valid, logz - ll, 0.0)) / n
            return jax.lax.pmean(loss, pe_axes)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.lax.pmean(grads, grad_axes)
        params, opt = adam_update(params, grads, opt, lr=1e-3)
        return params, opt, loss

    return step


def lower_gnn_coop_step(
    multi_pod: bool = False,
    verbose: bool = True,
    feat_dtype: str = "float32",
    bucket_safety: float = 3.0,
    model: str = "gcn",
    tag: str = "",
) -> dict:
    from repro.launch import roofline as rl
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND

    scale = SCALE_MAG if model == "rgcn" else SCALE
    NPE = 256
    pods = 2 if multi_pod else 1
    mesh = jax.make_mesh((pods, NPE), ("pod", "pe"))
    V = 1 << scale["log2_v"]
    vp = V // NPE
    ep = vp * scale["avg_degree"]
    caps = _caps(NPE, bucket_safety=bucket_safety, scale=scale)
    fdt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[feat_dtype]
    grad_axes = ("pe", "pod") if multi_pod else ("pe",)
    step = make_coop_train_step(NPE, "pe", caps, grad_axes=grad_axes, scale=scale)
    rgcn = scale["model"] == "rgcn"

    params_s = _gnn_params_specs(scale)
    opt_s = jax.eval_shape(lambda p: adam_init(p), params_s)
    specs = dict(
        indptr=jax.ShapeDtypeStruct((pods, NPE * (vp + 1)), jnp.int32),
        indices=jax.ShapeDtypeStruct((pods, NPE * ep), jnp.int32),
        v_start=jax.ShapeDtypeStruct((pods, NPE), jnp.int32),
        feats=jax.ShapeDtypeStruct((pods, V, scale["feat_dim"]), fdt),
        labels=jax.ShapeDtypeStruct((pods, V), jnp.int32),
        seeds=jax.ShapeDtypeStruct((pods, NPE, scale["local_batch"]), jnp.int32),
        etypes=jax.ShapeDtypeStruct((pods, NPE * ep), jnp.int32),
    )

    def sharded_step(params, opt, indptr, indices, v_start, feats, labels,
                     seeds, etypes):
        def per_pe(params, opt, indptr, indices, v_start, feats, labels,
                   seeds, etypes):
            return step(
                params,
                opt,
                indptr.reshape(-1)[: vp + 1],
                indices.reshape(-1),
                v_start.reshape(-1)[0],
                feats.reshape(-1, scale["feat_dim"]),
                labels.reshape(-1),
                seeds.reshape(-1),
                jnp.int32(0),
                etypes.reshape(-1) if rgcn else None,
            )

        return jax.shard_map(
            per_pe,
            mesh=mesh,
            in_specs=(
                P(),                    # params replicated
                P(),                    # opt replicated
                P("pod", "pe"),
                P("pod", "pe"),
                P("pod", "pe"),
                P("pod", ("pe",)),      # feats: rows owner-partitioned
                P("pod", ("pe",)),
                P("pod", "pe", None),
                P("pod", "pe"),         # etypes (aligned with indices)
            ),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(params, opt, indptr, indices, v_start, feats, labels, seeds, etypes)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(sharded_step).lower(
            params_s,
            opt_s,
            specs["indptr"],
            specs["indices"],
            specs["v_start"],
            specs["feats"],
            specs["labels"],
            specs["seeds"],
            specs["etypes"],
        )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    model_flops = 0.0  # GNN: flops are data-dependent; report HLO terms only
    roof = rl.analyze(compiled, mesh.size, model_flops, PRODUCTION_DEVICE_KIND)
    result = {
        "arch": "gnn-coop-mag240M-rgcn" if rgcn else "gnn-coop-papers100M-gcn",
        "shape": f"b{scale['local_batch']}xP{NPE}",
        "mesh": "pod2x256" if multi_pod else "pod1x256",
        "tag": tag,
        "overrides": {"feat_dtype": feat_dtype, "bucket_safety": bucket_safety,
                      "model": scale["model"]},
        "status": "ok",
        "devices": mesh.size,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "peak_per_device_gb": roof.peak_mem_bytes / 2**30,
        },
        "roofline": roof.to_dict(),
    }
    if verbose:
        print(
            f"[{result['arch']} | {result['shape']} | {result['mesh']}] ok "
            f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
            f"peak/dev {result['memory']['peak_per_device_gb']:.2f} GiB "
            f"bottleneck={roof.bottleneck} "
            f"(c={roof.compute_s*1e3:.2f}ms m={roof.memory_s*1e3:.2f}ms "
            f"coll={roof.collective_s*1e3:.2f}ms)",
            flush=True,
        )
    return result
