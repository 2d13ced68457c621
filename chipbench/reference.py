"""Plain reference of the first training steps of a cell.

It follows the published description of the training step, written
from scratch and importing nothing of the program:

* seed draw: the train ids are permuted by a keyed uint32 hash per step
  (per owner PE in the cooperative mode) and the first ``b`` are taken;
* sampling: LABOR-0 (Balin & Catalyurek 2023) with one uniform
  ``r_t`` per source vertex and layer, an edge ``t -> s`` kept iff
  ``r_t <= min(1, fanout / deg(s))``; frontiers are exact vertex sets;
* layers: each model's layer, activation included, and its parameters
  are in its own module, ``chipbench/models/<model>.py`` (``byname.py``
  says what one holds); this file keeps what every model shares;
* loss: mean softmax cross entropy over the seeds;
* update: Adam (lr 1e-3, betas 0.9 / 0.999, eps 1e-8).

Plans are built on the host with numpy set operations; the forward and
backward passes run on the device as segment sums over the sampled
edges, in float32 with matmuls at ``Precision.HIGHEST``.  The uniform
variates are computed on the device with the same float32 formula the
sampler's definition gives (hash -> uniform -> Phi^-1 -> Phi), so the
sampled edges are the ones the definition prescribes for this seed.

``variant`` selects what stands in the program's place for the
limits: ``"f32"`` (the reference), ``"bf16"`` (the same steps computed
in bfloat16, read beside the control, which is the program's own
bfloat16 path) and ``"half"`` (the loss averaged over half the seeds, a
planted fault).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.stats import norm

import byname

INVALID = np.int32(np.iinfo(np.int32).max)
GOLDEN = np.uint32(0x9E3779B9)
SALT_MUL = np.uint32(0x85EBCA6B)
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# Keyed hashes (uint32 arithmetic, wrapping)
# --------------------------------------------------------------------------
def mix_np(x):
    with np.errstate(over="ignore"):
        x = np.asarray(x).astype(np.uint32)
        x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
        x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
        return x ^ (x >> np.uint32(16))


def hash_np(ids, seed, salt):
    with np.errstate(over="ignore"):
        h = mix_np(np.asarray(ids).astype(np.uint32) ^ (np.uint32(seed) * GOLDEN))
        return mix_np(h ^ (np.asarray(salt).astype(np.uint32) * SALT_MUL))


def _mix_jnp(x):
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _hash_jnp(ids, seed, salt):
    h = _mix_jnp(ids.astype(jnp.uint32) ^ (seed * jnp.uint32(GOLDEN)))
    return _mix_jnp(h ^ (jnp.uint32(salt) * jnp.uint32(SALT_MUL)))


@partial(jax.jit, static_argnums=(0, 3))
def _vertex_uniform(V, z1, c, salt):
    """r_t for every vertex t under the smoothed schedule state (z1, c)."""
    ids = jnp.arange(V, dtype=jnp.int32)

    def normal(z):
        h = _hash_jnp(ids, z, salt)
        u = (h.astype(jnp.float32) + 0.5) * jnp.float32(1.0 / 4294967296.0)
        return norm.ppf(u)

    n = (jnp.cos(c * jnp.pi / 2) * normal(z1)
         + jnp.sin(c * jnp.pi / 2) * normal(z1 + jnp.uint32(1)))
    return norm.cdf(n)


@partial(jax.jit, static_argnums=(0, 1))
def _thresholds(fanout, max_degree):
    deg = jnp.arange(max_degree + 1).astype(jnp.float32)
    return jnp.minimum(1.0, fanout / jnp.maximum(deg, 1.0))


# --------------------------------------------------------------------------
# Host plan
# --------------------------------------------------------------------------
@dataclass
class HostGraph:
    indptr: np.ndarray
    indices: np.ndarray
    etypes: np.ndarray | None
    num_vertices: int
    max_degree: int


@dataclass
class Layer:
    """Edges of plan layer l: dst rows S_l, src rows S_{l+1}."""

    n_dst: int
    self_idx: np.ndarray   # (n_dst,) row of each dst in S_{l+1}
    dst: np.ndarray        # (e,) dst row
    src: np.ndarray        # (e,) src row in S_{l+1}
    etype: np.ndarray      # (e,)


def seed_rows(train_ids, seed, step, mode, num_pes, b):
    """Seed ids of ``step``: a keyed-hash permutation of the train ids
    (one row; or one row of owned ids per PE in the cooperative mode)."""
    pool = np.asarray(train_ids, np.int32)
    if mode == "cooperative":
        v = pool.astype(np.uint64)
        owner = ((v * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(33)) % np.uint64(num_pes)
        rows = [pool[owner == p] for p in range(num_pes)]
        need = b
    else:
        rows, need = [pool], num_pes * b
    width = max(need, max(len(r) for r in rows))
    base = np.uint32(seed & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        z = mix_np(np.uint32(step) ^ (base * GOLDEN))
    out = []
    for p, r in enumerate(rows):
        row = np.full(width, INVALID, np.int32)
        row[: len(r)] = r
        key = hash_np(row, z, p)
        key = np.where(row != INVALID, np.minimum(key, np.uint32(0xFFFFFFFE)),
                       np.uint32(0xFFFFFFFF))
        take = row[np.argsort(key, kind="stable")][: need if len(rows) == 1 else b]
        out.append(take[take != INVALID])
    return np.concatenate(out)


def build_plan(g: HostGraph, seeds, seed, step, fanout, num_layers):
    """Exact frontiers S_0..S_L and the sampled edges of every layer."""
    base = np.uint32(seed & 0xFFFFFFFF)
    z1 = jnp.uint32(np.uint32(base + np.uint32(step)))  # kappa = 1: window = step
    thr = np.asarray(_thresholds(fanout, g.max_degree))
    S = np.unique(seeds)
    frontiers, layers = [S], []
    for l in range(num_layers):
        r = np.asarray(_vertex_uniform(g.num_vertices, z1, jnp.float32(0.0), l))
        start = g.indptr[S].astype(np.int64)
        deg = (g.indptr[S + 1] - g.indptr[S]).astype(np.int64)
        dst = np.repeat(np.arange(len(S)), deg)
        pos = np.repeat(start - np.cumsum(deg) + deg, deg) + np.arange(deg.sum())
        t = g.indices[pos]
        keep = r[t] <= thr[deg[dst]]
        dst, t, pos = dst[keep], t[keep], pos[keep]
        et = g.etypes[pos] if g.etypes is not None else np.zeros(len(t), np.int32)
        S_next = np.unique(np.concatenate([S, t]))
        layers.append(Layer(
            n_dst=len(S), self_idx=np.searchsorted(S_next, S),
            dst=dst, src=np.searchsorted(S_next, t), etype=et,
        ))
        S = S_next
        frontiers.append(S)
    return frontiers, layers


# --------------------------------------------------------------------------
# Device forward / backward over padded plans (padding only fixes shapes)
# --------------------------------------------------------------------------
def _bucket(n: int) -> int:
    """Round up to 1/4 of a power of two, so shapes repeat across steps."""
    n = max(int(n), 8)
    q = 1 << max(int(np.ceil(np.log2(n))) - 2, 0)
    return -(-n // q) * q


def _pad(x, n, fill=0):
    out = np.full((n,), fill, x.dtype)
    out[: len(x)] = x
    return out


def pad_plan(frontiers, layers, labels):
    """Arrays of the device step: input ids, per-layer edge lists padded
    with weight-0 edges, seed labels and validity."""
    ids = frontiers[-1]
    n_in = _bucket(len(ids))
    arrs = {"input_ids": _pad(ids.astype(np.int32), n_in),
            "input_ok": _pad(np.ones(len(ids), np.float32), n_in)}
    layer_arrs = []
    for L in layers:
        nd, ne = _bucket(L.n_dst), _bucket(len(L.dst))
        layer_arrs.append(dict(
            self_idx=_pad(L.self_idx.astype(np.int32), nd),
            dst=_pad(L.dst.astype(np.int32), ne, nd - 1),
            src=_pad(L.src.astype(np.int32), ne),
            etype=_pad(L.etype.astype(np.int32), ne),
            w=_pad(np.ones(len(L.dst), np.float32), ne),
        ))
    n0 = len(frontiers[0])
    nd0 = _bucket(n0)
    arrs["labels"] = _pad(np.asarray(labels)[frontiers[0]].astype(np.int32), nd0)
    arrs["seed_ok"] = _pad(np.ones(n0, np.float32), nd0)
    arrs["layers"] = layer_arrs
    return arrs


@partial(jax.jit, static_argnums=(3, 4))
def loss_and_grad(params, features, arrs, config, variant):
    """Loss and gradients of one step over the padded plan ``arrs``;
    ``config`` is the configuration as JSON text (static, so it hashes)."""
    cfg = json.loads(config)
    model = byname.model(cfg["model"])
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    prec = jax.lax.Precision.DEFAULT if variant == "bf16" else HIGHEST

    def loss_fn(params):
        h = features[arrs["input_ids"]].astype(dtype) * arrs["input_ok"][:, None].astype(dtype)
        for l in reversed(range(cfg["num_layers"])):
            h = model.layer(params["layers"][l], h, arrs["layers"][l], l == 0,
                            dtype, prec, cfg)
        logits = h.astype(jnp.float32)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, arrs["labels"][:, None], -1)[:, 0]
        ok = arrs["seed_ok"]
        if variant == "half":
            n = jnp.sum(ok)
            ok = ok * (jnp.cumsum(ok) <= n // 2)
        return jnp.sum(ce * ok) / jnp.maximum(jnp.sum(ok), 1)

    return jax.value_and_grad(loss_fn)(params)


# --------------------------------------------------------------------------
# Parameters and Adam
# --------------------------------------------------------------------------
def glorot(k, shape):
    lim = float(np.sqrt(6.0 / (shape[-2] + shape[-1])))
    return jax.random.uniform(k, shape, jnp.float32, -lim, lim)


def init_params(seed: int, cfg: dict) -> dict:
    """The model's layers (``init_layer``), each from five subkeys of
    ``PRNGKey(seed)``, layer 0 (the output layer) first: Glorot-uniform
    weights and zero biases."""
    model = byname.model(cfg["model"])
    L = cfg["num_layers"]
    key = jax.random.PRNGKey(seed)
    layers = []
    for l in range(L):
        d_in = cfg["feature_dim"] if l == L - 1 else cfg["hidden_dim"]
        d_out = cfg["num_classes"] if l == 0 else cfg["hidden_dim"]
        key, *ks = jax.random.split(key, 6)
        layers.append(model.init_layer(ks, d_in, d_out, cfg))
    return {"layers": layers}


@jax.jit
def adam(params, grads, mu, nu, t):
    b1, b2, lr, eps = 0.9, 0.999, 1e-3, 1e-8
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32), mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                      nu, grads)
    tf = t.astype(jnp.float32)
    s1, s2 = 1.0 / (1 - b1 ** tf), 1.0 / (1 - b2 ** tf)
    new = jax.tree.map(
        lambda p, m, v: (p.astype(jnp.float32) - lr * (m * s1) / (jnp.sqrt(v * s2) + eps)
                         ).astype(p.dtype), params, mu, nu)
    return new, mu, nu


# --------------------------------------------------------------------------
# The steps
# --------------------------------------------------------------------------
def run(g: HostGraph, features, labels, train_ids, *, seed, cfg, mode,
        num_pes, steps=3, variant="f32"):
    """Losses of the first ``steps`` steps of the configuration ``cfg``,
    the first gradient, the parameters before step 0 and after ``steps``
    updates (host numpy)."""
    params = init_params(seed, cfg)
    p0 = jax.tree.map(np.asarray, params)
    if variant == "bf16":
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    config = json.dumps(cfg, sort_keys=True)
    mu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    nu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    losses, g1 = [], None
    for step in range(steps):
        seeds = seed_rows(train_ids, seed, step, mode, num_pes, cfg["local_batch"])
        frontiers, layers = build_plan(g, seeds, seed, step, cfg["fanout"],
                                       cfg["num_layers"])
        arrs = pad_plan(frontiers, layers, labels)
        loss, grads = loss_and_grad(params, features, arrs, config, variant)
        losses.append(float(loss))
        if g1 is None:
            g1 = jax.tree.map(lambda x: np.asarray(x, np.float32), grads)
        params, mu, nu = adam(params, grads, mu, nu, jnp.int32(step + 1))
    p_end = jax.tree.map(lambda x: np.asarray(x, np.float32), params)
    return {"losses": losses, "g1": g1, "p0": p0, "p_end": p_end}
