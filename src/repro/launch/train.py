"""Training launcher.

GNN (the paper's system):
    PYTHONPATH=src python -m repro.launch.train gnn \
        --mode cooperative --pes 4 --steps 100 --kappa 16

LM pool (reduced configs on CPU; full configs are dry-run-only):
    PYTHONPATH=src python -m repro.launch.train lm --arch granite-3-8b \
        --steps 5 --reduced
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def run_gnn(args) -> None:
    from repro.data import rmat_graph
    from repro.data.synthetic import SyntheticGraphDataset
    from repro.models.gnn import GNNConfig
    from repro.train.loop import TrainConfig, evaluate, train_gnn

    graph = rmat_graph(scale=args.scale, edge_factor=8, max_degree=32, seed=0)
    ds = SyntheticGraphDataset(graph, feature_dim=64, num_classes=16, seed=0)
    cfg = GNNConfig(model=args.model, num_layers=args.layers, in_dim=64,
                    hidden_dim=args.hidden, num_classes=16,
                    num_relations=graph.num_edge_types)
    tc = TrainConfig(mode=args.mode, num_pes=args.pes, local_batch=args.batch,
                     num_steps=args.steps, fanout=args.fanout,
                     kappa=args.kappa, sampler=args.sampler,
                     partition=args.partition,
                     eval_every=max(args.steps // 5, 1))
    r = train_gnn(ds, cfg, tc)
    # step_s[0] holds the step's compile; the median is the steady step
    print(f"[{args.mode}] {args.steps} steps, step median "
          f"{1e3 * np.median(r.step_s):.1f} ms, max {1e3 * max(r.step_s):.1f} ms, "
          f"traced {r.step_traces}x  "
          f"loss {r.losses[0]:.3f}->{np.mean(r.losses[-5:]):.3f}  "
          f"val_f1={r.val_f1}")
    print(f"test_f1={evaluate(ds, cfg, r.params, tc, split='test'):.3f}")


def run_lm(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.tokens import synthetic_token_batch
    from repro.launch.steps import make_train_step
    from repro.models.transformer import init_lm
    from repro.train.optim import adam_init

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    opt = adam_init(params)
    step = jax.jit(make_train_step(cfg, lr=1e-3))
    B, S = args.batch, args.seq
    s_text = S - cfg.num_prefix_tokens
    toks = synthetic_token_batch(B, s_text + 1, cfg.vocab_size, seed=0)
    batch = {
        "tokens": jnp.asarray(toks[:, :-1]),
        "labels": jnp.asarray(toks[:, 1:]),
    }
    if cfg.num_prefix_tokens:
        batch["prefix_embeds"] = jnp.zeros((B, cfg.num_prefix_tokens, cfg.d_model), cfg.jdtype)
    if cfg.enc_dec:
        batch["enc_out"] = jnp.zeros((B, cfg.enc_len, cfg.d_model), cfg.jdtype)
    t0 = time.time()
    for i in range(args.steps):
        params, opt, metrics = step(params, opt, batch)
        print(f"step {i}: loss={float(metrics['loss']):.4f}", flush=True)
    print(f"{args.steps} steps in {time.time()-t0:.1f}s")


def main() -> None:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("gnn")
    g.add_argument("--mode", default="cooperative",
                   choices=["cooperative", "independent"])
    g.add_argument("--model", default="gcn",
                   choices=["gcn", "sage", "gat", "rgcn"])
    g.add_argument("--pes", type=int, default=4)
    g.add_argument("--batch", type=int, default=64)
    g.add_argument("--steps", type=int, default=50)
    g.add_argument("--layers", type=int, default=3)
    g.add_argument("--hidden", type=int, default=128)
    g.add_argument("--fanout", type=int, default=10)
    g.add_argument("--kappa", type=int, default=1)
    g.add_argument("--sampler", default="labor0")
    g.add_argument("--partition", default="hash")
    g.add_argument("--scale", type=int, default=12)

    l = sub.add_parser("lm")
    l.add_argument("--arch", required=True)
    l.add_argument("--reduced", action="store_true")
    l.add_argument("--steps", type=int, default=3)
    l.add_argument("--batch", type=int, default=2)
    l.add_argument("--seq", type=int, default=64)

    args = ap.parse_args()
    if args.cmd == "gnn":
        run_gnn(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
