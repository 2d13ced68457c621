"""Serve live GNN ego-network traffic with cooperative coalescing.

    PYTHONPATH=src python examples/serve_gnn.py [--smoke]

A synthetic user-item recommendation graph (power-law degrees on both
sides) takes a Poisson stream of user ego-network queries; the server
coalesces concurrent requests into ONE shared minibatch plan per
dispatch (the paper's concavity argument applied to inference), gathers
features through the warm device cache, and scatters per-request
predictions back out with latency accounting.  Prints the policy
comparison against the independent per-request baseline.
"""
import argparse

import jax

from repro.data.recsys import make_recsys
from repro.models.gnn import GNNConfig, init_gnn
from repro.serve import GNNServer, ServeConfig, poisson_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny CI sizes")
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--rate", type=float, default=4000.0)
    args = ap.parse_args()

    if args.smoke:
        ds = make_recsys(num_users=512, num_items=256, edges_per_user=6,
                         feature_dim=32, seed=0)
        requests, hidden = min(args.requests, 80), 64
    else:
        ds = make_recsys(num_users=4096, num_items=1024, seed=0)
        requests, hidden = args.requests, 128

    gnn = GNNConfig(model="gcn", num_layers=2, in_dim=ds.feature_dim,
                    hidden_dim=hidden, num_classes=ds.num_classes)
    params = init_gnn(jax.random.PRNGKey(0), gnn)
    trace = poisson_trace(requests, rate_rps=args.rate,
                          seed_pool=ds.user_ids, seed=1)
    print(f"graph: |V|={ds.graph.num_vertices} |E|={ds.graph.num_edges} "
          f"({ds.num_users} users / {ds.num_items} items)")
    print(f"trace: {requests} requests @ {args.rate:.0f} req/s\n")

    base = ServeConfig(num_layers=2, fanout=5, max_batch=64,
                       max_wait_ms=10.0, use_cache=False)
    indep = GNNServer(ds.graph, ds.features, gnn, params, base)
    rep_i = indep.serve_independent(trace)
    print(f"independent per-request : {rep_i.summary()}")

    ref = None
    for policy in ("max_batch", "max_wait_ms", "hybrid"):
        import dataclasses

        server = GNNServer(ds.graph, ds.features, gnn, params,
                           dataclasses.replace(base, policy=policy))
        rep = server.serve_trace(trace)
        print(f"coalesced [{policy:<11}]: {rep.summary()}")
        print(f"  fetch reduction vs independent: "
              f"{rep_i.fetched_rows / rep.fetched_rows:.2f}x, "
              f"compiles per bucket: {rep.compiles['serve.forward']}")
        if ref is None:
            ref = {s.request.rid: s.pred for s in rep.served}

    # predictions are bit-identical to per-request inference
    import numpy as np

    ok = all(np.array_equal(ref[s.request.rid], s.pred) for s in rep_i.served)
    print(f"\ncoalesced == per-request predictions (bit-identical): {ok}")


if __name__ == "__main__":
    main()
