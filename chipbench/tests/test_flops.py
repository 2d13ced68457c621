"""The FLOP count of ``step.mfu.train`` by hand at a tiny plan, through
each model's module."""
import flops


def test_gcn_two_layers_by_hand():
    # plan: S0 = 2 seeds, S1 = 5, S2 = 9; E0 = 4, E1 = 11; in 3, hidden 4,
    # classes 2.  Layer 1 reads the features (3 -> 4): forward
    # (11 + 2*5)*3 + 2*5*3*4 = 63 + 120, weight grad 120, no input grad.
    # Layer 0 (4 -> 2): forward (4 + 2*2)*4 + 2*2*4*2 = 32 + 32, weight
    # grad 32, input grad 32 + 32.
    want = (63 + 120 + 120) + (32 + 32 + 32 + 32 + 32)
    cfg = {"model": "gcn", "feature_dim": 3, "hidden_dim": 4, "num_classes": 2}
    assert flops.step_flops(cfg, [2, 5, 9], [4, 11]) == want


def test_rgcn_counts_a_matmul_per_relation_and_self():
    # one layer reading the features, R = 2: aggregation (e + R n) k,
    # R + 1 matmuls forward and as many weight-gradient matmuls
    n, e, k, m, R = 3, 7, 5, 2, 2
    want = (e + R * n) * k + 2 * (2 * n * k * m * (R + 1))
    cfg = {"model": "rgcn", "feature_dim": k, "hidden_dim": 8, "num_classes": m,
           "graph": {"relation_shares": [0.5, 0.5]}}
    assert flops.step_flops(cfg, [n, 10], [e]) == want
