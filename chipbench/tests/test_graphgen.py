"""The device generator against the host generator it replaces."""
import numpy as np
import pytest

import graphgen as gg

SHARES = (0.750854, 0.111673, 0.111673, 0.0129, 0.0129)


def spec(scale, edge_factor, shares=(1.0,)):
    return gg.GraphSpec(scale, edge_factor, 0.30, 0.22, 0.22, 32, 8, 5, 100,
                        shares)


@pytest.mark.parametrize("edge_factor", [16, 7])
def test_csr_is_valid_simple_and_capped(edge_factor):
    s = spec(12, edge_factor, SHARES)
    n = gg.edge_count(gg.data_key(5), s)
    ds, yielded = gg.generate(5, s, n - 100)
    g = ds.graph.validate()
    assert yielded == n and g.num_edges == n - 100
    deg = np.diff(np.asarray(g.indptr))
    dst = np.repeat(np.arange(g.num_vertices), deg)
    src = np.asarray(g.indices)
    assert not (src == dst).any(), "self-loop"
    pairs = dst.astype(np.int64) * g.num_vertices + src
    assert len(np.unique(pairs)) == len(pairs), "parallel edge"
    assert deg.max() <= 32
    assert len(ds.train_ids) == 100 and len(np.unique(ds.train_ids)) == 100
    assert ds.features.shape == (4096, 8) and int(ds.labels.max()) < 5


@pytest.mark.parametrize("scale,edge_factor", [(14, 16), (14, 7)])
def test_degrees_match_rmat_graph(scale, edge_factor):
    """Mean degree within 1 %, share of vertices at the cap within 2
    points of the host generator with the same parameters."""
    from repro.data import rmat_graph

    s = spec(scale, edge_factor)
    n = gg.edge_count(gg.data_key(1), s)
    ds, _ = gg.generate(1, s, n)
    mine = np.diff(np.asarray(ds.graph.indptr))
    host = np.diff(np.asarray(rmat_graph(
        scale=scale, edge_factor=edge_factor, max_degree=32, a=0.30, b=0.22,
        c=0.22, seed=1).indptr))
    assert abs(mine.mean() / host.mean() - 1) < 0.01
    assert abs((mine == 32).mean() - (host == 32).mean()) < 0.02


def test_relation_shares_match_config():
    s = spec(14, 7, SHARES)
    n = gg.edge_count(gg.data_key(2), s)
    ds, _ = gg.generate(2, s, n)
    got = np.bincount(np.asarray(ds.graph.edge_types), minlength=5) / n
    np.testing.assert_allclose(got, SHARES, atol=0.003)


def test_same_seed_same_data_and_large_seeds_differ():
    s = spec(10, 8)
    n = gg.edge_count(gg.data_key(2**33 + 1), s) - 10
    a, _ = gg.generate(2**33 + 1, s, n)
    b, _ = gg.generate(2**33 + 1, s, n)
    c, _ = gg.generate(1, s, n)
    assert np.array_equal(a.graph.indices, b.graph.indices)
    assert np.array_equal(a.features, b.features)
    assert not np.array_equal(a.features, c.features)


def test_too_few_edges_is_an_error():
    s = spec(10, 8)
    n = gg.edge_count(gg.data_key(3), s)
    with pytest.raises(ValueError):
        gg.generate(3, s, n + 1)
