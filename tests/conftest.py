"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on ONE device;
multi-device tests start their own subprocess with forced host devices."""
import jax
import numpy as np
import pytest

from repro.data.synthetic import SyntheticGraphDataset, rmat_graph


@pytest.fixture(scope="session")
def small_graph():
    return rmat_graph(scale=10, edge_factor=8, max_degree=32, seed=0)


@pytest.fixture(scope="session")
def small_dataset(small_graph):
    return SyntheticGraphDataset(small_graph, feature_dim=32, num_classes=8, seed=0)


@pytest.fixture(scope="session")
def rel_graph():
    return rmat_graph(scale=9, edge_factor=6, max_degree=24, num_edge_types=4, seed=1)
