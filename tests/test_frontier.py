"""Padded set-ops: property-based (hypothesis) + unit tests."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import frontier
from repro.core.graph import INVALID
from repro.core.minibatch import CapacityPlan, build_minibatch
from repro.core.rng import DependentRNG
from repro.core.samplers import make_sampler

ids_strategy = st.lists(
    st.integers(min_value=0, max_value=500), min_size=0, max_size=64
)


@settings(max_examples=30, deadline=None)
@given(ids_strategy)
def test_unique_padded_matches_numpy(ids):
    ids_np = np.asarray(ids or [0], dtype=np.int32)
    cap = 128
    out = np.asarray(frontier.unique_padded(jnp.asarray(ids_np), cap))
    valid = out[out != INVALID]
    expect = np.unique(ids_np)
    np.testing.assert_array_equal(valid, expect)
    # sorted, padding at the end
    assert (np.sort(out) == out).all()


@settings(max_examples=30, deadline=None)
@given(ids_strategy, ids_strategy)
def test_union_is_set_union(a, b):
    a_np = np.asarray(a or [1], dtype=np.int32)
    b_np = np.asarray(b or [2], dtype=np.int32)
    out = np.asarray(
        frontier.union_padded(jnp.asarray(a_np), jnp.asarray(b_np), 256)
    )
    valid = out[out != INVALID]
    np.testing.assert_array_equal(valid, np.union1d(a_np, b_np))


@settings(max_examples=30, deadline=None)
@given(ids_strategy)
def test_lookup_inverts_membership(ids):
    ids_np = np.unique(np.asarray(ids or [3], dtype=np.int32))
    table = frontier.pad_to(jnp.asarray(ids_np), 128)
    pos = np.asarray(frontier.lookup(table, jnp.asarray(ids_np)))
    assert (pos >= 0).all()
    np.testing.assert_array_equal(np.asarray(table)[pos], ids_np)
    # absent ids -> -1
    absent = jnp.asarray([1001, 1002], jnp.int32)
    assert (np.asarray(frontier.lookup(table, absent)) == -1).all()


def test_lookup_invalid_is_minus_one():
    table = frontier.pad_to(jnp.asarray([1, 2, 3], jnp.int32), 8)
    out = frontier.lookup(table, jnp.asarray([INVALID], jnp.int32))
    assert int(out[0]) == -1


def test_count_valid():
    v = frontier.pad_to(jnp.asarray([5, 6], jnp.int32), 10)
    assert int(frontier.count_valid(v)) == 2


# --------------------------------------------------------------------------
# dedup-and-rank: one key-value sort against the independent pair
# unique_padded + lookup (jnp.unique, then a binary search)
# --------------------------------------------------------------------------
def _random_ids(rng, shape, num_vertices, invalid_share):
    ids = rng.integers(0, num_vertices, size=shape, dtype=np.int32)
    return np.where(rng.random(shape) < invalid_share, INVALID, ids).astype(np.int32)


def _oracle(flat, cap):
    uniq = frontier.unique_padded(flat, cap)
    return np.asarray(uniq), np.asarray(frontier.lookup(uniq, flat))


DEDUP_CASES = {
    # name: (input shape, cap, number of vertices, INVALID share)
    "no_invalid": ((4096,), 1024, 800, 0.0),
    "two_thirds_invalid": ((4096,), 2048, 3000, 0.66),
    "all_invalid": ((512,), 64, 100, 1.0),
    "overflow": ((5000,), 100, 4000, 0.3),
    "m_below_cap": ((50,), 256, 1000, 0.2),
    "cap_equals_V": ((3000,), 512, 512, 0.1),
    "vmap_stacked": ((3, 1024), 256, 2000, 0.5),
}


@pytest.mark.parametrize("case", list(DEDUP_CASES))
@pytest.mark.parametrize("seed", [0, 7])
def test_sort_dedup_matches_unique_padded_plus_lookup(case, seed):
    shape, cap, num_vertices, invalid_share = DEDUP_CASES[case]
    ids = _random_ids(np.random.default_rng(seed), shape, num_vertices, invalid_share)
    if len(shape) == 1:
        uniq, inv = frontier.unique_with_inverse(jnp.asarray(ids), cap)
        uniqs, invs = np.asarray(uniq)[None], np.asarray(inv)[None]
        rows = ids[None]
    else:
        uniqs, invs = jax.vmap(
            lambda x: frontier.unique_with_inverse(x, cap)
        )(jnp.asarray(ids))
        uniqs, invs, rows = np.asarray(uniqs), np.asarray(invs), ids
    for row, uniq, inv in zip(rows, uniqs, invs):
        uniq0, inv0 = _oracle(jnp.asarray(row), cap)
        np.testing.assert_array_equal(uniq, uniq0)
        np.testing.assert_array_equal(inv, inv0)
        assert inv.dtype == np.int32
        n_unique = len(np.unique(row[row != INVALID]))
        if case == "overflow":
            assert n_unique > cap  # the case does overflow: ranks >= cap read -1
            assert (inv == -1).sum() > (row == INVALID).sum()
        if case == "all_invalid":
            assert (uniq == INVALID).all() and (inv == -1).all()


def _hlo_counts(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return len(re.findall(r"\bwhile\(", txt)), len(re.findall(r"\bsort\(", txt))


def test_dedup_compiles_to_one_sort_and_no_search(small_graph):
    """No binary search (a while loop) in the dedup or in a whole plan."""
    ids = jax.ShapeDtypeStruct((4096,), jnp.int32)
    assert _hlo_counts(lambda x: frontier.unique_with_inverse(x, 1024), ids) == (0, 1)

    num_layers = 2
    sampler = make_sampler("labor0", fanout=4)
    caps = CapacityPlan.geometric(16, num_layers, 4, small_graph.num_vertices)

    def plan(graph, seeds):
        rng = DependentRNG(0, 1, 0)
        return build_minibatch(graph, sampler, seeds, rng, num_layers, caps)

    seeds = jax.ShapeDtypeStruct((16,), jnp.int32)
    # one sort for the seed frontier, one per hop's dedup (LABOR-0 sorts nothing)
    assert _hlo_counts(plan, small_graph, seeds) == (0, num_layers + 1)
