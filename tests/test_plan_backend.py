"""plan_backend API: reference-vs-fused parity, seed schedule, CacheConfig.

The fused backend must be a pure lowering choice: given the same
RNGState, ``plan_backend="fused"`` and ``"reference"`` produce
bit-identical plans in every mode and schedule.  On CPU the fused ops
dispatch to their jnp oracles, so this suite pins the *algorithmic*
equivalence (fused unique-with-inverse vs unique_padded + lookup, merged
resolve pass, COO assembly); the interpret-mode kernel tests in
test_kernels.py pin the Pallas kernels against those same oracles.
"""
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frontier
from repro.core.graph import INVALID
from repro.core.minibatch import layer_to_coo
from repro.engine import CacheConfig, EngineConfig, MinibatchEngine


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _engine(graph, backend, **kw):
    kw.setdefault("local_batch", 16)
    kw.setdefault("num_layers", 2)
    kw.setdefault("fanout", 4)
    kw.setdefault("sampler", "labor0")
    cfg = EngineConfig(plan_backend=backend, seed=3, **kw)
    return MinibatchEngine.from_config(graph, cfg)


CONFIGS = [
    dict(mode="independent", num_pes=1, schedule="iid"),
    dict(mode="independent", num_pes=2, schedule="smoothed", kappa=4),
    dict(mode="independent", num_pes=2, schedule="nested", kappa=4),
    dict(mode="cooperative", num_pes=2, schedule="iid"),
    dict(mode="cooperative", num_pes=2, schedule="smoothed", kappa=4),
    dict(mode="cooperative", num_pes=2, schedule="nested", kappa=4),
]


@pytest.mark.parametrize(
    "kw", CONFIGS, ids=[f"{c['mode']}-{c['schedule']}" for c in CONFIGS]
)
def test_fused_plans_bit_identical(small_graph, kw):
    ref = _engine(small_graph, "reference", **kw)
    fus = _engine(small_graph, "fused", **kw)
    for step in (0, 3, 5):
        _assert_trees_equal(ref.plan_at(step), fus.plan_at(step))


@pytest.mark.parametrize("sampler", ["ns", "full", "rw"])
def test_fused_parity_other_samplers(small_graph, sampler):
    ref = _engine(small_graph, "reference", sampler=sampler)
    fus = _engine(small_graph, "fused", sampler=sampler)
    _assert_trees_equal(ref.plan_at(1), fus.plan_at(1))


def test_plan_at_matches_build_plan(small_graph):
    """plan_at(step) == build_plan(seed_batch(step), rng_state(step))."""
    for kw in (CONFIGS[1], CONFIGS[3]):
        eng = _engine(small_graph, "reference", **kw)
        for step in (0, 4):
            direct = eng.build_plan(
                eng.seed_batch(step), rng=eng.rng_state(step)
            )
            _assert_trees_equal(eng.plan_at(step), direct)


# ---------------------------------------------------------------------------
# frontier-level overflow policy, both backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_unique_with_inverse_at_exact_capacity(backend):
    ids = jnp.asarray(np.r_[np.arange(32), np.arange(32)], jnp.int32)
    uniq, inv = frontier.unique_with_inverse(ids, 32, backend=backend)
    np.testing.assert_array_equal(np.asarray(uniq), np.arange(32))
    np.testing.assert_array_equal(
        np.asarray(inv), np.r_[np.arange(32), np.arange(32)]
    )


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_unique_with_inverse_above_capacity_keeps_smallest(backend):
    ids = jnp.asarray(np.arange(64)[::-1].copy(), jnp.int32)
    uniq, inv = frontier.unique_with_inverse(ids, 16, backend=backend)
    np.testing.assert_array_equal(np.asarray(uniq), np.arange(16))
    inv_np = np.asarray(inv)
    assert (inv_np[:48] == -1).all()        # ids 63..16 overflow
    np.testing.assert_array_equal(inv_np[48:], np.arange(16)[::-1])


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_unique_with_inverse_invalid_padding(backend):
    ids = jnp.asarray([5, INVALID, 5, 7, INVALID], jnp.int32)
    uniq, inv = frontier.unique_with_inverse(ids, 4, backend=backend)
    np.testing.assert_array_equal(np.asarray(uniq), [5, 7, INVALID, INVALID])
    np.testing.assert_array_equal(np.asarray(inv), [0, -1, 0, 1, -1])


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="plan backend"):
        frontier.unique_with_inverse(jnp.arange(4), 4, backend="gpu")
    with pytest.raises(ValueError, match="plan_backend"):
        EngineConfig(plan_backend="gpu")


# ---------------------------------------------------------------------------
# layer_to_coo
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_layer_to_coo_consistent(small_graph, backend):
    eng = _engine(small_graph, backend, num_pes=1)
    plan = eng.build_plan(eng.seed_batch(0)[0])  # 1-D plan
    layer = plan.layers[0]
    n, w = layer.nbr_idx.shape
    cap_e = n * w
    rows, cols, indptr = layer_to_coo(layer, cap_e, backend=backend)
    rows, cols, indptr = map(np.asarray, (rows, cols, indptr))
    mask = np.asarray(layer.mask)
    nbr_idx = np.asarray(layer.nbr_idx)
    total = int(mask.sum())
    assert indptr[-1] == total
    assert (rows[total:] == -1).all() and (cols[total:] == -1).all()
    # edge e sits in dst row rows[e] with src position cols[e], in
    # row-major order of the mask
    e = 0
    for i in range(n):
        assert indptr[i] == e
        for j in range(w):
            if mask[i, j]:
                assert rows[e] == i
                assert cols[e] == nbr_idx[i, j]
                e += 1
    assert e == total


# ---------------------------------------------------------------------------
# seed schedule invariants + golden pin
# ---------------------------------------------------------------------------
def test_seed_batch_golden_pin(small_graph):
    """Bit-pin the hash-permutation seed draw (regression anchor for the
    device-resident schedule that replaced the per-PE numpy loops)."""
    eng = _engine(small_graph, "reference", num_pes=2, schedule="nested",
                  kappa=4)
    got = eng.seed_batch(0)
    assert got.shape == (2, 16) and got.dtype == np.int32
    # fingerprint instead of 32 literals: stable across platforms because
    # the draw is pure integer hashing
    digest = int(np.uint64(np.abs(got.astype(np.int64) * 31).sum()))
    expect = EXPECTED_DIGESTS["nested"]
    assert digest == expect, (digest, got.tolist())
    eng_i = _engine(small_graph, "reference", num_pes=2, schedule="iid")
    got_i = eng_i.seed_batch(1)
    digest_i = int(np.uint64(np.abs(got_i.astype(np.int64) * 31).sum()))
    assert digest_i == EXPECTED_DIGESTS["iid"], (digest_i, got_i.tolist())


# weighted-sum fingerprints of seed_batch output for the configs above;
# any change to the hash-permutation draw must consciously update these
EXPECTED_DIGESTS = {"nested": 625084, "iid": 450244}


def test_nested_seed_batch_is_vectorized_and_disjoint(small_graph):
    """Sub-batches within one κ-group partition the group draw; the draw
    is a single batched permutation (no per-PE python RNG loop)."""
    eng = _engine(small_graph, "reference", num_pes=2, schedule="nested",
                  kappa=4)
    for p in range(2):
        seen = set()
        for step in range(4):
            row = eng.seed_batch(step)[p]
            row = row[row != np.int32(INVALID)]
            assert len(set(row.tolist()) & seen) == 0
            seen |= set(row.tolist())
    # next group reshuffles
    g0 = eng.seed_batch(0)
    g1 = eng.seed_batch(4)
    assert not np.array_equal(g0, g1)


def test_independent_draw_without_replacement_across_pes(small_graph):
    eng = _engine(small_graph, "reference", num_pes=4, schedule="iid",
                  local_batch=32)
    seeds = eng.seed_batch(7)
    valid = seeds[seeds != np.int32(INVALID)]
    assert len(valid) == len(set(valid.tolist()))  # global no-replacement


def test_cooperative_seed_rows_stay_owned(small_graph):
    eng = _engine(small_graph, "fused", mode="cooperative", num_pes=2)
    owner = np.asarray(eng.part.owner)
    for step in range(3):
        seeds = eng.seed_batch(step)
        for p in range(2):
            row = seeds[p][seeds[p] != np.int32(INVALID)]
            assert (owner[row] == p).all()


@pytest.mark.parametrize("schedule", ["iid", "nested"])
def test_cooperative_pool_width_is_the_pool(small_graph, schedule):
    """The cooperative pool table is as wide as the train pool, whatever
    share each PE owns, so a train step compiled for one dataset serves
    another of the same size; the draw equals one from a table padded
    only to the largest share."""
    from repro.data.synthetic import SyntheticGraphDataset
    from repro.engine.engine import _hash_permute_rows

    widths = set()
    for data_seed in (0, 1):
        ds = SyntheticGraphDataset(small_graph, feature_dim=4, num_classes=3,
                                   seed=data_seed)
        cfg = EngineConfig(mode="cooperative", num_pes=2, local_batch=16,
                           num_layers=2, fanout=4, schedule=schedule,
                           kappa=2, seed=3)
        eng = MinibatchEngine.from_config(small_graph, cfg, dataset=ds)
        rows = np.asarray(eng._seed_rows)
        widths.add(rows.shape[1])
        assert rows.shape[1] == len(ds.train_ids)
        narrow = rows[:, : max(len(r) for r in eng._owned_pools)]
        z = jnp.uint32(12345)
        np.testing.assert_array_equal(
            np.asarray(_hash_permute_rows(jnp.asarray(rows), z))[:, : narrow.shape[1]],
            np.asarray(_hash_permute_rows(jnp.asarray(narrow), z)))
    assert len(widths) == 1


# ---------------------------------------------------------------------------
# CacheConfig migration
# ---------------------------------------------------------------------------
def test_legacy_cache_kwargs_warn_and_map():
    with pytest.warns(DeprecationWarning):
        cfg = EngineConfig(feature_cache=True, cache_capacity=128,
                           cache_ways=4)
    assert cfg.cache == CacheConfig(enabled=True, capacity=128, ways=4)
    # mirrored legacy attrs keep old readers working
    assert cfg.feature_cache is True
    assert cfg.cache_capacity == 128
    assert cfg.cache_ways == 4


def test_cache_config_no_warning():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg = EngineConfig(cache=CacheConfig(enabled=True, capacity=64))
    assert cfg.cache.enabled and cfg.cache.capacity == 64


def test_replace_does_not_rewarn():
    with pytest.warns(DeprecationWarning):
        cfg = EngineConfig(feature_cache=True, cache_capacity=128)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg2 = cfg.with_mode("cooperative")
        cfg3 = replace(cfg2, num_pes=2)
    assert cfg3.cache == cfg.cache


def test_conflicting_cache_specs_rejected():
    with pytest.raises(ValueError, match="disagree"):
        EngineConfig(cache=CacheConfig(enabled=True), feature_cache=False)


def test_cache_validation_still_enforced():
    with pytest.raises(ValueError):
        CacheConfig(ways=0)
    with pytest.raises(ValueError):
        CacheConfig(capacity=2, ways=8)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            EngineConfig(cache_capacity=2, cache_ways=8)
