"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.gather.kernel import paged_gather_pallas
from repro.kernels.gather.ref import gather_ref
from repro.kernels.seg_softmax.kernel import seg_softmax_pallas
from repro.kernels.seg_softmax.ref import seg_softmax_ref
from repro.kernels.spmm.kernel import spmm_pallas
from repro.kernels.spmm.ref import spmm_ref

R = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize(
    "S,d,n,w,block_n,block_d",
    [
        (256, 128, 128, 8, 128, 128),
        (512, 256, 256, 12, 128, 128),
        (128, 128, 128, 1, 64, 128),   # degenerate width
        (1024, 384, 384, 16, 128, 128),
    ],
)
def test_spmm_matches_ref(S, d, n, w, block_n, block_d, dtype):
    src = jnp.asarray(R.standard_normal((S, d)).astype(dtype))
    idx = jnp.asarray(R.integers(0, S, (n, w)).astype(np.int32))
    mask = jnp.asarray(R.random((n, w)) < 0.6)
    for mean in (True, False):
        out = spmm_pallas(
            src, idx, mask, mean=mean, block_n=block_n, block_d=block_d,
            interpret=True,
        )
        ref = spmm_ref(src, idx, mask, mean=mean)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_spmm_all_masked_rows_zero():
    src = jnp.ones((128, 128), jnp.float32)
    idx = jnp.zeros((128, 4), jnp.int32)
    mask = jnp.zeros((128, 4), bool)
    out = spmm_pallas(src, idx, mask, mean=True, block_n=128, interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize(
    "V,d,n,page,block_n",
    [(2048, 128, 512, 512, 512), (4096, 256, 1024, 1024, 512), (1024, 128, 512, 256, 256)],
)
def test_paged_gather_matches_ref(V, d, n, page, block_n):
    tab = jnp.asarray(R.standard_normal((V, d)).astype(np.float32))
    ids = np.concatenate(
        [R.integers(0, V, n - 32), np.full(32, np.int32(2**31 - 1))]
    ).astype(np.int32)
    R.shuffle(ids)
    out = paged_gather_pallas(
        tab, jnp.asarray(ids), block_n=block_n, block_d=128, page=page,
        interpret=True,
    )
    ref = gather_ref(tab, jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=10, deadline=None)
@given(
    n=st.sampled_from([256, 512]),
    w=st.integers(min_value=1, max_value=24),
    frac=st.floats(min_value=0.1, max_value=0.9),
)
def test_seg_softmax_property(n, w, frac):
    rng = np.random.default_rng(42)
    e = jnp.asarray(rng.standard_normal((n, w)).astype(np.float32))
    mask = jnp.asarray(rng.random((n, w)) < frac)
    out = seg_softmax_pallas(e, mask, block_n=256, interpret=True)
    ref = seg_softmax_ref(e, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    out_np = np.asarray(out)
    m = np.asarray(mask)
    # rows with any valid slot sum to 1; invalid slots are exactly 0
    sums = out_np.sum(1)
    np.testing.assert_allclose(sums[m.any(1)], 1.0, atol=1e-5)
    assert (out_np[~m] == 0).all()


def test_ops_wrappers_dispatch_to_ref_on_cpu():
    """Public ops fall back to the oracle off-TPU (same math)."""
    from repro.kernels import paged_gather, seg_softmax, spmm_mean

    src = jnp.ones((64, 32), jnp.float32)
    idx = jnp.zeros((16, 4), jnp.int32)
    mask = jnp.ones((16, 4), bool)
    out = spmm_mean(src, idx, mask)
    np.testing.assert_allclose(np.asarray(out), 1.0)
    tab = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)
    np.testing.assert_array_equal(
        np.asarray(paged_gather(tab, jnp.asarray([2], jnp.int32)))[0],
        np.asarray(tab[2]),
    )
    e = jnp.zeros((8, 4))
    m = jnp.ones((8, 4), bool)
    np.testing.assert_allclose(np.asarray(seg_softmax(e, m)), 0.25)


# ---------------------------------------------------------------------------
# plan-construction kernels: unique_compact / frontier_gather / expand_indptr
# ---------------------------------------------------------------------------
from repro.core import frontier
from repro.kernels.expand_indptr.kernel import expand_indptr_pallas
from repro.kernels.expand_indptr.ref import expand_indptr_ref
from repro.kernels.frontier_gather.kernel import frontier_gather_pallas
from repro.kernels.frontier_gather.ref import frontier_gather_ref
from repro.kernels.unique_compact.kernel import unique_compact_pallas
from repro.kernels.unique_compact.ref import unique_with_inverse_ref

INVALID = np.int32(2**31 - 1)


def _padded_ids(m, hi, invalid_frac, seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, hi, size=m).astype(np.int32)
    ids[rng.random(m) < invalid_frac] = INVALID
    return jnp.asarray(ids)


@pytest.mark.parametrize(
    "m,cap,hi,block_m",
    [
        (512, 64, 100, 256),     # heavy duplication, overflow
        (512, 600, 100, 256),    # cap > unique count (normal regime)
        (256, 16, 8, 256),       # cap > value range: every id fits
        (1024, 128, 2**20, 256), # near-distinct ids
        (300, 64, 50, 128),      # m not a block multiple (ops pads)
    ],
)
def test_unique_compact_matches_frontier_algebra(m, cap, hi, block_m):
    """Kernel + ref both bit-match unique_padded + lookup."""
    ids = _padded_ids(m, hi, 0.2, seed=m + cap)
    uniq0 = frontier.unique_padded(ids, cap)
    inv0 = frontier.lookup(uniq0, ids)
    uniq1, inv1 = unique_with_inverse_ref(ids, cap)
    np.testing.assert_array_equal(np.asarray(uniq0), np.asarray(uniq1))
    np.testing.assert_array_equal(np.asarray(inv0), np.asarray(inv1))
    pad = (-m) % block_m
    flat = jnp.pad(ids, (0, pad), constant_values=INVALID)
    order = jnp.argsort(flat)
    inv_s, uniq2 = unique_compact_pallas(
        flat[order], cap, block_m=block_m, interpret=True
    )
    inv2 = jnp.zeros((m + pad,), jnp.int32).at[order].set(inv_s)[:m]
    np.testing.assert_array_equal(np.asarray(uniq0), np.asarray(uniq2))
    np.testing.assert_array_equal(np.asarray(inv0), np.asarray(inv2))


def test_unique_compact_all_invalid_and_empty_cap_edge():
    ids = jnp.full((256,), INVALID)
    uniq, inv = unique_with_inverse_ref(ids, 32)
    assert (np.asarray(uniq) == INVALID).all()
    assert (np.asarray(inv) == -1).all()
    inv_s, uniq_k = unique_compact_pallas(ids, 32, block_m=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(uniq_k), np.asarray(uniq))
    np.testing.assert_array_equal(np.asarray(inv_s), np.asarray(inv))


def test_frontier_gather_matches_neighbor_table(small_graph):
    g = small_graph
    n = 192
    rng = np.random.default_rng(3)
    seeds = rng.integers(0, g.num_vertices, size=n).astype(np.int32)
    seeds[rng.random(n) < 0.15] = INVALID
    seeds = jnp.asarray(seeds)
    nbr0, mask0 = g.neighbor_table(seeds)
    nbr1, mask1 = frontier_gather_ref(g.indptr, g.indices, seeds, g.max_degree)
    np.testing.assert_array_equal(np.asarray(nbr0), np.asarray(nbr1))
    np.testing.assert_array_equal(np.asarray(mask0), np.asarray(mask1))
    block_n, page = 64, 1024
    pad_n = (-n) % block_n
    pad_e = (-g.num_edges) % page
    seeds_p = jnp.pad(seeds, (0, pad_n), constant_values=INVALID)
    ind_p = jnp.pad(g.indices, (0, pad_e), constant_values=INVALID)
    nbr2 = frontier_gather_pallas(
        g.indptr, ind_p, seeds_p, max_degree=g.max_degree,
        block_n=block_n, page=page, interpret=True,
    )[:n]
    np.testing.assert_array_equal(np.asarray(nbr0), np.asarray(nbr2))
    np.testing.assert_array_equal(np.asarray(mask0), np.asarray(nbr2 != INVALID))


@pytest.mark.parametrize("R_,Ecap", [(8, 512), (256, 1024), (1, 512)])
def test_expand_indptr_matches_ref(R_, Ecap):
    rng = np.random.default_rng(R_)
    deg = rng.integers(0, 9, size=R_)
    iptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]).astype(np.int32))
    want = expand_indptr_ref(iptr, Ecap)
    got = expand_indptr_pallas(iptr, Ecap, block_e=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # row ids consistent with searchsorted semantics incl. empty rows
    w = np.asarray(want)
    total = min(int(iptr[-1]), Ecap)
    assert (w[total:] == -1).all()
    for e in range(total):
        r = w[e]
        assert iptr[r] <= e < iptr[r + 1]


def test_plan_kernel_ops_dispatch_to_ref_on_cpu():
    """Public ops fall back to the oracle off-TPU (same bits)."""
    from repro.kernels import expand_indptr, frontier_gather, unique_with_inverse

    assert jax.default_backend() != "tpu"  # CI precondition
    ids = _padded_ids(400, 64, 0.1, seed=9)
    uniq, inv = unique_with_inverse(ids, 48)
    np.testing.assert_array_equal(
        np.asarray(uniq), np.asarray(frontier.unique_padded(ids, 48))
    )
    np.testing.assert_array_equal(
        np.asarray(inv), np.asarray(frontier.lookup(uniq, ids))
    )
    iptr = jnp.asarray([0, 2, 2, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(expand_indptr(iptr, 8)),
        np.asarray([0, 0, 2, 2, 2, -1, -1, -1]),
    )


# ---------------------------------------------------------------------------
# TPU dispatch: every ops wrapper runs its compiled kernel or raises, naming
# it.  The backend is steered here, in the test, and kernels run in
# interpret mode where one is due to run.
# ---------------------------------------------------------------------------
def _on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_fused_plan_backend_raises_on_tpu(monkeypatch):
    from repro.engine import EngineConfig
    from repro.kernels.errors import KernelUnavailableError

    _on_tpu(monkeypatch)
    with pytest.raises(KernelUnavailableError, match="frontier_gather"):
        EngineConfig(plan_backend="fused")
    EngineConfig(plan_backend="reference")  # the default path is unaffected


@pytest.mark.parametrize("num_edges", [1000, 1024, 2050])
def test_expand_indptr_pads_on_tpu(monkeypatch, num_edges):
    """A length that is not a block multiple runs the kernel on the padded
    length and slices, instead of giving way to the oracle."""
    import importlib

    # the package attribute is shadowed by the function of the same name
    ops = importlib.import_module("repro.kernels.expand_indptr.ops")

    calls = []

    def interpreted(indptr, n, *, block_e):
        calls.append((n, block_e))
        return expand_indptr_pallas(indptr, n, block_e=block_e, interpret=True)

    _on_tpu(monkeypatch)
    monkeypatch.setattr(ops, "expand_indptr_pallas", interpreted)
    deg = np.random.default_rng(num_edges).integers(0, 9, size=200)
    iptr = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]).astype(np.int32))
    got = ops.expand_indptr(iptr, num_edges)
    assert calls == [(-(-num_edges // 1024) * 1024, 1024)]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(expand_indptr_ref(iptr, num_edges))
    )


def _uncompiled_calls():
    from repro import kernels
    from repro.store.kernel import tag_probe

    ids = jnp.zeros((8,), jnp.int32)
    return {
        "frontier_gather": lambda: kernels.frontier_gather(
            jnp.zeros((9,), jnp.int32), ids, ids, 4),
        "paged_gather": lambda: kernels.paged_gather(jnp.zeros((8, 4)), ids),
        "spmm": lambda: kernels.spmm_mean(
            jnp.zeros((8, 4)), jnp.zeros((8, 2), jnp.int32),
            jnp.ones((8, 2), bool)),
        "tag_probe": lambda: tag_probe(jnp.zeros((8, 2), jnp.int32), ids, ids),
    }


@pytest.mark.parametrize(
    "kernel", ["frontier_gather", "paged_gather", "spmm", "tag_probe"]
)
def test_uncompiled_kernel_wrappers_raise_on_tpu(monkeypatch, kernel):
    from repro.kernels.errors import UNCOMPILED_ON_TPU, KernelUnavailableError

    assert kernel in UNCOMPILED_ON_TPU
    call = _uncompiled_calls()[kernel]
    call()  # off the TPU: the oracle
    _on_tpu(monkeypatch)
    with pytest.raises(KernelUnavailableError, match=kernel):
        call()


def test_clock_cache_probe_runs_on_tpu(monkeypatch):
    """Serving's default cache probes with XLA on every backend."""
    from repro.core.graph import INVALID
    from repro.store import ClockCache

    _on_tpu(monkeypatch)
    # a shape no other test uses, so clock_access traces under the patch
    cache = ClockCache(capacity=72, ways=8)
    ids = np.array([[3, 5, 9, INVALID, 11]], np.int32)
    assert cache.access_batch(ids) == 4
    assert cache.access_batch(ids) == 0
