"""Compile the Pallas kernels and a GCN layer step for a described TPU v5e.

Nothing here runs on a chip: each test lowers and compiles for a v5e that
is described, not attached, so what Mosaic or XLA refuses at the widths
of the main path (papers100M: feature dim 128, hidden 1024, b = 1024,
degree cap 32) fails here instead of on the chip.  The topology is
described inside a module-scoped fixture, never at import: only the test
worker that runs this file loads the TPU compiler.

Kernels listed in ``repro.kernels.errors.UNCOMPILED_ON_TPU`` are expected
to fail, strictly: once one compiles, its test fails until the entry is
removed and its ``ops.py`` wrapper runs it on the TPU again.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.errors import UNCOMPILED_ON_TPU

B, FANOUT_CAP, HIDDEN, FEAT = 1024, 32, 1024, 128
CAP1 = 14080        # CapacityPlan.geometric(1024, 3, 10, V).caps[1]
IDS = 14336         # CAP1 padded to the 512-row blocks of the gathers
V = 1 << 21


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log


def _cases(sharding):
    """name -> (fn, argument shapes) at the widths of the main path."""
    from repro.kernels.expand_indptr.kernel import expand_indptr_pallas
    from repro.kernels.frontier_gather.kernel import frontier_gather_pallas
    from repro.kernels.gather.kernel import paged_gather_pallas
    from repro.kernels.seg_softmax.kernel import seg_softmax_pallas
    from repro.kernels.spmm.kernel import spmm_pallas
    from repro.kernels.unique_compact.kernel import unique_compact_pallas
    from repro.store.kernel import tag_probe_pallas

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    hop1 = B * (1 + FANOUT_CAP)             # [S^0 | sampled nbrs], hop 1
    hop1 += (-hop1) % 4096                  # ops pads to the block
    return {
        # GAT edge softmax over the hop-1 destinations' neighbor slots
        "seg_softmax": (
            seg_softmax_pallas,
            [s((CAP1, FANOUT_CAP), jnp.float32), s((CAP1, FANOUT_CAP), bool)],
        ),
        "unique_compact": (
            lambda ids: unique_compact_pallas(ids, CAP1), [s((hop1,))],
        ),
        "expand_indptr": (
            lambda iptr: expand_indptr_pallas(iptr, CAP1 * FANOUT_CAP),
            [s((CAP1 + 1,))],
        ),
        "frontier_gather": (
            lambda iptr, ind, seeds: frontier_gather_pallas(
                iptr, ind, seeds, max_degree=FANOUT_CAP),
            [s((V + 1,)), s((29 * V,)), s((CAP1,))],
        ),
        "paged_gather": (
            paged_gather_pallas, [s((V, FEAT), jnp.float32), s((IDS,))],
        ),
        "spmm": (
            spmm_pallas,
            [s((CAP1, FEAT), jnp.float32), s((B, FANOUT_CAP)),
             s((B, FANOUT_CAP), bool)],
        ),
        "tag_probe": (
            # a CLOCK cache of V / 4 rows in 8 ways
            tag_probe_pallas, [s((V // 4 // 8, 8)), s((IDS,)), s((IDS,))],
        ),
    }


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", ["seg_softmax", "unique_compact", "expand_indptr"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    compiled = _compile(fn, args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", [
    pytest.param(name, marks=pytest.mark.xfail(strict=True, reason=why))
    for name, why in sorted(UNCOMPILED_ON_TPU.items())
])
def test_uncompiled_kernel_still_fails_for_v5e(one_chip, name):
    fn, args = _cases(one_chip)[name]
    _compile(fn, args)


def test_gcn_layer_step_compiles_for_v5e(one_chip):
    """Forward + backward of one hidden GCN layer at b=1024, hidden 1024:
    the b destinations of hop 0 aggregate their degree-capped neighbors
    out of the hop-1 frontier."""
    from repro.models.gnn import GNNConfig
    from repro.models.gnn.layers import layer_apply

    cfg = GNNConfig(model="gcn", num_layers=3, in_dim=FEAT,
                    hidden_dim=HIDDEN, num_classes=172)

    def step(p, Ht, self_idx, nbr_idx, mask):
        def loss(p, Ht):
            out = layer_apply(p, cfg, 1, Ht, self_idx, nbr_idx, mask, None)
            return jnp.sum(out)
        return jax.value_and_grad(loss, argnums=(0, 1))(p, Ht)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w": s((HIDDEN, HIDDEN)), "b": s((HIDDEN,))}
    compiled = _compile(step, [
        p, s((CAP1, HIDDEN)), s((B,), jnp.int32),
        s((B, FANOUT_CAP), jnp.int32), s((B, FANOUT_CAP), bool),
    ])
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
