"""Launch-layer units: HLO cost parser, sharding rules, specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.roofline import model_flops, peaks_for
from repro.launch.specs import SHAPES, batch_specs, shape_applicable


def test_hlo_parser_counts_loop_iterations():
    """A jitted scan's dots must be multiplied by the trip count."""

    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, x, None, length=7)
        return h

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    costs = analyze_hlo(compiled.as_text())
    expect = 7 * 2 * 64 * 64 * 64
    assert abs(costs.dot_flops - expect) / expect < 0.01, costs.dot_flops


def test_hlo_parser_finds_unrolled_dots():
    def f(x, w):
        for _ in range(3):
            x = x @ w
        return x

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    compiled = jax.jit(f).lower(x, x).compile()
    costs = analyze_hlo(compiled.as_text())
    expect = 3 * 2 * 32**3
    assert abs(costs.dot_flops - expect) / expect < 0.01


def test_batch_specs_shapes():
    from repro.configs import get_config

    cfg = get_config("internvl2-26b")
    spec = SHAPES["train_4k"]
    b = batch_specs(cfg, spec)
    # vlm: 64 prefix patch embeddings + text fills the rest of seq_len
    assert b["tokens"].shape == (256, 4096 - 64)
    assert b["prefix_embeds"].shape == (256, 64, cfg.d_model)

    cfg_w = get_config("whisper-tiny")
    bw = batch_specs(cfg_w, SHAPES["prefill_32k"])
    assert bw["enc_out"].shape == (32, cfg_w.enc_len, cfg_w.d_model)


def test_shape_applicability_matrix():
    from repro.configs import ALL_ARCHS, get_config

    long_ok = {a for a in ALL_ARCHS if shape_applicable(get_config(a), "long_500k")[0]}
    assert long_ok == {"mamba2-2.7b", "hymba-1.5b", "gemma2-2b", "gemma3-27b"}
    for a in ALL_ARCHS:  # every other shape applies to every arch
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(get_config(a), s)[0]


def test_model_flops_formulas():
    from repro.configs import get_config
    from repro.models.transformer.config import active_param_count

    cfg = get_config("granite-3-8b")
    n = active_param_count(cfg)
    t = model_flops(cfg, SHAPES["train_4k"], n)
    assert t == 6.0 * n * 256 * 4096
    d = model_flops(cfg, SHAPES["decode_32k"], n)
    assert d == 2.0 * n * 128


def test_param_sharding_rules_small_mesh():
    """Divisibility gating: shards what divides, replicates what doesn't."""
    from repro.launch.shardings import param_shardings

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = {
        "embed": jnp.zeros((64, 8)),
        "blocks": [{"attn": {"wq": jnp.zeros((2, 8, 16))},
                    "norm1": jnp.zeros((2, 8))}],
        "tail": [],
        "final_norm": jnp.zeros((8,)),
    }
    sh = param_shardings(mesh, params)
    assert sh["embed"].spec == P("model", None)
    assert sh["blocks"][0]["attn"]["wq"].spec == P(None, None, "model")
    assert sh["blocks"][0]["norm1"].spec == P(None, None)


def test_roofline_peaks_are_keyed_by_device_kind():
    """v5e's published peaks; a device without published peaks is an
    error, not a silent v5e default."""
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="cpu"):
        peaks_for("cpu")
