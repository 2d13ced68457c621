"""A model reaches the harness as one file, ``chipbench/models/<model>.py``,
found by the configuration's ``"model"``: a toy module dropped into a
models directory drives the program's configuration, the reference's
parameters, layers and steps, and the FLOP count, with no other file
changed; a model with no module names the file to add."""
import numpy as np
import pytest

import byname
import flops
import reference
import run

TOY = '''
import jax.numpy as jnp

import flops
from reference import glorot


def program_args(cfg):
    return {"num_heads": cfg["toy_heads"]}


def init_layer(ks, d_in, d_out, cfg):
    return {"v": glorot(ks[2], (d_in, d_out))}


def layer(p, h, L, is_out, dtype, prec, cfg):
    out = jnp.matmul(h[L["self_idx"]], p["v"], precision=prec)
    return out if is_out else jnp.tanh(out)


def step_flops(sizes, edges, cfg):
    return flops.train_step_flops(sizes, edges, cfg,
                                  lambda n, e, k, m: (0.0, 2 * n * k * m))
'''

CFG = {"model": "toy", "toy_heads": 3, "num_layers": 2, "feature_dim": 4,
       "hidden_dim": 6, "num_classes": 3, "fanout": 2, "local_batch": 4,
       "dtype": "float32"}


@pytest.fixture
def toy_dir(tmp_path, monkeypatch):
    (tmp_path / "toy.py").write_text(TOY)
    monkeypatch.setattr(byname, "MODELS", str(tmp_path))
    return tmp_path


def ring(V):
    """Every vertex has in-neighbours v-1 and v+1."""
    indices = np.stack([(np.arange(V) - 1) % V, (np.arange(V) + 1) % V], 1)
    return reference.HostGraph(
        indptr=np.arange(0, 2 * V + 1, 2, dtype=np.int64),
        indices=indices.ravel().astype(np.int32), etypes=None,
        num_vertices=V, max_degree=2)


def test_a_dropped_in_module_is_found_by_name(toy_dir):
    assert run.gnn_config(CFG).num_heads == 3
    params = reference.init_params(0, CFG)
    assert [sorted(p) for p in params["layers"]] == [["v"], ["v"]]
    assert params["layers"][0]["v"].shape == (6, 3)
    assert params["layers"][1]["v"].shape == (4, 6)
    # layer 1 reads the features (no input gradient), layer 0 does not
    assert flops.step_flops(CFG, [2, 5, 9], [4, 11]) == (
        2 * (2 * 5 * 4 * 6) + 3 * (2 * 2 * 6 * 3))

    V = 16
    rng = np.random.default_rng(0)
    features = rng.standard_normal((V, 4)).astype(np.float32)
    labels = rng.integers(0, 3, V)
    out = reference.run(ring(V), features, labels, np.arange(V), seed=0,
                        cfg=CFG, mode="independent", num_pes=1, steps=2)
    assert len(out["losses"]) == 2 and all(np.isfinite(out["losses"]))
    moved = [np.abs(a - b).max() for a, b in zip(
        [p["v"] for p in out["p_end"]["layers"]],
        [p["v"] for p in out["p0"]["layers"]])]
    assert min(moved) > 0


@pytest.mark.parametrize("call", [
    lambda cfg: run.gnn_config(cfg),
    lambda cfg: reference.init_params(0, cfg),
    lambda cfg: flops.step_flops(cfg, [2, 5], [4]),
])
def test_a_model_without_a_module_names_the_file_to_add(call):
    cfg = dict(CFG, model="nosuch")
    with pytest.raises(LookupError, match="chipbench/models/nosuch.py"):
        call(cfg)
