"""GAT: the program's layer and train step against the plain reference
(``repro.models.gnn.gat_ref``), on seeded random weights at a small size.

Tolerance: ``TOL``, relative to the largest entry of the reference.  The
program reorders the same float32 arithmetic (each slot's scores as
``x_j · (W att)`` from the rows it gathers, the reduce before the
projection), which moves
results by about 1e-7 of their scale (1e-6 for gradients through three
layers); the same layer computed in bfloat16 moves them by about 5e-3,
which ``test_bfloat16_fails_the_tolerance`` holds to.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import INVALID
from repro.data.synthetic import SyntheticGraphDataset, rmat_graph
from repro.models.gnn import GNNConfig, gat_ref, init_gnn
from repro.models.gnn.layers import layer_apply as _layer_apply
from repro.train import loop
from test_tracing import step_recorder

layer_apply = jax.jit(_layer_apply, static_argnums=(1, 2))

TOL = 2e-5
BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chipbench")


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def layer_case(l: int, k: int, dtype=jnp.float32, seed: int = 0):
    """Parameters of plan layer ``l`` reading width ``k`` (4 heads, hidden
    32, 5 classes), perturbed so no bias is zero, and a random block of
    12 destinations over 40 source rows with 6 slots each, some masked,
    some -1, and destination 3 with no neighbour at all."""
    rng = np.random.default_rng(seed)
    cfg = (GNNConfig(model="gat", num_layers=2, in_dim=k, hidden_dim=32,
                     num_classes=5) if l == 1 else
           GNNConfig(model="gat", num_layers=2, in_dim=3, hidden_dim=k,
                     num_classes=5))
    p = init_gnn(jax.random.PRNGKey(seed), cfg)["layers"][l]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(
        rng.standard_normal(a.shape), a.dtype), p)
    n_src, n, w = 40, 12, 6
    x = jnp.asarray(rng.standard_normal((n_src, k)), jnp.float32)
    self_idx = jnp.asarray(rng.choice(n_src, n, replace=False), jnp.int32)
    nbr = np.where(rng.random((n, w)) < 0.2, -1, rng.integers(0, n_src, (n, w)))
    mask = (rng.random((n, w)) < 0.6) & (nbr >= 0)
    mask[3] = False
    cfg = dataclasses.replace(cfg, dtype=dtype)
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    return cfg, p, x, self_idx, jnp.asarray(nbr, jnp.int32), jnp.asarray(mask)


def reference_layer(p, l, x, self_idx, nbr, mask):
    rows, cols = np.nonzero(np.asarray(mask))
    with jax.default_matmul_precision("highest"):
        return jax.jit(gat_ref.layer, static_argnums=5)(
            p, x, self_idx, jnp.asarray(np.asarray(nbr)[rows, cols]),
            jnp.asarray(rows, jnp.int32), l == 0)


# (plan layer, input width): hidden layers have H·C = 32 and the output
# layer H·C = 4 × 5 = 20, so widths 8 and 16 are narrower than the heads
# and 32 and 64 as wide or wider
FORMS = [(1, 8), (1, 32), (0, 16), (0, 64)]
FORM_IDS = ["hidden-narrow-input", "hidden-wide-input", "output-narrow-input",
            "output-wide-input"]


@pytest.mark.parametrize("l,k", FORMS, ids=FORM_IDS)
def test_layer_matches_reference(l, k):
    cfg, p, x, self_idx, nbr, mask = layer_case(l, k)
    out = layer_apply(p, cfg, l, x, self_idx, nbr, mask, None)
    assert out.shape == (12, 5 if l == 0 else 32)
    assert rel_err(out, reference_layer(p, l, x, self_idx, nbr, mask)) <= TOL


@pytest.mark.parametrize("l,k", FORMS, ids=FORM_IDS)
def test_layer_gradients_match_reference(l, k):
    cfg, p, x, self_idx, nbr, mask = layer_case(l, k, seed=1)
    rows, cols = np.nonzero(np.asarray(mask))
    src, dst = jnp.asarray(np.asarray(nbr)[rows, cols]), jnp.asarray(rows, jnp.int32)

    def prog(p, x):
        return jnp.sum(jnp.sin(layer_apply(p, cfg, l, x, self_idx, nbr, mask, None)))

    def ref(p, x):
        return jnp.sum(jnp.sin(gat_ref.layer(p, x, self_idx, src, dst, l == 0)))

    got = jax.jit(jax.grad(prog, (0, 1)))(p, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(ref, (0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel_err(a, b) <= TOL


@pytest.mark.parametrize("l", [0, 1])
def test_a_destination_with_no_neighbour_attends_to_itself_alone(l):
    cfg, p, x, self_idx, nbr, mask = layer_case(l, 8)
    out = layer_apply(p, cfg, l, x, self_idx, nbr, mask, None)[3]
    xi = x[self_idx[3]]
    H, C = p["att_src"].shape
    z = (xi @ p["w"]).reshape(H, C)          # weight 1 on its own row
    o = z.mean(0) if l == 0 else z.reshape(-1)
    want = o + p["b"] + xi @ p["w_skip"] + p["b_skip"]
    want = want if l == 0 else jax.nn.elu(want)
    assert rel_err(out, want) <= TOL


@pytest.mark.parametrize("l,k", FORMS[:2])
def test_bfloat16_fails_the_tolerance(l, k):
    """The control: the program's own bfloat16 path misses ``TOL``."""
    _, p, x, self_idx, nbr, mask = layer_case(l, k)
    cfg, pb, *_ = layer_case(l, k, dtype=jnp.bfloat16)
    out = layer_apply(pb, cfg, l, x.astype(jnp.bfloat16), self_idx, nbr, mask, None)
    assert rel_err(out.astype(jnp.float32),
                   reference_layer(p, l, x, self_idx, nbr, mask)) > 20 * TOL


def scatter_add_operands(closed_jaxpr) -> list[tuple[int, ...]]:
    """Shapes of the operands of every ``scatter-add`` in a jaxpr, nested
    calls included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scatter-add":
                found.append(tuple(eqn.invars[0].aval.shape))
            for v in eqn.params.values():
                if hasattr(v, "eqns"):
                    walk(v)
                elif hasattr(getattr(v, "jaxpr", None), "eqns"):
                    walk(v.jaxpr)

    walk(closed_jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("l,x_differentiated", [(1, False), (1, True), (0, True)],
                         ids=["input-layer", "hidden-layer", "output-layer"])
def test_score_gradient_needs_no_scatter(l, x_differentiated):
    """The backward pass scatters no score gradient to the source rows: each
    slot's source score comes from the row it gathers for the reduce, so
    only the row gather's own scatter-add into ``(n_src, k)`` is left, and
    none where ``x`` is the undifferentiated input."""
    # plan layer 1 of ``layer_case`` reads the input features; with ``x``
    # differentiated it is a hidden layer of a deeper model
    cfg, p, x, self_idx, nbr, mask = layer_case(l, 8 if l else 16)
    H = p["att_src"].shape[0]

    def f(p, x):
        return jnp.sum(_layer_apply(p, cfg, l, x, self_idx, nbr, mask, None))

    argnums = (0, 1) if x_differentiated else 0
    found = scatter_add_operands(jax.make_jaxpr(jax.grad(f, argnums))(p, x))
    assert (x.shape[0], H) not in found
    if x_differentiated:
        assert found and set(found) == {x.shape}
    else:
        assert found == []


# --------------------------------------------------------------------------
# One train_gnn step against the reference over the plan the step built
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dataset():
    g = rmat_graph(scale=9, edge_factor=8, max_degree=32, seed=3)
    return SyntheticGraphDataset(g, feature_dim=8, num_classes=5, seed=3)


def reference_plan(plan, num_layers: int):
    """The program's stacked plan (independent or cooperative, one row
    per PE) as the reference's plan over global vertex ids: the sorted
    frontiers ``S^0 .. S^L`` and each layer's ``self_idx``, ``src`` and
    ``dst`` as rows of them."""
    def ids(a):
        a = np.asarray(a).ravel()
        return np.unique(a[a != INVALID])

    front = [ids(ly.seeds) for ly in plan.layers] + [ids(plan.input_ids)]
    layers = []
    for l, ly in enumerate(plan.layers):
        if hasattr(ly, "tilde_ids"):        # cooperative: rows of S~^{l+1}
            rows_of = np.asarray(ly.tilde_ids)
        else:
            rows_of = np.asarray(plan.layers[l + 1].seeds if l + 1 < num_layers
                                 else plan.input_ids)
        seeds, nbr = np.asarray(ly.seeds), np.asarray(ly.nbr_idx)
        mask, self_idx = np.asarray(ly.mask), np.asarray(ly.self_idx)
        dst, src = [], []
        for p in range(seeds.shape[0]):
            ok = seeds[p] != INVALID
            assert (rows_of[p][self_idx[p][ok]] == seeds[p][ok]).all()
            r, c = np.nonzero(mask[p] & ok[:, None])
            dst.append(seeds[p][r])
            src.append(rows_of[p][nbr[p][r, c]])
        layers.append({
            "self_idx": jnp.asarray(np.searchsorted(front[l + 1], front[l]), jnp.int32),
            "src": jnp.asarray(np.searchsorted(front[l + 1], np.concatenate(src)), jnp.int32),
            "dst": jnp.asarray(np.searchsorted(front[l], np.concatenate(dst)), jnp.int32),
        })
    return front, layers


@pytest.mark.parametrize("mode,pes", [("independent", 1), ("cooperative", 4)])
def test_train_step_matches_reference(dataset, mode, pes):
    """The loss and the gradient of ``train_gnn``'s first step (read from
    Adam's first moment, ``(1 - 0.9) g``, which the benchmark's
    ``StepRecorder`` keeps) against the reference over the
    same sampled plan; three layers, so inputs both narrower than the
    heads (the 8-wide features against 4 × 4, the 16-wide hidden rows
    against 4 heads of 5 classes) and as wide (the hidden layer's 16) run."""
    from repro.engine import MinibatchEngine

    cfg = GNNConfig(model="gat", num_layers=3, in_dim=8, hidden_dim=16,
                    num_classes=5, num_heads=4)
    tc = loop.TrainConfig(mode=mode, num_pes=pes, local_batch=8, num_steps=1,
                          fanout=4, eval_every=0)
    with step_recorder() as rec:
        res = loop.train_gnn(dataset, cfg, tc)
    grads = jax.tree.map(lambda m: np.asarray(m) / np.float32(0.1), rec.opt1.mu)

    eng = MinibatchEngine.from_config(dataset.graph, tc.engine_config(3),
                                      dataset=dataset)
    front, layers = reference_plan(eng.plan_at(0), 3)
    assert len(front[0]) == 8 * pes and all(len(ly["src"]) for ly in layers)
    x = jnp.asarray(dataset.features)[front[3]]
    labels = jnp.asarray(dataset.labels)[front[0]]
    loss, want = jax.jit(gat_ref.loss_and_grad)(rec.p0, x, layers, labels)
    assert res.losses[0] == pytest.approx(float(loss), rel=TOL)
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, g in flat:
        assert rel_err(got[path], g) <= TOL, jax.tree_util.keystr(path)


def test_init_matches_the_benchmark_module(monkeypatch):
    """``init_gnn`` and the benchmark's ``chipbench/models/gat.py`` draw the
    same leaves, under the same names, from the same subkeys."""
    monkeypatch.syspath_prepend(BENCH)
    import reference

    cfg = {"model": "gat", "num_layers": 3, "feature_dim": 10, "hidden_dim": 24,
           "num_classes": 7, "num_heads": 4}
    want = reference.init_params(11, cfg)
    got = init_gnn(jax.random.PRNGKey(11), GNNConfig(
        model="gat", num_layers=3, in_dim=10, hidden_dim=24, num_classes=7,
        num_heads=4))
    flat_w = jax.tree_util.tree_flatten_with_path(want)[0]
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    assert [k for k, _ in flat_g] == [k for k, _ in flat_w]
    for (k, a), (_, b) in zip(flat_g, flat_w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(k))
    assert got["layers"][0]["att_src"].shape == (4, 7)
    assert got["layers"][1]["att_src"].shape == (4, 6)
