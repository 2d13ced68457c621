"""Pieces of the benchmark found by name: each is a file of its own,
loaded by its path, so that adding one edits no other file.

* a metric's reader: ``chipbench/metrics/<metric>.py`` (``run.read_metric``);
* a model: ``chipbench/models/<model>.py``, where ``<model>`` is the
  configuration's ``"model"``.  It holds everything that differs from
  model to model, and imports nothing of the program:

  - ``program_args(cfg) -> dict``: the program's ``GNNConfig`` fields
    beyond those ``run.gnn_config`` sets for every model;
  - ``init_layer(ks, d_in, d_out, cfg) -> dict``: one layer's reference
    parameters from the five subkeys ``reference.init_params`` splits
    for it;
  - ``layer(p, h, L, is_out, dtype, prec, cfg)``: one reference layer,
    activation included, over the padded edge list of
    ``reference.pad_plan``;
  - ``step_flops(sizes, edges, cfg) -> float``: forward and backward
    FLOPs of a step under ``flops.py``'s rules.
"""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
MODELS = os.path.join(HERE, "models")


def load(path: str, label: str):
    """The module in the file ``path``, executed afresh."""
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model(name: str):
    """The module of model ``name``; a model with no module is an error
    that names the file to add."""
    path = os.path.join(MODELS, name + ".py")
    if not os.path.isfile(path):
        raise LookupError(
            f"no model {name!r} in the benchmark: add chipbench/models/{name}.py "
            f"with program_args, init_layer, layer and step_flops")
    return load(path, "chipbench_model_" + name.replace("-", "_"))
