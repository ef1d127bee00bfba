"""Model kinds as plug-ins of the harness.

* A new kind, its configuration, traffic and limits are added as files
  alone: a root of the test's own holds a copy of ``BENCHMARK.json``
  with one more configuration and cell, their files, and
  ``chipbench/kinds/mlp.py`` (``kind_mlp.py``). The harness runs that
  cell on the CPU at the kind's ``shrink`` size: a sound run is correct,
  a state left unchanged and half minibatches are not, with and without
  a frozen tree beside the planes.
* The CNN and the MLR kinds make the same weights, data columns and
  reference outputs, bit for bit, as the harness did before kinds were
  modules. The sha256 digests in ``PINS`` were recorded at each cell's
  ``shrink`` size on seed ``SEED``, from the parent commit's harness
  (a53241a, where ``test_chipbench_faults.tiny`` made the sizes), by
  ``digest`` of: ``data.init_weights(PRNGKey(s_w),
  data.model_key(model))``; ``data.stacked_images`` or
  ``data.stacked_synthetic`` (config ``data``, ``n_clients``,
  ``s_data``), where ``s_data, s_w = data.sub_seeds(SEED, 4)[:2]``; and
  the ``losses``, ``evals``, ``norms`` and ``first_grad`` of
  ``Run.reference()`` after ``Run.warm_up()`` and ``free_program()``.
"""
import copy
import hashlib
import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

from chipbench import data, harness
from chipbench.tests.test_chipbench_faults import (  # noqa: F401
    SEED, no_cache, plant, tiny)

HERE = os.path.dirname(os.path.abspath(__file__))

PINS = {
    "cnn_n100_walk1": {
        "weights": "3dc4dfff843417ece44296e2270445fd"
                   "58bdf96fbd20183f7582a036136fa8f3",
        "columns": "e1b175b6a6c249c0ef07161093a4036c"
                   "2d8fc52b703b27e845210552eec5d8ca",
        "outputs": "6b45915a81087707cd4b24bc0437912f"
                   "e426ba616720fc72465d0fbeb2376cbb"},
    "mlr_n1e4_gm": {
        "weights": "46cb74c6e69261552c0c1c889fe925d8"
                   "a2eab0710da68749631036dc32aac955",
        "columns": "5eb998825c086bb8be1b92c90c107c41"
                   "0f91ce0b0d955478db7915224c7981e5",
        "outputs": "bd3287b6e238591d21a034d4d5f23d00"
                   "e9d2567f8e2e283803947a6d2dea720b"},
}


def digest(obj) -> str:
    """sha256 of a tree of dicts, lists, floats and arrays: keys in
    sorted order, floats by ``float.hex``, arrays by dtype, shape and
    bytes."""
    h = hashlib.sha256()

    def feed(o):
        if isinstance(o, dict):
            h.update(b"{%d" % len(o))
            for k in sorted(o):
                h.update(repr(k).encode())
                feed(o[k])
        elif isinstance(o, (list, tuple)):
            h.update(b"[%d" % len(o))
            for v in o:
                feed(v)
        elif isinstance(o, float):
            h.update(float(o).hex().encode())
        else:
            a = np.asarray(o)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(np.ascontiguousarray(a).tobytes())

    feed(obj)
    return h.hexdigest()


@pytest.mark.parametrize("reference_device", [None, "accelerator"])
@pytest.mark.parametrize("name", sorted(PINS))
def test_kind_is_bit_identical_to_the_parent(name, reference_device,
                                             no_cache):
    """On the CPU ``accelerator`` is the CPU too: the same outputs."""
    cell = tiny(name)
    if reference_device:
        cell.config["reference_device"] = reference_device
    cfg = cell.config
    s_data, s_w = data.sub_seeds(SEED, 4)[:2]
    trainable, frozen = cell.kind.weights(jax.random.PRNGKey(s_w),
                                          cfg["model"])
    assert frozen is None
    cols = cell.kind.columns(cfg, int(cfg["n_clients"]), s_data)
    run = harness.Run(cell, SEED)
    run.warm_up()
    run.free_program()
    ref = run.reference()
    got = {"weights": digest(trainable), "columns": digest(cols),
           "outputs": digest({"losses": ref.losses, "evals": ref.evals,
                              "norms": ref.norms,
                              "first_grad": ref.first_grad})}
    assert got == PINS[name]


def test_unknown_reference_device_is_refused():
    cell = tiny("mlr_n1e4_gm")
    cell.config["reference_device"] = "gpu"
    with pytest.raises(ValueError, match="reference_device"):
        harness.build(cell, SEED)


# ------------------------------------------------- a kind from files --
MLP_CELL = "mlp_gauss_walk1"
MLP_CONFIG = {
    "name": "mlp_gauss",
    "model": {"kind": "mlp", "features": 60, "hidden": 100,
              "n_classes": 10},
    "data": {"train_rows": 24, "test_rows": 8, "shift": 0.5},
    "n_clients": 1000,
    "plane": "dense",
    "solver": {"kind": "closed_form", "engine": "scan", "beta": 10.0,
               "kappa": 0.001, "kappa_decay": 0.99, "epsilon": 1e-05,
               "batch_size": 20},
}
MLP_TRAFFIC = {"walkers": 1, "zone_size": 8, "min_degree": 5,
               "regen_every": 10, "eval_every": 4, "prefetch": False}
MLP_LIMITS = {"loss_gap": {"limit": 1e-4}, "norm_gap": {"limit": 1e-3},
              "eval_gap": {"limit": 1e-4}, "zone_faults": {"limit": 0}}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def mlp_root(tmp_path, frozen: list) -> str:
    """A root holding the benchmark plus one configuration and cell of a
    kind that ``chipbench/`` does not have."""
    root = str(tmp_path)
    bench = copy.deepcopy(harness.load_bench())
    bench["configs"].append({
        "name": MLP_CONFIG["name"],
        "source": "https://arxiv.org/abs/2304.12534",
        "file": "chipbench/configs/mlp_gauss.json", "reduced": [],
        "why": "the paper MLP on Gaussian clients"})
    bench["workloads"].append({
        "name": MLP_CELL, "config": MLP_CONFIG["name"],
        "traffic": "mlp_walk1", "chips": 1,
        "why": "one walker over the MLP's dense plane"})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    cfg = copy.deepcopy(MLP_CONFIG)
    cfg["model"]["frozen"] = frozen
    _write(os.path.join(root, "chipbench", "configs", "mlp_gauss.json"), cfg)
    _write(os.path.join(root, "chipbench", "traffic", "mlp_walk1.json"),
           MLP_TRAFFIC)
    _write(os.path.join(root, "chipbench", "limits", MLP_CELL + ".json"),
           MLP_LIMITS)
    os.makedirs(os.path.join(root, "chipbench", "kinds"))
    shutil.copy(os.path.join(HERE, "kind_mlp.py"),
                os.path.join(root, "chipbench", "kinds", "mlp.py"))
    return root


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
@pytest.mark.parametrize("frozen", [[], ["fc1"]], ids=["all", "fc1_frozen"])
def test_new_kind_from_files_alone(frozen, fault, tmp_path, no_cache,
                                   monkeypatch):
    root = mlp_root(tmp_path, frozen)
    cell = harness.resolve(harness.load_bench(root), MLP_CELL, root)
    assert cell.kind.__file__.startswith(root)
    cell.config, cell.traffic = cell.kind.shrink(cell.config, cell.traffic)
    trainable, held = cell.kind.weights(jax.random.PRNGKey(0),
                                        cell.config["model"])
    assert set(trainable) | set(held or {}) == {"fc1", "fc2", "out"}
    assert set(held or {}) == set(frozen)
    plant(fault, monkeypatch)
    out = harness.run_cell(cell, SEED, 0.5, False,
                           t_start=time.perf_counter())
    assert set(out["checks"]) == set(MLP_LIMITS)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0
