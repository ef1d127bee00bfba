"""The compiled round names its parts for a device trace: the chunk of
the dense scan, the simultaneous fleet and the fused Pallas path
(interpret mode here) carries ``rwsadmm.grad``, ``rwsadmm.zone_update``
and ``rwsadmm.scatter`` in the op_name metadata of its instructions."""
import os
import re

import jax
import numpy as np
import pytest

from repro.core.rwsadmm import RWSADMMHparams
from repro.data import make_image_dataset, pathological_split
from repro.data.loader import build_federated
from repro.fl.base import to_device_data
from repro.fl.fleet_trainer import FleetRWSADMMTrainer
from repro.fl.rwsadmm_trainer import RWSADMMTrainer
from repro.models.small import get_model

SCOPES = ("rwsadmm.grad", "rwsadmm.zone_update", "rwsadmm.scatter")


@pytest.fixture(scope="module")
def fed():
    imgs, labels = make_image_dataset(200, seed=0)
    parts = pathological_split(labels, 8, seed=0)
    return (to_device_data(build_federated(imgs, labels, parts)),
            get_model("mlr", (28, 28, 1)))


@pytest.mark.parametrize("path", ["dense", "fleet_simultaneous",
                                  "scan_fused"])
def test_chunk_hlo_carries_the_round_scopes(fed, path):
    data, model = fed
    kw = dict(zone_size=4, batch_size=8, solver="closed_form", seed=0)
    if path == "fleet_simultaneous":
        tr = FleetRWSADMMTrainer(model, data, RWSADMMHparams(beta=10.0),
                                 n_walkers=3, fleet_mode="simultaneous",
                                 **kw)
    else:
        tr = RWSADMMTrainer(model, data, RWSADMMHparams(beta=10.0), **kw)
    engine = "scan_fused" if path == "scan_fused" else "scan"
    state = tr.init_state(jax.random.PRNGKey(0))
    sched = tr.schedule(3, np.random.default_rng(0))
    with tr.capture_jitted() as entries:
        tr.run_chunk(state, sched, engine=engine)
    (fn, args), = [(f, a) for name, f, a, _ in entries
                   if name.startswith("chunk")]
    op_names = set(re.findall(r'op_name="([^"]*)"',
                              fn.lower(*args).compile().as_text()))
    for scope in SCOPES:
        pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
        assert any(pat.search(o) for o in op_names), (scope, path)


def test_compile_cache_keys_executables_by_their_metadata(monkeypatch,
                                                           tmp_path):
    """An executable cached from other code with the same HLO (the round
    without its scopes) must not be loaded in place of this code's: the
    entry points key the persistent cache by metadata, with source paths
    taken relative to the checkout so that a moved checkout still hits."""
    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    names = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    old = {n: getattr(jax.config, n) for n in names}
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        pat = jax.config.jax_hlo_source_file_canonicalization_regex
        src = os.path.join(compile_cache.CHECKOUT, "src", "repro", "x.py")
        assert re.sub(pat, "", src) == os.path.join("src", "repro", "x.py")
    finally:
        for n, v in old.items():
            jax.config.update(n, v)
