"""End-to-end behaviour tests for the whole system (paper claims level).

These validate the three paper headlines on offline data:
  1. RWSADMM converges fast and reaches high personalized accuracy under
     pathological non-IID (Fig. 2 / Table 1 directionally),
  2. it beats the non-personalized benchmark (FedAvg) decisively,
  3. its per-round communication is O(1) in the client count (§4).
Plus: hypothesis property tests on system invariants.
"""
import jax
import numpy as np
import pytest

from _hypothesis_compat import hypothesis, st

from repro.baselines import FedAvgTrainer
from repro.core.rwsadmm import RWSADMMHparams
from repro.data import make_image_dataset, pathological_split
from repro.data.loader import build_federated
from repro.fl.base import to_device_data
from repro.fl.rwsadmm_trainer import RWSADMMTrainer
from repro.fl.simulation import run_simulation
from repro.models.small import get_model


@pytest.fixture(scope="module")
def fed_setup():
    imgs, labels = make_image_dataset(1500, seed=0)
    parts = pathological_split(labels, 10, seed=0)
    data = to_device_data(build_federated(imgs, labels, parts))
    model = get_model("mlr", (28, 28, 1))
    return data, model


def test_rwsadmm_beats_fedavg_under_non_iid(fed_setup):
    data, model = fed_setup
    rw = RWSADMMTrainer(
        model, data, RWSADMMHparams(beta=1.0, kappa=0.001, epsilon=1e-5),
        zone_size=6, batch_size=32)
    fa = FedAvgTrainer(model, data, clients_per_round=5)
    res_rw = run_simulation(rw, rounds=100, eval_every=100, seed=0)
    res_fa = run_simulation(fa, rounds=100, eval_every=100, seed=0)
    assert res_rw.final["acc_personalized"] > res_fa.final["acc_global"]
    assert res_rw.final["acc_personalized"] > 0.8


def test_comm_per_round_independent_of_n():
    accounts = []
    for n in (10, 40):
        imgs, labels = make_image_dataset(600, seed=1)
        parts = pathological_split(labels, n, seed=1)
        data = to_device_data(build_federated(imgs, labels, parts))
        model = get_model("mlr", (28, 28, 1))
        tr = RWSADMMTrainer(model, data, RWSADMMHparams(beta=1.0),
                            zone_size=4)
        accounts.append(tr.comm_bytes_per_round(4))
    assert accounts[0] == accounts[1]  # O(1): same zone ⇒ same bytes


def test_server_token_is_deployable_checkpoint(fed_setup, tmp_path):
    """The y token round-trips through the checkpoint layer and evaluates
    identically — the 'tactical vehicle hands the model over' path."""
    from repro.checkpoint import load_pytree, save_pytree

    data, model = fed_setup
    tr = RWSADMMTrainer(model, data, RWSADMMHparams(beta=1.0),
                        zone_size=4, batch_size=32)
    rng = np.random.default_rng(0)
    state = tr.init_state(jax.random.PRNGKey(0))
    for r in range(30):
        state, _ = tr.round(state, r, rng)
    path = str(tmp_path / "ckpt_30.npz")
    save_pytree(path, state.server.y)
    restored = load_pytree(path, state.server.y)
    cols = (data.x_test, data.y_test, data.mask_test)
    a1, _ = tr.eval_rows_shared(state.server.y, *cols)
    a2, _ = tr.eval_rows_shared(restored, *cols)
    np.testing.assert_allclose(a1, a2)


# ------------------------------------------------------ hypothesis --------
@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(
    n=st.integers(min_value=4, max_value=40),
    deg=st.integers(min_value=2, max_value=6),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_property_graph_always_valid(n, deg, seed):
    """Invariant: any generated client graph is connected, symmetric, and
    meets the min-degree requirement (Assumption 3.1 needs irreducible)."""
    from repro.core.graph import random_geometric_graph

    g = random_geometric_graph(n, min_degree=deg,
                               rng=np.random.default_rng(seed))
    assert g.is_connected()
    assert (g.adjacency == g.adjacency.T).all()
    assert (g.degree() >= min(deg, n - 1)).all()


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(
    n_clients=st.integers(min_value=2, max_value=12),
    lpc=st.integers(min_value=1, max_value=3),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_property_partition_label_budget(n_clients, lpc, seed):
    """Invariant: pathological split never exceeds labels_per_client."""
    from repro.data import pathological_split

    labels = np.random.default_rng(seed).integers(0, 10, 400).astype(
        np.int32)
    parts = pathological_split(labels, n_clients, labels_per_client=lpc,
                               seed=seed)
    for idx in parts:
        assert len(set(labels[idx].tolist())) <= lpc
        assert len(idx) > 0


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(
    beta=st.floats(min_value=0.5, max_value=50.0),
    kappa=st.floats(min_value=0.0, max_value=0.5),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_property_zone_round_preserves_finiteness(beta, kappa, seed):
    """Invariant: one zone round maps finite states to finite states for
    any admissible hyperparameters."""
    from repro.core import rwsadmm, tree

    hp = RWSADMMHparams(beta=beta, kappa=kappa, epsilon=1e-5)
    key = jax.random.PRNGKey(seed)
    template = {"w": jax.random.normal(key, (16,))}
    client, server = rwsadmm.init_states(template, hp, n_clients=3)
    grads = jax.tree_util.tree_map(
        lambda l: jax.random.normal(key, l.shape), client.x)
    new_clients, y = rwsadmm.zone_round(client, server.y, grads, hp,
                                        kappa, n_total=5)
    assert not bool(tree.any_nan(new_clients.x))
    assert not bool(tree.any_nan(y))
