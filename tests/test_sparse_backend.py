"""Sparse neighbor-list graph backend: the O(n·k) control plane must be
bit-identical to the dense O(n²) oracle wherever the construction is
RNG-free — graphs, walks, zone schedules (incl. pricing), fleet plans —
and individually deterministic where it is not (link-dropout sampling,
a documented RNG-stream break between backends).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import graph as G
from repro.core import markov
from repro.core.graph import (
    NeighborGraph,
    neighbor_graph_from_dense,
    pair_sq_dists,
    pairwise_sq_dists,
    patch_connected,
    patch_connected_lists,
    random_geometric_graph,
)
from repro.core.markov import RandomWalkServer
from repro.scenarios import (
    LinkConfig,
    LinkModel,
    MobilityConfig,
    Scenario,
    ScenarioConfig,
    get_scenario_config,
    range_graph,
    sparse_knn_graph,
    sparse_range_graph,
)


def _sparse_cfg(name: str, n: int, **kw) -> ScenarioConfig:
    return dataclasses.replace(get_scenario_config(name),
                               graph_backend="sparse", neighbor_k_max=n,
                               **kw)


def _check_invariants(g: NeighborGraph):
    """Packed-left, row-sorted, symmetric, self-loop-free."""
    deg = g.nbr_mask.sum(axis=1)
    adj = g.to_dense().adjacency
    assert not adj.diagonal().any()
    np.testing.assert_array_equal(adj, adj.T)
    for i in range(g.n):
        row = g.nbrs[i]
        d = int(deg[i])
        assert g.nbr_mask[i, :d].all() and not g.nbr_mask[i, d:].any()
        assert (np.diff(row[:d]) > 0).all()
        np.testing.assert_array_equal(
            g.nbr_d2[i, :d], pair_sq_dists(g.positions,
                                           np.full(d, i), row[:d]))


# ------------------------------------------------ distance formula pin --
def test_pair_formula_matches_matrix_formula():
    """The one distance expression: gathered pairs, the (n, n) matrix,
    and the (R, n, n) batch must produce identical floats — the
    foundation of every sparse≡dense pin below."""
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 1, (200, 2))
    d2 = pairwise_sq_dists(pos)
    i = rng.integers(0, 200, 5000)
    j = rng.integers(0, 200, 5000)
    keep = i != j
    np.testing.assert_array_equal(pair_sq_dists(pos, i[keep], j[keep]),
                                  d2[i[keep], j[keep]])
    np.testing.assert_array_equal(
        G.pairwise_sq_dists_batch(pos[None])[0], d2)


# ------------------------------------------------ graph construction ----
@pytest.mark.parametrize("seed", range(8))
def test_sparse_range_graph_matches_dense(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(10, 150))
    pos = rng.uniform(0, 1, (n, 2))
    radio = float(rng.uniform(0.08, 0.45))
    dense = range_graph(pos, radio, 5)
    sparse = sparse_range_graph(pos, radio, 5, k_max=n)
    np.testing.assert_array_equal(sparse.to_dense().adjacency,
                                  dense.adjacency)
    _check_invariants(sparse)


@pytest.mark.parametrize("seed", range(8))
def test_sparse_knn_graph_matches_dense(seed):
    """random_geometric_graph's body (kNN + patch) for given positions."""
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(8, 150))
    pos = rng.uniform(0, 1, (n, 2))
    d2 = pairwise_sq_dists(pos)
    adj = patch_connected(G.knn_adjacency(d2, 5), d2)
    sparse = sparse_knn_graph(pos, 5, k_max=n)
    np.testing.assert_array_equal(sparse.to_dense().adjacency, adj)
    _check_invariants(sparse)


def test_neighbor_graph_dense_roundtrip_and_accessors():
    g = random_geometric_graph(60, 5, np.random.default_rng(3))
    ng = neighbor_graph_from_dense(g)
    _check_invariants(ng)
    assert ng.n == g.n and ng.n_edges == g.n_edges
    assert ng.is_connected() == g.is_connected()
    np.testing.assert_array_equal(ng.degree(), g.degree())
    for i in (0, 17, 59):
        np.testing.assert_array_equal(ng.neighbors(i), g.neighbors(i))
        np.testing.assert_array_equal(ng.neighborhood(i),
                                      g.neighborhood(i))
    np.testing.assert_array_equal(ng.to_dense().adjacency, g.adjacency)


def test_connectivity_and_patch_match_dense():
    """BFS-on-lists + the cross-component patch replay the dense lane's
    exact edge insertions on a clustered (disconnected) layout."""
    rng = np.random.default_rng(7)
    pos = np.concatenate([rng.uniform(0.0, 0.25, (20, 2)),
                          rng.uniform(0.75, 1.0, (20, 2)),
                          rng.uniform([0.0, 0.75], [0.25, 1.0], (15, 2))])
    d2 = pairwise_sq_dists(pos)
    adj = G.knn_adjacency(d2, 3)
    rows, cols = np.nonzero(adj)
    ng = G.neighbor_graph_from_pairs(
        len(pos), rows, cols, pair_sq_dists(pos, rows, cols), pos)
    assert ng.is_connected() == G.adjacency_connected(adj)
    assert not ng.is_connected()
    patched = patch_connected(adj.copy(), d2)
    nbrs, mask, nd2 = patch_connected_lists(
        ng.nbrs.copy(), ng.nbr_mask.copy(), ng.nbr_d2.copy(), pos)
    out = NeighborGraph(nbrs=nbrs, nbr_mask=mask, positions=pos,
                        nbr_d2=nd2)
    np.testing.assert_array_equal(out.to_dense().adjacency, patched)
    _check_invariants(out)


def test_k_max_caps_knn_union_hubs():
    """The static_regen lane honors neighbor_k_max too: symmetrized-kNN
    hub nodes are truncated to their nearest links, the degree floor is
    re-patched, and the graph stays connected."""
    pos = np.random.default_rng(21).uniform(0, 1, (400, 2))
    capped = sparse_knn_graph(pos, 5, k_max=7)
    free = sparse_knn_graph(pos, 5, k_max=400)
    _check_invariants(capped)
    assert capped.is_connected()
    assert capped.degree().min() >= 5
    assert capped.degree().max() < free.degree().max()


def test_k_max_caps_degree_but_keeps_graph_usable():
    """A tight k_max truncates to each node's nearest in-range links;
    the result stays symmetric, connected, and above the degree floor
    (patches may locally exceed the cap — it is a soft cap)."""
    pos = np.random.default_rng(11).uniform(0, 1, (300, 2))
    g = sparse_range_graph(pos, 0.25, 5, k_max=8)
    _check_invariants(g)
    assert g.is_connected()
    deg = g.degree()
    assert deg.min() >= 5
    dense_deg = range_graph(pos, 0.25, 5).degree()
    assert deg.max() < dense_deg.max()          # the cap actually bit


# ------------------------------------------------ random walk parity ----
@pytest.mark.parametrize("transition", ["degree", "metropolis"])
def test_sparse_walk_replays_dense_walk(transition):
    """step() on neighbor lists consumes the walker RNG exactly like the
    dense Generator.choice path and visits the same clients."""
    g = random_geometric_graph(80, 5, np.random.default_rng(2))
    ng = neighbor_graph_from_dense(g)
    wd = RandomWalkServer(transition=transition, seed=5)
    ws = RandomWalkServer(transition=transition, seed=5)
    wd.reset(g, start=3)
    ws.reset(ng, start=3)
    for _ in range(200):
        assert wd.step(g) == ws.step(ng)
    np.testing.assert_array_equal(wd.visit_counts, ws.visit_counts)
    # streams still aligned after 200 steps
    assert wd._rng.random() == ws._rng.random()


@pytest.mark.parametrize("transition", ["degree", "metropolis"])
def test_sparse_batched_walk_replays_dense(transition):
    g = random_geometric_graph(50, 5, np.random.default_rng(4))
    ng = neighbor_graph_from_dense(g)
    wd = RandomWalkServer(transition=transition, seed=8)
    ws = RandomWalkServer(transition=transition, seed=8)
    wd.reset(g, start=0)
    ws.reset(ng, start=0)
    np.testing.assert_array_equal(
        wd.walk_schedule_batched([g] * 60, advance_first=True),
        ws.walk_schedule_batched([ng] * 60, advance_first=True))


def test_sparse_transition_row_matches_dense():
    g = random_geometric_graph(40, 5, np.random.default_rng(9))
    ng = neighbor_graph_from_dense(g)
    for transition in ("degree", "metropolis"):
        wd = RandomWalkServer(transition=transition)
        ws = RandomWalkServer(transition=transition)
        for i in (0, 13, 39):
            np.testing.assert_array_equal(ws.transition_row(ng, i),
                                          wd.transition_row(g, i))


def _biased_pair(policy, seed, n=60):
    """Dense/sparse walker twins for a biased policy on one graph."""
    g = random_geometric_graph(n, 5, np.random.default_rng(2))
    ng = neighbor_graph_from_dense(g)
    out = []
    for _ in range(2):
        w = RandomWalkServer(transition="metropolis", seed=seed,
                             policy=policy, bias_gamma=1.5)
        if policy == "label_skew":
            w.set_label_weights(
                np.random.default_rng(42).uniform(0.5, 3.0, n))
        out.append(w)
    return g, ng, out[0], out[1]


@pytest.mark.parametrize("policy", sorted(markov.BIASED_POLICIES))
def test_sparse_biased_walk_replays_dense(policy):
    """Biased-policy step() on neighbor lists: same visits, same
    importance weights (exact floats — the shared ``_biased_row``
    scatter), same RNG stream, matching the dense Generator.choice
    path."""
    g, ng, wd, ws = _biased_pair(policy, seed=5)
    wd.reset(g, start=3)
    ws.reset(ng, start=3)
    for _ in range(200):
        assert wd.step(g) == ws.step(ng)
    np.testing.assert_array_equal(wd.visit_counts, ws.visit_counts)
    np.testing.assert_array_equal(np.asarray(wd.weight_history),
                                  np.asarray(ws.weight_history))
    assert wd._rng.random() == ws._rng.random()


@pytest.mark.parametrize("policy", sorted(markov.BIASED_POLICIES))
def test_sparse_biased_batched_walk_replays_dense(policy):
    """walk_schedule_batched under biased policies: bit-for-bit visit
    and weight sequences across backends (the compressed sparse CDF
    shares the dense CDF's float levels)."""
    g, ng, wd, ws = _biased_pair(policy, seed=8, n=50)
    wd.reset(g, start=0)
    ws.reset(ng, start=0)
    np.testing.assert_array_equal(
        wd.walk_schedule_batched([g] * 60, advance_first=True),
        ws.walk_schedule_batched([ng] * 60, advance_first=True))
    np.testing.assert_array_equal(np.asarray(wd.weight_history),
                                  np.asarray(ws.weight_history))
    np.testing.assert_array_equal(wd.walk_weights(60), ws.walk_weights(60))


@pytest.mark.parametrize("policy", sorted(markov.BIASED_POLICIES))
def test_sparse_biased_transition_row_matches_dense(policy):
    """Row i of the biased MH chain is bit-identical across backends at
    every walker state, and matches the full-matrix construction."""
    g, ng, wd, ws = _biased_pair(policy, seed=3, n=40)
    wd.reset(g, start=0)
    ws.reset(ng, start=0)
    for step in range(30):
        p = markov.biased_transition_matrix(g, wd.policy_weights(g.n))
        for i in (0, 13, 39, wd.position):
            dense_row = wd.transition_row(g, i)
            np.testing.assert_array_equal(ws.transition_row(ng, i),
                                          dense_row)
            np.testing.assert_allclose(dense_row, p[i], atol=1e-15)
        assert wd.step(g) == ws.step(ng)


# ------------------------------------------------ scenario schedules ----
SCENARIOS_RNG_FREE = ["static_regen", "random_waypoint", "gauss_markov",
                      "duty_cycle"]


@pytest.mark.parametrize("scenario", SCENARIOS_RNG_FREE)
def test_zone_schedule_sparse_equals_dense(scenario):
    """The acceptance pin: graphs → avail traces → walks → zones → keys
    → latency/energy columns, identical across backends, across chunk
    boundaries. (Dropout scenarios are excluded: per-edge sampling is
    the documented RNG-stream break.)"""
    n, rounds = 26, 22

    def build(backend):
        cfg = dataclasses.replace(get_scenario_config(scenario),
                                  graph_backend=backend,
                                  neighbor_k_max=n)
        sc = Scenario(n, cfg, seed=3)
        w = RandomWalkServer(seed=7)
        w.reset(sc.current())
        rng = np.random.default_rng(11)

        def price(graphs, clients, idx, mask):
            return sc.price_schedule(graphs, clients, idx, mask, 4096)

        s1 = markov.zone_schedule(sc, w, rounds, 6, rng, price=price)
        s2 = markov.zone_schedule(sc, w, rounds, 6, rng,
                                  start_round=rounds, price=price)
        return s1, s2

    for a, b in zip(build("dense"), build("sparse")):
        np.testing.assert_array_equal(a.idx, b.idx)
        np.testing.assert_array_equal(a.mask, b.mask)
        np.testing.assert_array_equal(a.n_i, b.n_i)
        np.testing.assert_array_equal(a.clients, b.clients)
        np.testing.assert_array_equal(a.active, b.active)
        np.testing.assert_array_equal(a.keys, b.keys)
        np.testing.assert_array_equal(a.latency_s, b.latency_s)
        np.testing.assert_array_equal(a.energy_j, b.energy_j)


@pytest.mark.parametrize("mode", ["roundrobin", "simultaneous"])
def test_fleet_schedule_sparse_equals_dense(mode):
    n, rounds, k_walkers = 24, 18, 3

    def build(backend):
        cfg = _sparse_cfg("duty_cycle", n) if backend == "sparse" else \
            dataclasses.replace(get_scenario_config("duty_cycle"))
        sc = Scenario(n, cfg, seed=2)
        ws = [RandomWalkServer(seed=50 + 10 * k)
              for k in range(k_walkers)]
        for w in ws:
            w.reset(sc.current())
        rng = np.random.default_rng(0)
        return markov.fleet_zone_schedule(sc, ws, rounds, 5, rng,
                                          mode=mode, sync_every=6)

    a, b = build("dense"), build("sparse")
    np.testing.assert_array_equal(a.idx, b.idx)
    np.testing.assert_array_equal(a.mask, b.mask)
    np.testing.assert_array_equal(a.clients, b.clients)
    np.testing.assert_array_equal(a.keys, b.keys)
    np.testing.assert_array_equal(a.sync, b.sync)


def test_positions_only_identical_across_backends():
    """positions_only consumers (base-station baselines) never touch
    connectivity, so the backends are trivially interchangeable."""
    for name in ("random_waypoint", "gauss_markov"):
        sd = Scenario(20, dataclasses.replace(
            get_scenario_config(name)), seed=1, positions_only=True)
        ss = Scenario(20, _sparse_cfg(name, 20), seed=1,
                      positions_only=True)
        for _ in range(10):
            sd.step()
            ss.step()
        np.testing.assert_array_equal(sd.positions, ss.positions)


# ------------------------------------------------ link dropout lane -----
def test_sparse_dropout_deterministic_subset_connected():
    """The sparse dropout stream: same seed → same survivors; survivors
    ⊆ base edges ∪ patch links; every round connected; eager step and
    batched rollout replay each other draw-for-draw."""
    n = 30
    cfg = _sparse_cfg("lossy_links", n)

    def run(batched):
        sc = Scenario(n, cfg, seed=4)
        graphs = sc.schedule(12, include_current=True, batched=batched)
        return graphs

    g1, g2 = run(True), run(False)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(a.nbrs, b.nbrs)
        np.testing.assert_array_equal(a.nbr_mask, b.nbr_mask)
    base = Scenario(n, dataclasses.replace(cfg, links=LinkConfig()),
                    seed=4)
    base_graphs = base.schedule(12, include_current=True)
    for eff, mob in zip(g1, base_graphs):
        assert eff.is_connected()
        _check_invariants(eff)
        lost = mob.n_edges - eff.n_edges
        assert lost >= 0 or eff.n_edges - mob.n_edges <= n  # patch links


def test_sparse_dropout_respects_probabilities():
    """Statistically: far edges drop more often than near edges."""
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 1, (60, 2))
    g = sparse_range_graph(pos, 0.5, 5, k_max=60)
    link = LinkModel(LinkConfig(enabled=True, dropout=True))
    ei, ej, d2 = g.undirected_edges()
    near = d2 < np.median(d2)
    survived = np.zeros(len(ei))
    for t in range(60):
        eff = link._apply_dropouts_sparse(g, np.random.default_rng(t))
        dense = eff.to_dense().adjacency
        survived += dense[ei, ej]
    assert survived[near].mean() > survived[~near].mean()


# ------------------------------------------------ end-to-end trainer ----
def test_trainer_trajectory_identical_across_backends():
    """RWSADMMTrainer on a sparse gauss_markov scenario reproduces the
    dense trainer's compiled-scan trajectory bit-for-bit (no dropout)."""
    import jax

    from repro.data import make_image_dataset, pathological_split
    from repro.data.loader import build_federated
    from repro.fl.base import to_device_data
    from repro.fl.rwsadmm_trainer import RWSADMMTrainer
    from repro.models.small import get_model

    imgs, labels = make_image_dataset(400, seed=0)
    parts = pathological_split(labels, 12, seed=0)
    data = to_device_data(build_federated(imgs, labels, parts))
    model = get_model("mlr", (28, 28, 1))

    def run(backend):
        cfg = ScenarioConfig(
            name=f"t_{backend}",
            mobility=MobilityConfig(model="gauss_markov"),
            graph_backend=backend, neighbor_k_max=12)
        tr = RWSADMMTrainer(model, data, zone_size=4, batch_size=16,
                            solver="closed_form", scenario=cfg, seed=0)
        rng = np.random.default_rng(0)
        state = tr.init_state(jax.random.PRNGKey(0))
        sched = tr.schedule(10, rng)
        state, stacked = tr.run_chunk(state, sched, engine="scan")
        return np.asarray(stacked["train_loss"]), state

    losses_d, st_d = run("dense")
    losses_s, st_s = run("sparse")
    np.testing.assert_array_equal(losses_d, losses_s)
    import jax

    for a, b in zip(jax.tree_util.tree_leaves(st_d.clients),
                    jax.tree_util.tree_leaves(st_s.clients)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="graph_backend"):
        Scenario(10, dataclasses.replace(
            get_scenario_config("static_regen"), graph_backend="csr"))


def test_cell_list_guard_rejects_effectively_dense_search():
    """A radio range far too large for n must fail loudly, not OOM."""
    pos = np.random.default_rng(0).uniform(0, 1, (4000, 2))
    from repro.scenarios.mobility import _CellGrid

    with pytest.raises(ValueError, match="candidate pairs"):
        _CellGrid(pos, 0.9).candidate_pairs(max_pairs=100_000)


def test_range_pairs_guard_counts_the_full_search():
    """The half-neighborhood search keeps the guard's meaning: the
    directed candidates the 3×3 search would generate."""
    pos = np.random.default_rng(0).uniform(0, 1, (4000, 2))
    from repro.scenarios.mobility import _CellGrid

    with pytest.raises(ValueError, match="candidate pairs"):
        _CellGrid(pos, 0.9).range_pairs(0.81, max_pairs=100_000)
    grid = _CellGrid(pos, 0.05)
    full = len(grid.candidate_pairs()[0]) + len(pos)     # self pairs too
    grid.range_pairs(0.0025, max_pairs=full)
    with pytest.raises(ValueError, match="candidate pairs"):
        grid.range_pairs(0.0025, max_pairs=full - 1)


# ------------------------------ whole-array floor against the row loop --
def _oracle_cap_degree_pairs(n, pi, pj, d2, k_max):
    """The degree cap as the row-by-row construction had it: a full
    (i, j) lexsort first, then the (d2, i) ranking of every row."""
    order = np.lexsort((pj, pi))
    pi, pj, d2 = pi[order], pj[order], d2[order]
    deg = np.bincount(pi, minlength=n)
    if not len(pi) or deg.max() <= k_max:
        return pi, pj, d2
    by_dist = np.lexsort((d2, pi))
    rank = np.empty(len(pi), dtype=np.int64)
    rank[by_dist] = G.segmented_arange(deg)
    keep_dir = rank < k_max
    key = pi * n + pj
    ridx = np.searchsorted(key, pj * n + pi)
    keep = keep_dir & keep_dir[ridx]
    return pi[keep], pj[keep], d2[keep]


def _oracle_patch_min_degree_lists(nbrs, mask, nd2, pos, grid, k):
    """The min-degree floor row by row: each row below ``k`` gets its
    ring-searched k nearest inserted one edge at a time (writes into
    its inputs)."""
    if k <= 0:
        return nbrs, mask, nd2
    deg = mask.sum(axis=1)
    for i in np.flatnonzero(deg < k):
        for j in grid.exact_knn(int(i), k):
            e2 = float(pair_sq_dists(pos, np.asarray([i]),
                                     np.asarray([j]))[0])
            nbrs, mask, nd2 = G._insert_edge_lists(
                nbrs, mask, nd2, int(i), int(j), e2)
    return nbrs, mask, nd2


def _oracle_sparse_range_graph(pos, radio, min_degree, k_max):
    """(capped pairs, graph, deficient rows) of the row-by-row construction:
    the full 3×3 directed search, the oracle cap, one pack, the loop."""
    from repro.scenarios.mobility import _CellGrid

    n = len(pos)
    grid = _CellGrid(pos, radio)
    pi, pj = grid.candidate_pairs()
    d2 = pair_sq_dists(pos, pi, pj)
    keep = d2 <= radio * radio
    pairs = _oracle_cap_degree_pairs(n, pi[keep], pj[keep], d2[keep],
                                     k_max)
    g = G.neighbor_graph_from_pairs(n, *pairs, pos, assume_sorted=True)
    k = min(min_degree, n - 1)
    deficient = int((g.degree() < k).sum()) if k > 0 else 0
    nbrs, mask, nd2 = _oracle_patch_min_degree_lists(
        g.nbrs.copy(), g.nbr_mask.copy(), g.nbr_d2.copy(), pos, grid, k)
    nbrs, mask, nd2 = patch_connected_lists(nbrs, mask, nd2, pos)
    return pairs, NeighborGraph(nbrs=nbrs, nbr_mask=mask, positions=pos,
                                nbr_d2=nd2), deficient


def _gauss_markov_frames(n, degree, frames, seed):
    """(radio range for the expected degree, gauss-markov positions)."""
    from repro.scenarios.mobility import GaussMarkovMobility

    radio = float(np.sqrt(degree / (np.pi * n)))
    mob = GaussMarkovMobility(
        n, MobilityConfig(model="gauss_markov", radio_range=radio),
        backend="sparse")
    rng = np.random.default_rng(seed)
    mob.reset_positions(rng)
    return radio, [mob.step_positions(rng).copy() for _ in range(frames)]


def _floor_case(case):
    """(radio, frames, min_degree, k_max, compare with the dense lane)."""
    if case == "cell":           # the MLR cell's control plane
        return (*_gauss_markov_frames(10_000, 12, 3, seed=15), 5, 32,
                False)
    if case == "deficient":      # expected degree 3: most rows short
        return (*_gauss_markov_frames(3000, 3.0, 2, seed=16), 5, 32,
                False)
    if case == "cap":            # k_max 8 against expected degree 12
        return (*_gauss_markov_frames(2000, 12, 2, seed=17), 5, 8, False)
    if case == "corner":         # a bunch at a corner, rows on two edges
        rng = np.random.default_rng(18)
        edge = rng.uniform(0, 1, 300)
        pos = np.concatenate([
            rng.uniform(0.0, 0.03, (400, 2)),
            np.stack([edge, np.ones(300)], axis=1),
            np.stack([np.zeros(300), rng.uniform(0, 1, 300)], axis=1),
            rng.uniform(0, 1, (1300, 2))])
        return float(np.sqrt(12 / (np.pi * len(pos)))), [pos], 5, 32, \
            False
    seed = int(case.split("-")[1])   # small-<seed>: n 10–150
    rng = np.random.default_rng(300 + seed)
    n = int(rng.integers(10, 150))
    pos = rng.uniform(0, 1, (n, 2))
    radio = float(rng.uniform(0.04, 0.45))
    k_max = n if seed % 2 == 0 else int(rng.integers(2, 8))
    return radio, [pos], 5, k_max, k_max == n


FLOOR_CASES = ["cell", "deficient", "cap", "corner"] + [
    f"small-{s}" for s in range(8)]


@pytest.mark.parametrize("case", FLOOR_CASES)
def test_whole_array_floor_matches_row_loop(case):
    """The half-neighborhood search, the cap and the whole-array
    min-degree floor give the row-by-row construction's graph exactly: the
    same (i, j, d2) pairs, neighbor lists, distances and packed width,
    without writing into their inputs; the counters count the rows
    below the floor and the ring searches."""
    from repro.scenarios.mobility import (
        _CellGrid,
        _cap_degree_pairs,
        _floor_min_degree,
    )

    radio, frames, min_degree, k_max, vs_dense = _floor_case(case)
    ring = 0
    for pos in frames:
        n = len(pos)
        want_pairs, want, deficient = _oracle_sparse_range_graph(
            pos, radio, min_degree, k_max)
        grid = _CellGrid(pos, radio)
        pairs = _cap_degree_pairs(n, *grid.range_pairs(radio * radio),
                                  k_max)
        for a, b in zip(pairs, want_pairs):
            np.testing.assert_array_equal(a, b)
        kept = [a.copy() for a in pairs]
        _floor_min_degree(n, *pairs, pos, grid, min(min_degree, n - 1))
        for a, b in zip(pairs, kept):
            np.testing.assert_array_equal(a, b)
        counts = {}
        got = sparse_range_graph(pos, radio, min_degree, k_max, counts)
        for name in ("nbrs", "nbr_mask", "nbr_d2"):
            np.testing.assert_array_equal(getattr(got, name),
                                          getattr(want, name))
        assert counts["deficient"] == deficient
        assert 0 <= counts["ring_fallbacks"] <= deficient
        ring += counts["ring_fallbacks"]
        if n <= 2500:
            _check_invariants(got)
        if vs_dense:
            np.testing.assert_array_equal(
                got.to_dense().adjacency,
                range_graph(pos, radio, min_degree).adjacency)
    if case == "deficient":
        assert ring > 0


# sha256 of a 16-round schedule of the MLR cell's scenario at n = 2000
# (seed 2147483911), recorded with the row-by-row range-graph construction:
# every graph's neighbor lists, mask, distances and positions.
MLR_CELL_SCHEDULE_SHA256 = {
    True: "69a3c8bfb2f18c4133944678a6db96686d6db85260bb49909561f8bcd37e6dd1",
    False: "cc10aa15846e414b00ecac3240cad6859f9acec17305878b9c9cf9d334552c12",
}


@pytest.mark.parametrize("links", [True, False])
def test_mlr_cell_schedule_pinned(links):
    """The MLR cell's control plane (gauss-markov, sparse, k ≤ 32,
    chunks of 8, expected degree 12) rolls out the recorded graphs:
    what the walk and, with links on, the link layer are handed did
    not move."""
    import hashlib

    n = 2000
    cfg = ScenarioConfig(
        name="mlr_pin",
        mobility=MobilityConfig(model="gauss_markov",
                                radio_range=float(np.sqrt(12 / (np.pi * n)))),
        links=LinkConfig(enabled=links, dropout=links),
        graph_backend="sparse", neighbor_k_max=32, rollout_chunk=8)
    h = hashlib.sha256()
    for g in Scenario(n, cfg, seed=2147483911).schedule(16):
        for a in (g.nbrs, g.nbr_mask, g.nbr_d2, g.positions):
            h.update(str((a.shape, a.dtype.str)).encode())
            h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == MLR_CELL_SCHEDULE_SHA256[links]


@pytest.mark.parametrize("seed", [109, 597, 747])
def test_floor_width_follows_the_ring_order(monkeypatch, seed):
    """Where a row's own insertions can fill it, the order of its k
    nearest decides the packed width the row loop reaches: with the
    ring search handing its neighbors farthest first (layouts where
    that order changes the width), the whole-array floor still packs
    the loop's width."""
    from repro.scenarios.mobility import _CellGrid

    exact_knn = _CellGrid.exact_knn
    monkeypatch.setattr(_CellGrid, "exact_knn",
                        lambda self, i, k: exact_knn(self, i, k)[::-1])
    rng = np.random.default_rng(seed)
    n = int(rng.integers(10, 60))
    pos = rng.uniform(0, 1, (n, 2))
    radio = float(np.sqrt(rng.uniform(1.0, 6) / (np.pi * n)))
    k_max = int(rng.integers(3, 8))
    _, want, _ = _oracle_sparse_range_graph(pos, radio, 5, k_max)
    got = sparse_range_graph(pos, radio, 5, k_max)
    for name in ("nbrs", "nbr_mask", "nbr_d2"):
        np.testing.assert_array_equal(getattr(got, name),
                                      getattr(want, name))
