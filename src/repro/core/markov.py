"""Markov-chain machinery for the mobile server's random walk.

Implements the paper's §3:
  * transition matrix  [P(k)]_{ij} = 1/deg(i) for j ~ i  (experiments §5),
  * Metropolis-Hastings variant (uniform stationary distribution π = 1/n,
    which makes Assumption 3.1's π_* as large as possible — used when a
    uniform client-visit frequency is desired),
  * stationary distribution π, spectral quantities σ(P), λ₂(P),
  * mixing time τ(δ) from Eq. (6),
  * P_max elementwise envelope (Eq. (5)) for the dynamic chain,
  * random-walk sampling of the visited-client sequence (i_k),
  * importance-biased walk policies (staleness / label-skew targets with
    the Walk-for-Learning importance-weight correction — see
    ``docs/walks.md``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import weakref
from typing import Any, Sequence

import numpy as np

from . import graph as graph_mod
from .graph import ClientGraph, NeighborGraph


def degree_transition_matrix(graph: ClientGraph) -> np.ndarray:
    """[P]_{ij} = 1/deg(i) for j in N(i)\\{i}; the paper's experimental
    choice. Stationary distribution is π_i ∝ deg(i)."""
    adj = graph.adjacency.astype(np.float64)
    deg = adj.sum(axis=1, keepdims=True)
    return adj / np.maximum(deg, 1.0)


def metropolis_transition_matrix(graph: ClientGraph) -> np.ndarray:
    """Metropolis-Hastings weights: uniform stationary distribution.

    P_ij = min(1/deg(i), 1/deg(j)) for j~i; self-loop absorbs the rest.

    Vectorized: one (n, n) elementwise min instead of a Python double
    loop (this runs at every regeneration epoch, and every round under
    link-dropout scenarios). Pinned against the loop form in
    ``tests/test_graph_markov.py``.
    """
    adj = graph.adjacency.astype(np.float64)
    deg = adj.sum(axis=1)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    p = adj * np.minimum(inv[:, None], inv[None, :])
    # The rounded min(1/deg_i, 1/deg_j) terms can sum a hair above 1
    # even though the exact sum never does; a −2⁻⁵² self-loop would
    # poison rng.choice mid-walk, so clamp (mirrored in _sparse_row
    # and the biased builders so all row constructions stay
    # bit-identical).
    np.fill_diagonal(p, np.maximum(1.0 - p.sum(axis=1), 0.0))
    return p


def biased_transition_matrix(graph: ClientGraph,
                             weights: np.ndarray) -> np.ndarray:
    """Metropolis-Hastings chain targeting π ∝ ``weights``.

    P_ij = min(1/deg(i), w_j / (w_i · deg(j))) for j ~ i; the self-loop
    absorbs the rest. With w ≡ 1 this is *float-identical* to
    :func:`metropolis_transition_matrix` (min(1/deg_i, 1/deg_j)).
    Detailed balance: w_i·P_ij = min(w_i/deg_i, w_j/deg_j) = w_j·P_ji,
    so the stationary distribution is exactly w/Σw on any connected
    graph — the lever the biased walk policies (staleness, label-skew)
    pull to steer visit frequencies, with the induced sampling bias
    undone by the 1/(n·π_i) importance weights (``docs/walks.md``).
    """
    adj = graph.adjacency.astype(np.float64)
    deg = adj.sum(axis=1)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    w = np.asarray(weights, np.float64)
    p = adj * np.minimum(inv[:, None], (w[None, :] * inv[None, :])
                         / w[:, None])
    # The rounded w_j/(w_i·deg_j) terms can sum a hair above 1 even
    # though the exact sum never does; a −2⁻⁵² self-loop would poison
    # rng.choice, so clamp (mirrored bit-for-bit in _biased_row).
    np.fill_diagonal(p, np.maximum(1.0 - p.sum(axis=1), 0.0))
    return p


def stationary_distribution(p: np.ndarray, tol: float = 1e-12) -> np.ndarray:
    """π with πᵀP = πᵀ, via power iteration on Pᵀ."""
    n = p.shape[0]
    pi = np.full(n, 1.0 / n)
    for _ in range(100_000):
        nxt = pi @ p
        if np.abs(nxt - pi).max() < tol:
            pi = nxt
            break
        pi = nxt
    return pi / pi.sum()


def sigma(p: np.ndarray) -> float:
    """σ(P) := sup { ||fᵀP|| / ||f|| : fᵀ1 = 0 }  (paper Eq. 6).

    Equals the largest singular value of Pᵀ restricted to 1⊥.
    """
    n = p.shape[0]
    # Orthonormal basis of 1-perp via QR of [1 | I].
    q, _ = np.linalg.qr(np.concatenate([np.ones((n, 1)) / math.sqrt(n),
                                        np.eye(n)[:, : n - 1]], axis=1))
    basis = q[:, 1:]  # (n, n-1), orthonormal, ⊥ 1
    m = basis.T @ p @ p.T @ basis
    ev = np.linalg.eigvalsh(m)
    return float(np.sqrt(max(ev.max(), 0.0)))


def lambda2(p: np.ndarray) -> float:
    """Second-largest eigenvalue modulus (reversible-chain rate, Eq. 30)."""
    ev = np.linalg.eigvals(p)
    ev = np.sort(np.abs(ev))[::-1]
    return float(ev[1]) if len(ev) > 1 else 0.0


def mixing_time(p: np.ndarray, delta: float = 0.5,
                pi: np.ndarray | None = None) -> int:
    """τ(δ) = ceil( ln(√2/(δ π_*)) / (1 − σ(P)) )   (paper Eq. 6)."""
    if pi is None:
        pi = stationary_distribution(p)
    pi_star = float(pi.min())
    s = sigma(p)
    if s >= 1.0 - 1e-12:
        return 2**31 - 1  # non-ergodic chain: infinite mixing time
    return int(math.ceil(math.log(math.sqrt(2.0) / (delta * pi_star))
                         / (1.0 - s)))


def p_max_envelope(ps: list[np.ndarray]) -> np.ndarray:
    """Eq. (5): elementwise max over the dynamic chain's matrices P(k)."""
    env = ps[0].copy()
    for p in ps[1:]:
        np.maximum(env, p, out=env)
    return env


def verify_assumption_3_1(p: np.ndarray, delta: float = 0.5) -> dict:
    """Empirically verify the mixing inequality Eq. (3)/(4) for τ(δ)."""
    pi = stationary_distribution(p)
    tau = mixing_time(p, delta, pi)
    if tau >= 2**30:  # non-ergodic (e.g. periodic bipartite chain)
        return {"tau": tau, "holds": False, "max_dev": float("inf"),
                "pi_star": float(pi.min()), "sigma": sigma(p),
                "lambda2": lambda2(p)}
    pt = np.linalg.matrix_power(p, tau)
    dev = np.abs(pt - pi[None, :]).max()
    return {
        "tau": tau,
        "pi_star": float(pi.min()),
        "sigma": sigma(p),
        "lambda2": lambda2(p),
        "max_dev": float(dev),
        "holds": bool(dev <= delta * pi.min() + 1e-9),
    }


# Walk-policy axis: which stationary distribution the walk targets.
# "degree"/"metropolis" are the uniform (unbiased) chains the paper uses;
# "staleness"/"label_skew" are importance-biased MH chains (π ∝ w) whose
# sampling bias the per-visit importance weights undo (docs/walks.md).
WALK_POLICIES = ("degree", "metropolis", "staleness", "label_skew")
BIASED_POLICIES = frozenset({"staleness", "label_skew"})


@dataclasses.dataclass
class RandomWalkServer:
    """The mobile server: walks the client graph per the Markov chain.

    Host-side control plane; the visited sequence (i_k) drives which zone
    the compiled SPMD round operates on.

    ``policy`` picks the chain the walk runs (defaults to ``transition``):

    * ``"degree"`` / ``"metropolis"`` — the unbiased chains (π ∝ deg,
      π uniform); importance weights are identically 1.0.
    * ``"staleness"`` — MH chain targeting π ∝ (1 + steps-since-visit)^γ
      (γ = ``bias_gamma``): under-visited clients attract the walk.
    * ``"label_skew"`` — MH chain targeting the fixed per-client data
      utilities installed via :meth:`set_label_weights` (from
      ``data.partition.label_skew_weights``): clients holding rare
      labels attract the walk.

    Every visit records an importance weight ``(Σw)/(n·w_i)`` (≡ 1/(n·π_i)
    normalized so uniform policies give 1.0) in ``weight_history``,
    aligned 1:1 with ``history`` — the Walk-for-Learning correction the
    trainers fold into the Eq. 31 y-update to keep the stochastic
    estimator unbiased under a biased visit distribution.
    """

    transition: str = "degree"  # "degree" (paper) | "metropolis"
    seed: int = 0
    policy: str | None = None   # defaults to ``transition``
    bias_gamma: float = 1.0     # staleness exponent γ

    def __post_init__(self):
        if self.policy is None:
            self.policy = self.transition
        elif self.policy in ("degree", "metropolis"):
            # A uniform policy IS a transition kind; keep them in sync so
            # matrix()/transition_row() dispatch stays single-sourced.
            self.transition = self.policy
        if self.policy not in WALK_POLICIES:
            raise ValueError(f"unknown walk policy {self.policy!r}; "
                             f"pick one of {WALK_POLICIES}")
        self._rng = np.random.default_rng(self.seed)
        self.position: int | None = None
        self.visit_counts: np.ndarray | None = None
        self.history: list[int] = []
        self.weight_history: list[float] = []
        self.label_weights: np.ndarray | None = None
        self._last_visit: np.ndarray | None = None
        self._n_seen = 0
        self._cover_step: int | None = None
        self._matrix_cache: tuple[Any, np.ndarray] | None = None

    # -- policy weights ---------------------------------------------------
    @property
    def is_biased(self) -> bool:
        return self.policy in BIASED_POLICIES

    def set_label_weights(self, weights: np.ndarray | None) -> None:
        """Install per-client utilities for the ``label_skew`` policy
        (normalized to mean 1 — importance weights are scale-invariant,
        this just keeps the floats well-conditioned)."""
        if weights is None:
            self.label_weights = None
            return
        w = np.asarray(weights, np.float64)
        if (w <= 0).any():
            raise ValueError("label weights must be strictly positive")
        self.label_weights = w / w.mean()

    def policy_weights(self, n: int) -> np.ndarray:
        """(n,) current target weights w (π ∝ w). Uniform policies → 1s.
        Deterministic in walker state, so row construction and the
        importance-weight record read identical floats."""
        if self.policy == "staleness":
            assert self._last_visit is not None, "call reset() first"
            k = len(self.history) - 1
            s = (k - self._last_visit).astype(np.float64)  # never seen → k+1
            return (1.0 + s) ** self.bias_gamma
        if self.policy == "label_skew" and self.label_weights is not None:
            if len(self.label_weights) != n:
                raise ValueError(
                    f"label weights have length {len(self.label_weights)}, "
                    f"graph has {n} clients")
            return self.label_weights
        return np.ones(n)

    def stationary_target(self, n: int) -> np.ndarray:
        """The designed stationary distribution π = w/Σw at the current
        walker state (uniform policies: exactly 1/n; the degree chain's
        deg-proportional π comes from ``stationary_distribution`` of the
        matrix instead — its target is implicit in the graph)."""
        w = self.policy_weights(n)
        return w / w.sum()

    def matrix(self, graph: ClientGraph) -> np.ndarray:
        # The graph object only changes at regeneration epochs (every
        # ``regen_every`` rounds), but step() runs every round — cache
        # the O(n²) transition matrix per graph instance (weakref so a
        # recycled id can never alias a dead graph). Biased policies are
        # never cached: their weights move with walker state (staleness)
        # or with set_label_weights, so a cached P could silently stale.
        if self.is_biased:
            g = (graph.to_dense() if isinstance(graph, NeighborGraph)
                 else graph)
            return biased_transition_matrix(g, self.policy_weights(graph.n))
        if self._matrix_cache is not None \
                and self._matrix_cache[0]() is graph:
            return self._matrix_cache[1]
        # Diagnostics-only densification for sparse graphs: the walking
        # hot paths (step / walk_schedule*) never come through here for
        # a NeighborGraph — they sample O(deg) rows directly.
        g = graph.to_dense() if isinstance(graph, NeighborGraph) else graph
        if self.transition == "degree":
            p = degree_transition_matrix(g)
        elif self.transition == "metropolis":
            p = metropolis_transition_matrix(g)
        else:
            raise ValueError(f"unknown transition kind {self.transition!r}")
        self._matrix_cache = (weakref.ref(graph), p)
        return p

    def reset(self, graph: ClientGraph, start: int | None = None) -> int:
        self.visit_counts = np.zeros(graph.n, dtype=np.int64)
        self.history = []
        self.weight_history = []
        self._last_visit = np.full(graph.n, -1, dtype=np.int64)
        self._n_seen = 0
        self._cover_step = None
        self.position = (int(self._rng.integers(graph.n))
                         if start is None else int(start))
        self._record_visit(self.position, graph.n, initial=True)
        return self.position

    def _record_visit(self, i: int, n: int, *, initial: bool = False) -> None:
        """Shared visit bookkeeping for reset/step/batched-step: counts,
        history, the importance weight of THIS visit (from the weight
        vector the step was drawn under — before the visit mutates it),
        the staleness clock, and the incremental first-full-coverage
        step that makes :meth:`hitting_time` O(1)."""
        if initial or not self.is_biased:
            iw = 1.0   # start position / unbiased chain: no correction
        else:
            w = self.policy_weights(n)
            iw = float(w.sum() / (n * w[i]))
        if self.visit_counts[i] == 0:
            self._n_seen += 1
            if self._n_seen == n and self._cover_step is None:
                self._cover_step = len(self.history)
        self.visit_counts[i] += 1
        self.history.append(i)
        self.weight_history.append(iw)
        self._last_visit[i] = len(self.history) - 1

    def transition_row(self, graph: ClientGraph, i: int) -> np.ndarray:
        """Row i of P(k) — all one walk step needs. A cached full matrix
        is reused when present (static graphs between regens); otherwise
        the degree chain builds just the O(n) row, so link-dropout
        scenarios (a fresh surviving graph every round) skip the O(n²)
        full-matrix rebuild per round. The row values are bit-identical
        to the matrix row (0/1 sums are exact, one division either way).
        Metropolis rows need every node's degree, so that chain still
        goes through the cached matrix. Biased policies always build the
        row fresh (their weights move with walker state) through the
        backend-shared scatter in :meth:`_biased_row`, so dense and
        sparse backends read bit-identical rows."""
        if self.is_biased:
            _, row = self._biased_row(graph, i)
            return row
        if self._matrix_cache is not None \
                and self._matrix_cache[0]() is graph:
            return self._matrix_cache[1][i]
        if isinstance(graph, NeighborGraph):
            cands, probs = self._sparse_row(graph, i)
            row = np.zeros(graph.n)
            row[cands] = probs
            return row
        if self.transition == "degree":
            row = graph.adjacency[i].astype(np.float64)
            return row / max(row.sum(), 1.0)
        return self.matrix(graph)[i]

    def _biased_row(self, graph: ClientGraph, i: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """(candidates, full row) of the biased MH chain at node i —
        ONE construction for both graph backends. Only the neighbor /
        degree gather differs per backend (identical integers either
        way); every float op afterwards is shared, so dense and sparse
        rows are bit-identical by construction, and both match the
        elementwise expression in :func:`biased_transition_matrix`
        (same multiply/divide order, same length-n pairwise sum for
        the self-loop mass)."""
        w = self.policy_weights(graph.n)
        if isinstance(graph, NeighborGraph):
            nbrs = graph.neighbors(i)
            deg_nb = graph.nbr_mask[nbrs].sum(axis=1).astype(np.float64)
        else:
            nbrs = np.flatnonzero(graph.adjacency[i])
            nbrs = nbrs[nbrs != i]
            deg_nb = graph.adjacency[nbrs].astype(np.float64).sum(axis=1)
        deg_i = np.float64(len(nbrs))
        inv_i = np.where(deg_i > 0, 1.0 / np.maximum(deg_i, 1.0), 0.0)
        inv_nb = np.where(deg_nb > 0, 1.0 / np.maximum(deg_nb, 1.0), 0.0)
        row = np.zeros(graph.n)
        row[nbrs] = np.minimum(inv_i, (w[nbrs] * inv_nb) / w[i])
        # Same float-error clamp as biased_transition_matrix: rounding
        # in the off-diagonal terms can push their sum past 1.
        row[i] = max(1.0 - row.sum(), 0.0)
        cands = np.insert(nbrs, np.searchsorted(nbrs, i), i)
        return cands, row

    def _sparse_row(self, graph: NeighborGraph, i: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """(candidates, probs): the nonzero support of row i of P(k), in
        ascending client order, for a neighbor-list graph — O(deg) for
        the degree chain instead of the dense row's O(n).

        The floats match the dense row exactly: the degree chain divides
        by the same degree, and the Metropolis self-loop scatters the
        neighbor masses into a length-n row first so ``1 − row.sum()``
        reduces with the same pairwise summation the dense matrix row
        uses. Together with the choice emulation in :meth:`step` this
        makes sparse walks replay dense walks draw-for-draw (pinned in
        ``tests/test_sparse_backend.py``).
        """
        if self.is_biased:
            cands, row = self._biased_row(graph, i)
            return cands, row[cands]
        if self.transition == "degree":
            nbrs = graph.neighbors(i)
            return nbrs, np.full(len(nbrs), 1.0) / max(float(len(nbrs)),
                                                       1.0)
        if self.transition != "metropolis":
            raise ValueError(f"unknown transition kind {self.transition!r}")
        nbrs = graph.neighbors(i)
        # Only deg(i) and deg(j) for j ~ i are needed — O(deg²) worst
        # case, not the full (n, k_cap) mask reduction. The values are
        # integer-valued float64 divisions, so they equal the dense
        # matrix's elementwise 1/deg floats exactly.
        deg_i = np.float64(len(nbrs))
        deg_nb = graph.nbr_mask[nbrs].sum(axis=1).astype(np.float64)
        inv_i = np.where(deg_i > 0, 1.0 / np.maximum(deg_i, 1.0), 0.0)
        inv_nb = np.where(deg_nb > 0, 1.0 / np.maximum(deg_nb, 1.0), 0.0)
        # Scatter into a length-n row so the self-loop mass reduces
        # with the same pairwise summation the dense matrix row uses.
        row = np.zeros(graph.n)
        row[nbrs] = np.minimum(inv_i, inv_nb)
        # Same float-error clamp as metropolis_transition_matrix.
        row[i] = max(1.0 - row.sum(), 0.0)
        cands = np.insert(nbrs, np.searchsorted(nbrs, i), i)
        return cands, row[cands]

    def _sample_sparse(self, graph: NeighborGraph, u: float) -> int:
        """Map one uniform through row ``position``'s CDF exactly as
        ``Generator.choice(n, p=row)`` does on the dense row (cumsum,
        normalize, searchsorted-right): the zero-mass entries of the
        dense row never move the CDF's float levels, so the compressed
        search lands on the same client for the same uniform."""
        cands, probs = self._sparse_row(graph, self.position)
        cdf = probs.cumsum()
        cdf /= cdf[-1]
        j = int(np.searchsorted(cdf, u, side="right"))
        return int(cands[min(j, len(cands) - 1)])

    def step(self, graph: ClientGraph) -> int:
        """One random-walk move: i_{k+1} ~ [P(k)]_{i_k, ·} (Eq. 2)."""
        assert self.position is not None, "call reset() first"
        if isinstance(graph, NeighborGraph):
            self.position = self._sample_sparse(graph, self._rng.random())
        else:
            row = self.transition_row(graph, self.position)
            # The dynamic graph may have disconnected the current node
            # from its old neighbors; row always sums to 1 on the
            # *current* graph.
            self.position = int(self._rng.choice(graph.n, p=row))
        self._record_visit(self.position, graph.n)
        return self.position

    def hitting_time(self) -> int | None:
        """T = max_i T_i once every client has been visited (paper §4).
        O(1): the first-full-coverage step is tracked incrementally by
        ``_record_visit`` instead of rescanning the visit history on
        every call (regression-pinned against the oracle scan)."""
        if self.visit_counts is None:
            return None
        return self._cover_step

    def walk_schedule(self, graphs: Sequence[ClientGraph],
                      *, advance_first: bool = True) -> np.ndarray:
        """Batch variant of :meth:`step`: the visited sequence (i_k) over a
        precomputed graph schedule (one graph per round).

        Consumes the walk RNG exactly as per-round ``step()`` calls would,
        so eager and compiled-schedule drivers visit identical clients.
        ``advance_first=False`` keeps the first entry at the current
        position (the round-0 convention: the server starts *at* a client
        before its first move).
        """
        positions = np.empty(len(graphs), dtype=np.int64)
        for k, graph in enumerate(graphs):
            if k == 0 and not advance_first:
                assert self.position is not None, "call reset() first"
                positions[k] = self.position
            else:
                positions[k] = self.step(graph)
        return positions

    def walk_schedule_batched(self, graphs: Sequence[ClientGraph],
                              *, advance_first: bool = True) -> np.ndarray:
        """Inverse-CDF variant of :meth:`walk_schedule`: all step uniforms
        are pre-drawn in ONE ``rng.random`` call and each step maps its
        uniform through the transition row's CDF — O(1) RNG dispatches
        per window instead of one ``Generator.choice`` (which rebuilds a
        CDF and re-enters the generator) per round.

        RNG-STREAM BREAK: raw uniforms consume the walker's bit stream
        differently from ``choice``, so a run mixing this with eager
        ``step()`` calls diverges. It therefore ships opt-in (the
        trainers' ``batched_walk`` flag); the stream it does produce is
        deterministic, chunk-composable (``random(a)`` then ``random(b)``
        equals ``random(a+b)`` for PCG64), and pinned by a seed-stability
        test so it can never drift silently.
        """
        rounds = len(graphs)
        positions = np.empty(rounds, dtype=np.int64)
        start = 0
        if rounds and not advance_first:
            assert self.position is not None, "call reset() first"
            positions[0] = self.position
            start = 1
        u = self._rng.random(rounds - start)
        for k in range(start, rounds):
            assert self.position is not None, "call reset() first"
            if isinstance(graphs[k], NeighborGraph):
                cands, row = self._sparse_row(graphs[k], self.position)
            else:
                cands = None
                row = self.transition_row(graphs[k], self.position)
            cdf = np.cumsum(row)
            # Scale by the realized total (≈1.0) so fp undershoot in the
            # cumsum can never push the draw past the last bin.
            j = int(np.searchsorted(cdf, u[k - start] * cdf[-1],
                                    side="right"))
            # A uniform within 1 ulp of 1.0 can land past the last
            # positive-mass bin (trailing zero-probability states share
            # cdf[-1]); clamp to the first bin reaching the total — the
            # last state the row actually supports. The sparse lane's
            # compressed CDF shares the dense CDF's float levels, so
            # the clamp index maps to the same client.
            j = min(j, int(np.searchsorted(cdf, cdf[-1], side="left")))
            self.position = int(cands[j]) if cands is not None else j
            self._record_visit(self.position, graphs[k].n)
            positions[k] = self.position
        return positions

    def walk_weights(self, rounds: int) -> np.ndarray | None:
        """(R,) importance weights of the walker's last ``rounds``
        visits (the schedule column the trainers consume), or ``None``
        for unbiased policies — the engines then skip the correction
        entirely, keeping the uniform-policy computation graphs (and
        their bit-identical pins) untouched."""
        if not self.is_biased:
            return None
        if rounds == 0:
            return np.zeros(0, np.float64)
        assert rounds <= len(self.weight_history)
        return np.asarray(self.weight_history[-rounds:], np.float64)


# ---------------------------------------------------------------------------
# Precomputed zone schedules — the host-side half of the compiled
# multi-round (lax.scan) driver. Everything data-dependent that the random
# walk decides (which client, which zone members, which PRNG key) is
# resolved here into fixed-shape arrays; the device then runs R rounds as
# one XLA executable with no host round-trips.
# ---------------------------------------------------------------------------


def round_key_seed(rng: np.random.Generator) -> int:
    """Draw one round's PRNG-key seed from the shared simulation RNG.

    The single choke point for per-round key derivation: the eager
    drivers (single-walker, fleet) and the schedule precompute all draw
    through here, so their key streams are identical *by construction* —
    the eager/scan equivalence pins are structural, not incidental.
    """
    return int(rng.integers(2**31 - 1))


def round_key(rng: np.random.Generator):
    """Eager-driver form: materialize the round's key on device."""
    import jax

    return jax.random.PRNGKey(round_key_seed(rng))


def round_keys(seeds: np.ndarray) -> np.ndarray:
    """Schedule form: one batched dispatch for a whole window's key block
    (threefry init is jit-traced, so vmap over seeds matches per-seed
    ``PRNGKey`` bit-for-bit)."""
    import jax

    return np.asarray(jax.vmap(jax.random.PRNGKey)(np.asarray(seeds)))


@dataclasses.dataclass(frozen=True)
class ZoneSchedule:
    """R precomputed zone rounds as fixed-shape host arrays.

    idx:     (R, Z) int32 — active-client ids, padded with 0.
    mask:    (R, Z) float32 — 1 for live slots, 0 for padding.
    n_i:     (R,) float32 — |N(i_k)| zone sizes (pre-subsampling).
    keys:    (R, 2) uint32 — per-round PRNG keys (minibatch sampling).
    clients: (R,) int32 — the visited client i_k per round.
    active:  (R,) int32 — number of live slots per round (≤ Z).

    When the schedule is built from a scenario with a wireless comm
    model (``scenarios/``), two extra host-side columns price each
    round; they never enter the compiled scan (control-plane only):

    latency_s: (R,) float64 — expected round latency, or None.
    energy_j:  (R,) float64 — expected round radio energy, or None.

    Under a biased walk policy (``RandomWalkServer.policy`` in
    ``BIASED_POLICIES``) one more per-round column rides along, consumed
    by BOTH engines' Eq. 31 y-update (the Walk-for-Learning correction):

    iw: (R,) float64 — importance weight 1/(n·π_{i_k}) of the visited
        client, or None for unbiased policies (engines skip the
        correction entirely — the uniform computation graph, and its
        bit-identical eager ≡ scan pins, stay untouched).
    """

    idx: np.ndarray
    mask: np.ndarray
    n_i: np.ndarray
    keys: np.ndarray
    clients: np.ndarray
    active: np.ndarray
    latency_s: np.ndarray | None = None
    energy_j: np.ndarray | None = None
    iw: np.ndarray | None = None

    @property
    def rounds(self) -> int:
        return int(self.idx.shape[0])

    @property
    def zone_size(self) -> int:
        return int(self.idx.shape[1])


def plan_zone_round(
    graph: ClientGraph,
    i_k: int,
    zone_size: int,
    rng: np.random.Generator,
    avail: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, int]:
    """Form the active zone S(i_k) ⊆ N(i_k) for one round (Eq. 31 subset).

    Returns (idx (Z,), mask (Z,), n_i). Zones larger than ``zone_size``
    are subsampled: i_k plus random neighbors, drawn from ``rng`` — the
    single host RNG shared with per-round key generation, so schedule
    precomputation replays the eager driver's draw sequence exactly.

    ``avail`` is an optional (n,) bool client-availability mask (churn /
    duty-cycling, ``scenarios/``): offline neighbors are dropped from the
    zone before subsampling. The visited client i_k always participates —
    the server is physically at its location. ``avail=None`` (the default)
    consumes ``rng`` identically to the pre-scenario code path.
    """
    zone = graph.neighborhood(i_k)
    if avail is not None:
        zone = zone[avail[zone] | (zone == i_k)]
    n_i = len(zone)
    if n_i > zone_size:
        others = zone[zone != i_k]
        pick = rng.choice(others, size=zone_size - 1, replace=False)
        active = np.concatenate([[i_k], pick])
    else:
        active = zone
    mask = np.zeros(zone_size, np.float32)
    mask[: len(active)] = 1.0
    idx = np.zeros(zone_size, np.int32)
    idx[: len(active)] = active
    return idx, mask, n_i


def _plan_rounds(graphs, positions, zone_size, rng, avails):
    """The shared per-round planning loop: zone membership + key seeds.

    Inherently sequential in ``rng`` (subsample draws and key seeds
    interleave in round order, replaying the eager drivers exactly), so
    it stays a host loop; everything around it — walk stepping, key
    materialization, pricing — is batched by the callers.
    """
    rounds = len(graphs)
    z = zone_size
    idx = np.zeros((rounds, z), np.int32)
    mask = np.zeros((rounds, z), np.float32)
    n_i = np.zeros((rounds,), np.float32)
    seeds = np.zeros((rounds,), np.int64)
    active = np.zeros((rounds,), np.int32)
    for k in range(rounds):
        idx[k], mask[k], n_i[k] = plan_zone_round(
            graphs[k], int(positions[k]), z, rng,
            avail=None if avails is None else avails[k],
        )
        active[k] = int(mask[k].sum())
        seeds[k] = round_key_seed(rng)
    return idx, mask, n_i, seeds, active


def _no_phase(name: str, **meta):
    """The default ``phase`` of the schedule functions: times nothing."""
    return contextlib.nullcontext()


def zone_schedule(
    dyn_graph,
    walker: RandomWalkServer,
    rounds: int,
    zone_size: int,
    rng: np.random.Generator,
    *,
    start_round: int = 0,
    price=None,
    batched_walk: bool = False,
    phase=_no_phase,
) -> ZoneSchedule:
    """Precompute ``rounds`` zone rounds: graphs (covering regeneration
    epochs), random-walk positions, padded zone membership, and PRNG keys.

    Advances ``dyn_graph``, ``walker``, and ``rng`` exactly as the same
    number of eager per-round calls would, so chunked schedules compose:
    ``zone_schedule(..., R1) + zone_schedule(..., R2, start_round=R1)``
    reproduces one eager run of R1+R2 rounds draw-for-draw.

    ``dyn_graph`` is either a plain ``graph.DynamicGraph`` or a
    ``scenarios.Scenario``. A scenario additionally yields per-round
    client-availability masks (churn) via ``pop_avail_trace()``, which
    feed zone planning, and — when ``price`` is given — per-round
    latency/energy columns. ``price(graphs, clients, idx, mask) ->
    ((R,), (R,))`` prices the whole window in one vectorized call and
    must be deterministic (no RNG) so eager and scan engines price
    identically.

    ``batched_walk=True`` swaps the per-round ``rng.choice`` walk step
    for the pre-drawn-uniform inverse-CDF sampler
    (:meth:`RandomWalkServer.walk_schedule_batched`) — an RNG-stream
    break from the eager driver, hence opt-in.

    ``phase(name, **meta)`` returns a context manager that times one
    step (``walk``; ``zones``: zone planning and the round keys;
    ``price``): a telemetry phase span from the trainer, or nothing by
    default.
    """
    first = start_round == 0
    graphs = dyn_graph.schedule(rounds, include_current=first)
    pop_trace = getattr(dyn_graph, "pop_avail_trace", None)
    avails = pop_trace() if pop_trace is not None else None
    step = (walker.walk_schedule_batched if batched_walk
            else walker.walk_schedule)
    with phase("walk", rounds=rounds):
        positions = step(graphs, advance_first=not first)
        # The last `rounds` recorded weights align with `positions` in
        # both advance_first regimes: with the round-0 convention the
        # window's first entry is the walker's current position, whose
        # weight was recorded when it was visited (1.0 at reset).
        iw = walker.walk_weights(rounds)

    with phase("zones", rounds=rounds):
        idx, mask, n_i, seeds, active = _plan_rounds(
            graphs, positions, zone_size, rng, avails)
        keys = round_keys(seeds)
    latency = energy = None
    if price is not None:
        with phase("price", rounds=rounds):
            latency, energy = price(graphs, positions, idx, mask)
    return ZoneSchedule(
        idx=idx, mask=mask, n_i=n_i, keys=keys,
        clients=positions.astype(np.int32), active=active,
        latency_s=latency, energy_j=energy, iw=iw,
    )


# ---------------------------------------------------------------------------
# Fleet schedules — K mobile servers compiled into one scan window.
# Round-robin mode serves one walker's zone per round (the walkers take
# turns; one wall step moves every walker once per K rounds); simultaneous
# mode moves ALL K walkers every wall step and serves K zones at once.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FleetZoneSchedule(ZoneSchedule):
    """R precomputed fleet rounds (see :class:`ZoneSchedule`).

    Round-robin mode keeps the base-class shapes and adds:

    walker: (R,) int32 — the active walker per round.
    sync:   (R,) float32 — 1.0 where a rendezvous (token averaging)
            follows the round, 0.0 otherwise.

    Under biased walk policies the base class's ``iw`` column is (R,)
    in round-robin mode (the active walker's importance weight) and
    (R, K) in simultaneous mode (one weight per walker's zone).

    Simultaneous mode gains a walker axis: idx/mask are (R, K, Z),
    clients/n_i/active are (R, K), and the latency/energy columns keep
    their (R,) wall-clock aggregates (parallel service: latency is the
    max over walkers, energy the sum) with the per-walker (R, K) columns
    preserved in ``latency_s_walkers``/``energy_j_walkers``.
    """

    walker: np.ndarray | None = None
    sync: np.ndarray | None = None
    latency_s_walkers: np.ndarray | None = None
    energy_j_walkers: np.ndarray | None = None
    mode: str = "roundrobin"
    n_walkers: int = 1

    @property
    def zone_size(self) -> int:
        return int(self.idx.shape[-1])


def plan_fleet_zone_round(
    graph: ClientGraph,
    positions: np.ndarray,
    zone_size: int,
    rng: np.random.Generator,
    avail: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """K zone plans for one simultaneous wall step.

    Returns (idx (K, Z), mask (K, Z), n_i (K,)). Walkers plan in index
    order and a client claimed by an earlier walker is excluded from
    later walkers' zones — deterministic conflict resolution (lowest
    walker index wins), so the K zones are pairwise disjoint and the
    multi-zone round's scatter-add is duplicate-free. A walker whose own
    position was already claimed serves whatever unclaimed neighbors
    remain (possibly none: an all-padding row — the walker idles).
    ``avail`` composes exactly as in :func:`plan_zone_round`: offline
    neighbors drop out, but a walker's own position always participates
    (unless claimed — the server at that client is the earlier walker).
    """
    k_walkers = len(positions)
    idx = np.zeros((k_walkers, zone_size), np.int32)
    mask = np.zeros((k_walkers, zone_size), np.float32)
    n_i = np.zeros((k_walkers,), np.float32)
    taken = np.zeros(graph.n, dtype=bool)
    for k, i_k in enumerate(positions):
        i_k = int(i_k)
        zone = graph.neighborhood(i_k)
        if avail is not None:
            zone = zone[avail[zone] | (zone == i_k)]
        zone = zone[~taken[zone]]
        n_i[k] = len(zone)
        if len(zone) > zone_size:
            if taken[i_k]:
                active = rng.choice(zone, size=zone_size, replace=False)
            else:
                others = zone[zone != i_k]
                pick = rng.choice(others, size=zone_size - 1, replace=False)
                active = np.concatenate([[i_k], pick])
        else:
            active = zone
        mask[k, : len(active)] = 1.0
        idx[k, : len(active)] = active
        taken[active] = True
    return idx, mask, n_i


def _plan_fleet_round_fast(
    graph,
    positions: np.ndarray,
    zone_size: int,
    rng: np.random.Generator,
    avail: np.ndarray | None = None,
):
    """No-conflict fast path of :func:`plan_fleet_zone_round`.

    When the K walkers' candidate neighborhoods are pairwise disjoint
    (the common case once n ≫ K·deg), the sequential loop's ``taken``
    bookkeeping is a no-op, so the K zone plans can be formed from one
    vectorized neighborhood gather — only walkers whose zone
    oversubscribes still draw from ``rng``, in walker order, exactly as
    the loop would. Returns ``None`` whenever any client is reachable by
    two walkers (including a walker standing on another's candidate or
    duplicate walker positions): the caller falls back to the loop for
    that round. Bit-identical to the loop when it applies (pinned in
    ``tests/test_fleet_scan.py``).
    """
    k_walkers = len(positions)
    pos_arr = np.asarray(positions, dtype=np.int64)
    n = graph.n
    if isinstance(graph, NeighborGraph):
        cand = np.concatenate([graph.nbrs[pos_arr].astype(np.int64),
                               pos_arr[:, None]], axis=1)
        cmask = np.concatenate(
            [graph.nbr_mask[pos_arr],
             np.ones((k_walkers, 1), dtype=bool)], axis=1)
        if avail is not None:
            cmask &= avail[cand] | (cand == pos_arr[:, None])
        live = cand[cmask]
        if len(np.unique(live)) != len(live):
            return None
        # Row-sort with an n sentinel on dead slots → each walker's
        # zone in ascending client order (the loop's ordering).
        sortable = np.where(cmask, cand, n)
        zones = np.sort(sortable, axis=1)
        counts = cmask.sum(axis=1)
    else:
        cand = graph.adjacency[pos_arr].copy()        # (K, n)
        if avail is not None:
            cand &= avail[None, :]
        cand[np.arange(k_walkers), pos_arr] = True
        if (cand.sum(axis=0) > 1).any():
            return None
        counts = cand.sum(axis=1)
        width = int(counts.max()) if k_walkers else 0
        zones = np.full((k_walkers, max(width, 1)), n, dtype=np.int64)
        rr, cc = np.nonzero(cand)                     # row-major → sorted
        zones[rr, graph_mod.segmented_arange(counts)] = cc
    z = zone_size
    idx = np.zeros((k_walkers, z), np.int32)
    mask = np.zeros((k_walkers, z), np.float32)
    n_i = counts.astype(np.float32)
    w = min(zones.shape[1], z)
    small = counts <= z
    fits = zones[:, :w]
    live_cols = fits < n
    idx[:, :w][small] = np.where(live_cols, fits, 0)[small]
    mask[:, :w][small] = live_cols[small].astype(np.float32)
    for k in np.flatnonzero(~small):                  # walker order
        zone = zones[k, : int(counts[k])]
        others = zone[zone != pos_arr[k]]
        pick = rng.choice(others, size=z - 1, replace=False)
        active = np.concatenate([[pos_arr[k]], pick])
        idx[k, : len(active)] = active
        mask[k, : len(active)] = 1.0
    return idx, mask, n_i


def fleet_zone_schedule(
    dyn_graph,
    walkers: Sequence[RandomWalkServer],
    rounds: int,
    zone_size: int,
    rng: np.random.Generator,
    *,
    start_round: int = 0,
    sync_every: int = 20,
    mode: str = "roundrobin",
    price=None,
    price_fleet=None,
    batched_walk: bool = False,
    fast_path: bool = True,
    phase=_no_phase,
) -> FleetZoneSchedule:
    """Precompute ``rounds`` fleet rounds in one batched pass: the
    active-walker index, per-walker random-walk positions, the zone
    plan(s), rendezvous (sync) mask, PRNG keys, and wireless pricing.

    Consumes ``dyn_graph``, each walker's RNG, and the shared simulation
    ``rng`` exactly as the eager fleet driver would, so chunked fleet
    schedules compose and eager/scan trajectories pin bit-for-bit.

    Round-robin: walker ``(start_round + r) % K`` serves round r; the
    graph holds still (and nobody moves) for the first K rounds — every
    vehicle starts parked at a client — then advances per round with the
    active walker taking its step. Walk stepping is batched per walker
    (each walker's RNG stream is independent, so regrouping the rounds
    by walker replays the per-round order exactly).

    Simultaneous: every walker moves every wall step and
    :func:`plan_fleet_zone_round` forms K disjoint zones per round —
    through the vectorized no-conflict fast path
    (:func:`_plan_fleet_round_fast`) when the walkers' neighborhoods are
    disjoint, falling back to the sequential loop for rounds where they
    overlap (``fast_path=False`` forces the loop everywhere; both paths
    are bit-identical where the fast path applies);
    ``price_fleet(graphs, clients (R, K), idx, mask) -> ((R, K), (R, K))``
    prices each walker's zone, aggregated to wall-clock (R,) columns
    (max latency — the zones are served in parallel — and summed energy).
    ``phase`` times the ``walk``, ``zones`` and ``price`` steps as in
    :func:`zone_schedule`.
    """
    k_walkers = len(walkers)
    first = start_round == 0
    pop_trace = getattr(dyn_graph, "pop_avail_trace", None)
    avail_fn = getattr(dyn_graph, "availability", None)

    if mode == "roundrobin":
        lead = min(max(k_walkers - start_round, 0), rounds)
    elif mode == "simultaneous":
        lead = 1 if first else 0
    else:
        raise ValueError(
            f"mode must be roundrobin|simultaneous, got {mode!r}")

    graphs = [dyn_graph.current()] * lead
    cur_avail = avail_fn() if avail_fn is not None else None
    avails_lead = [cur_avail] * lead
    stepped: list = []
    trace = None
    if rounds > lead:
        stepped = dyn_graph.schedule(rounds - lead, include_current=False)
        trace = pop_trace() if pop_trace is not None else None
    graphs = graphs + stepped
    if cur_avail is None and trace is None:
        avails = None
    else:
        avails = avails_lead + (list(trace) if trace is not None
                                else [None] * len(stepped))

    step_name = "walk_schedule_batched" if batched_walk else "walk_schedule"
    biased = any(w.is_biased for w in walkers)
    rs = np.arange(rounds)
    if mode == "roundrobin":
        active_walker = ((start_round + rs) % k_walkers).astype(np.int32)
        positions = np.empty((rounds,), np.int64)
        iw = np.ones((rounds,), np.float64) if biased else None
        with phase("walk", rounds=rounds):
            for k, w in enumerate(walkers):
                mine = np.flatnonzero(active_walker == k)
                parked = mine[mine < lead]
                if len(parked):
                    assert w.position is not None, "call reset() first"
                    positions[parked] = w.position
                    if iw is not None:
                        # Parked rounds serve the walker's current
                        # position; its weight was recorded at the visit
                        # that put it there (1.0 for the reset visit) —
                        # same float the eager fleet round reads.
                        iw[parked] = w.weight_history[-1]
                moving = mine[mine >= lead]
                if len(moving):
                    positions[moving] = getattr(w, step_name)(
                        [graphs[r] for r in moving], advance_first=True)
                    if iw is not None and w.is_biased:
                        iw[moving] = w.walk_weights(len(moving))
        with phase("zones", rounds=rounds):
            idx, mask, n_i, seeds, active = _plan_rounds(
                graphs, positions, zone_size, rng, avails)
            keys = round_keys(seeds)
        latency = energy = None
        if price is not None:
            with phase("price", rounds=rounds):
                latency, energy = price(graphs, positions, idx, mask)
        return FleetZoneSchedule(
            idx=idx, mask=mask, n_i=n_i, keys=keys,
            clients=positions.astype(np.int32), active=active,
            latency_s=latency, energy_j=energy, iw=iw,
            walker=active_walker,
            sync=_sync_mask(start_round, rounds, sync_every),
            mode=mode, n_walkers=k_walkers,
        )

    # -- simultaneous -----------------------------------------------------
    positions = np.empty((rounds, k_walkers), np.int64)
    iw = np.ones((rounds, k_walkers), np.float64) if biased else None
    with phase("walk", rounds=rounds):
        for k, w in enumerate(walkers):
            if lead:
                assert w.position is not None, "call reset() first"
                positions[0, k] = w.position
                if iw is not None:
                    iw[0, k] = w.weight_history[-1]
            if rounds > lead:
                positions[lead:, k] = getattr(w, step_name)(
                    stepped, advance_first=True)
                if iw is not None and w.is_biased:
                    iw[lead:, k] = w.walk_weights(rounds - lead)
    z = zone_size
    idx = np.zeros((rounds, k_walkers, z), np.int32)
    mask = np.zeros((rounds, k_walkers, z), np.float32)
    n_i = np.zeros((rounds, k_walkers), np.float32)
    seeds = np.zeros((rounds,), np.int64)
    with phase("zones", rounds=rounds):
        for r in range(rounds):
            av = None if avails is None else avails[r]
            plan = (_plan_fleet_round_fast(graphs[r], positions[r], z,
                                           rng, avail=av)
                    if fast_path else None)
            if plan is None:    # overlapping neighborhoods this round
                plan = plan_fleet_zone_round(graphs[r], positions[r], z,
                                             rng, avail=av)
            idx[r], mask[r], n_i[r] = plan
            seeds[r] = round_key_seed(rng)
        keys = round_keys(seeds)
    active = mask.sum(axis=2).astype(np.int32)          # (R, K)
    latency = energy = lat_kw = en_kw = None
    if price_fleet is not None:
        with phase("price", rounds=rounds):
            lat_kw, en_kw = price_fleet(graphs, positions, idx, mask)
        latency, energy = lat_kw.max(axis=1), en_kw.sum(axis=1)
    return FleetZoneSchedule(
        idx=idx, mask=mask, n_i=n_i, keys=keys,
        clients=positions.astype(np.int32), active=active,
        latency_s=latency, energy_j=energy, iw=iw,
        sync=_sync_mask(start_round, rounds, sync_every),
        latency_s_walkers=lat_kw, energy_j_walkers=en_kw,
        mode=mode, n_walkers=k_walkers,
    )


def _sync_mask(start_round: int, rounds: int, sync_every: int) -> np.ndarray:
    """(R,) float32 rendezvous mask: 1.0 after rounds where
    ``(rnd + 1) % sync_every == 0`` — the eager fleet's trigger."""
    rs = start_round + np.arange(rounds)
    return ((rs + 1) % max(int(sync_every), 1) == 0).astype(np.float32)
