"""Shared FL trainer substrate.

All algorithms (RWSADMM + the five baselines + Walkman) operate on the same
device-resident stacked client data and share jitted building blocks:
stochastic gradients, local-SGD inner loops (lax.scan), and personalized
evaluation. Batches are sampled *inside* jit with fixed shapes, so a whole
simulation reuses one compiled round function per algorithm.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.loader import FederatedData
from ..models.small import SmallModel, accuracy, cross_entropy

PyTree = Any

# ---------------------------------------------------------------------------
# round_metrics schema: one canonical contract for every engine.
# ---------------------------------------------------------------------------

#: keys every round_metrics entry must carry, whichever engine emitted it
REQUIRED_ROUND_KEYS = ("round", "comm_bytes")

#: canonical host-side types for the known metric keys (unknown keys are
#: allowed — trainers extend the schema — but a known key emitted with a
#: surprising type is a bug: it breaks the telemetry JSONL stream and
#: the eager ≡ scan equality pins). bool is NOT an int here.
ROUND_METRIC_TYPES: dict[str, type] = {
    "round": int, "comm_bytes": int, "client": int, "zone": int,
    "n_i": int, "walker": int, "staleness_max": int,
    "train_loss": float, "kappa": float, "latency_s": float,
    "energy_j": float, "staleness_p50": float, "clients": tuple,
}


def normalize_round_metrics(metrics: dict, rnd: int) -> dict:
    """Copy + backfill the keys the schema requires of every entry —
    the single normalization path both simulation engines run each
    entry through (eager per round, scan per chunk entry)."""
    m = dict(metrics)
    m.setdefault("round", rnd)
    m.setdefault("comm_bytes", 0)
    return m


def validate_round_metrics(entries: list[dict], *,
                           start_round: int = 0) -> frozenset:
    """Assert a round_metrics list obeys the canonical schema and
    return its key set: required keys present, ONE key set shared by
    every entry, known keys carrying their canonical host types, and
    ``round`` values consecutive from ``start_round``. Both engines
    must produce lists that pass this with identical key sets (the
    schema-parity test asserts exactly that)."""
    if not entries:
        return frozenset()
    keys = frozenset(entries[0])
    for i, m in enumerate(entries):
        missing = [k for k in REQUIRED_ROUND_KEYS if k not in m]
        assert not missing, f"entry {i} missing required keys {missing}"
        assert frozenset(m) == keys, (
            f"entry {i} key set {sorted(m)} != entry 0 {sorted(keys)}")
        assert m["round"] == start_round + i, (
            f"entry {i}: round={m['round']}, expected {start_round + i}")
        for k, v in m.items():
            want = ROUND_METRIC_TYPES.get(k)
            if want is None:
                continue
            ok = isinstance(v, want) and not (
                want is not bool and isinstance(v, bool))
            assert ok, (f"entry {i} key {k!r}: expected {want.__name__}, "
                        f"got {type(v).__name__} ({v!r})")
    return keys


class DeviceData(NamedTuple):
    """Stacked federated data on device (leading axis = client)."""

    x_train: jnp.ndarray  # (n, m_tr, *feat)
    y_train: jnp.ndarray  # (n, m_tr)
    n_train: jnp.ndarray  # (n,) valid counts
    x_test: jnp.ndarray   # (n, m_te, *feat)
    y_test: jnp.ndarray   # (n, m_te)
    mask_test: jnp.ndarray  # (n, m_te)

    @property
    def n_clients(self) -> int:
        return self.x_train.shape[0]


def to_device_data(fed: FederatedData) -> DeviceData:
    return DeviceData(
        x_train=jnp.asarray(fed.x_train),
        y_train=jnp.asarray(fed.y_train),
        n_train=jnp.asarray(fed.mask_train.sum(axis=1).astype(np.int32)),
        x_test=jnp.asarray(fed.x_test),
        y_test=jnp.asarray(fed.y_test),
        mask_test=jnp.asarray(fed.mask_test),
    )


def sample_batch(data: DeviceData, client: jnp.ndarray, key: jnp.ndarray,
                 batch_size: int):
    """Uniform-with-replacement minibatch ξ from one client (fixed shape)."""
    idx = jax.random.randint(key, (batch_size,), 0, data.n_train[client])
    return data.x_train[client, idx], data.y_train[client, idx]


class TrainerBase:
    """Common plumbing: loss/grad/local-SGD/eval builders for a model."""

    name: str = "base"
    personalized: bool = True
    #: whether this trainer can run on the lazy client plane
    #: (``data=ClientDataFactory`` + the bounded LRU store). Trainers
    #: that keep dense per-client ``(n, …)`` stacks in their state
    #: (Ditto, APFL, Walkman) set this False and refuse loudly.
    lazy_capable: bool = True

    def __init__(self, model: SmallModel, data,
                 batch_size: int = 20, telemetry=None, *,
                 store_capacity: int = 4096, prefetch: bool = False,
                 mesh=None):
        self.model = model
        # ``data`` is either eagerly stacked DeviceData (the dense
        # client plane) or a per-client ClientDataFactory (the lazy
        # plane, ``client_plane="lazy"``): no (n, …) arrays ever
        # materialize, clients are fetched on visit through the bounded
        # LRU ClientStore built below.
        lazy = not isinstance(data, DeviceData)
        self.client_plane = "lazy" if lazy else "dense"
        self.data_factory = data if lazy else None
        self.data = None if lazy else data
        self.batch_size = int(batch_size)
        self.n_clients = data.n_clients
        self.scenario = None   # attach_scenario() / trainer kwarg
        self.telemetry = telemetry   # TelemetryRun or None (off)
        # Static-analysis capture (repro.analysis.jaxpr_audit): when
        # armed, the drivers register every jitted step closure + the
        # exact traced call they are about to make. Off by default and
        # a single flag test per round — the hot paths are untouched.
        self._audit_capture = False
        self._audit_entries: list = []
        # Device-sharded client plane: with a mesh, every leading
        # client/capacity axis goes data-parallel over its "data" axis
        # (fl/sharding.py); without one, placement is untouched.
        self.fl_sharding = None
        if mesh is not None:
            from .sharding import FLSharding

            self.fl_sharding = (mesh if isinstance(mesh, FLSharding)
                                else FLSharding(mesh))
        self.store = None
        if lazy:
            if not self.lazy_capable:
                raise NotImplementedError(
                    f"{type(self).__name__} keeps dense per-client "
                    "(n, …) state stacks and does not support "
                    "client_plane='lazy'; pass stacked DeviceData")
            from .client_store import ClientStore

            self.store = ClientStore(self.data_factory,
                                     int(store_capacity),
                                     prefetch=prefetch,
                                     sharding=self.fl_sharding)
            self.store.telemetry = telemetry
        elif self.fl_sharding is not None:
            # Shard the dense stacked data once; the closures below
            # capture the sharded copy so jitted rounds see data-parallel
            # inputs and propagate the placement.
            data = self.fl_sharding.shard_rows(data)
            self.data = data

        def loss_fn(params, xb, yb, rng):
            logits = model.apply(params, xb, train=True, rng=rng)
            return cross_entropy(logits, yb)

        self.loss_fn = loss_fn
        self.grad_fn = jax.grad(loss_fn)
        self.value_and_grad_fn = jax.value_and_grad(loss_fn)

        def eval_row(params, x, y, m):
            logits = model.apply(params, x, train=False)
            return accuracy(logits, y, m), cross_entropy(logits, y, m)

        # Row-based evaluation over explicit test arrays, shared by both
        # planes: the dense plane passes its stacked (n, …) test columns,
        # the lazy plane its packed store rows. The test data is a traced
        # argument, never a closure constant baked into the executable.
        self.eval_rows_stacked = jax.jit(
            jax.vmap(eval_row, in_axes=(0, 0, 0, 0)))
        self.eval_rows_shared = jax.jit(
            jax.vmap(eval_row, in_axes=(None, 0, 0, 0)))

        if lazy:
            return   # the dense diagnostics below capture self.data

        def train_loss_client(params, client, key):
            xb, yb = sample_batch(data, client, key, self.batch_size)
            return loss_fn(params, xb, yb, None)

        self._train_loss_client = train_loss_client

    # -- local inner loops ------------------------------------------------
    def make_local_sgd(self, lr: float, steps: int) -> Callable:
        """(params, client, key[, data]) -> params after ``steps`` SGD
        steps on the client's data. jit/vmap-safe. ``data`` defaults to
        the dense stacked plane; the lazy plane passes the packed store
        block as a traced argument (``client`` is then a store slot)."""

        def run(params, client, key, data=None):
            data_ = self.data if data is None else data

            def body(p, k):
                xb, yb = sample_batch(data_, client, k, self.batch_size)
                g = self.grad_fn(p, xb, yb, k)
                p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
                return p, None

            keys = jax.random.split(key, steps)
            params, _ = jax.lax.scan(body, params, keys)
            return params

        return run

    # -- evaluation hooks (override personalized_params in subclasses) ----
    def personalized_params(self, state) -> PyTree | None:
        """Stacked (n, ...) personalized parameters, or None."""
        return None

    def global_params(self, state) -> PyTree | None:
        return None

    def evaluate(self, state) -> dict:
        if self.client_plane == "lazy":
            # The dense path below iterates every client's stacked test
            # set — exactly the O(n) materialization the lazy plane
            # removes. Store-backed trainers evaluate over the resident
            # (materialized) clients instead.
            return self._evaluate_lazy(state)
        out: dict[str, float] = {}
        d = self.data
        pers = self.personalized_params(state)
        if pers is not None:
            acc, loss = self.eval_rows_stacked(pers, d.x_test, d.y_test,
                                               d.mask_test)
            out["acc_personalized"] = float(jnp.mean(acc))
            out["acc_personalized_std"] = float(jnp.std(acc))
            out["loss_personalized"] = float(jnp.mean(loss))
        glob = self.global_params(state)
        if glob is not None:
            acc, loss = self.eval_rows_shared(glob, d.x_test, d.y_test,
                                              d.mask_test)
            out["acc_global"] = float(jnp.mean(acc))
            out["loss_global"] = float(jnp.mean(loss))
        out["acc"] = out.get("acc_personalized", out.get("acc_global", 0.0))
        return out

    def _evaluate_lazy(self, state) -> dict:
        """Evaluation restricted to the MATERIALIZED clients — the lazy
        plane's answer to the dense path's all-n iteration. Runs the
        row-based eval over all capacity slots (fixed shapes, one
        executable) and averages over the occupied ones. Personalized
        rows come from :meth:`_lazy_personalized_rows` (None → global
        eval only, e.g. FedAvg); the global model from
        :meth:`global_params`. Reports how many clients the estimate
        covers (``eval_clients``) — at large n this is a resident-set
        sample of the population metric, by design."""
        store = self.store
        occ = store.gid_of >= 0                          # (capacity,)
        d = store.data

        def masked_stats(acc, loss):
            return np.asarray(acc)[occ], np.asarray(loss)[occ]

        out: dict[str, float] = {}
        pers = self._lazy_personalized_rows(state)
        if pers is not None:
            acc, loss = self.eval_rows_stacked(pers, d.x_test, d.y_test,
                                               d.mask_test)
            acc, loss = masked_stats(acc, loss)
            out["acc_personalized"] = float(acc.mean()) if len(acc) else 0.0
            out["acc_personalized_std"] = (float(acc.std())
                                           if len(acc) else 0.0)
            out["loss_personalized"] = (float(loss.mean())
                                        if len(loss) else 0.0)
        glob = self.global_params(state)
        if glob is not None:
            acc, loss = self.eval_rows_shared(glob, d.x_test, d.y_test,
                                              d.mask_test)
            acc, loss = masked_stats(acc, loss)
            out["acc_global"] = float(acc.mean()) if len(acc) else 0.0
            out["loss_global"] = float(loss.mean()) if len(loss) else 0.0
        out["acc"] = out.get("acc_personalized",
                             out.get("acc_global", 0.0))
        out["eval_clients"] = int(occ.sum())
        return out

    def _lazy_personalized_rows(self, state) -> PyTree | None:
        """Per-slot ``(capacity, …)`` personalized parameters for the
        lazy eval path, or None when this trainer evaluates the global
        model only. RWSADMM substitutes visited clients' x rows; the
        adaptation-based baselines adapt the global model on each
        resident slot's data rows."""
        return None

    # -- lazy client-plane plumbing (client_plane="lazy") -----------------
    def _state_clients(self, state) -> PyTree:
        """Where the packed per-client state pytree lives in this
        trainer's state. The FedAvg-family baselines keep NO per-client
        state — the store then manages only the packed data block."""
        return ()

    def _state_visited(self, state):
        return None

    def _with_clients(self, state, clients):
        return state

    def _store_template(self) -> PyTree:
        """Single-client init row the store broadcasts into fresh slots
        (empty for trainers with no per-client state)."""
        return ()

    def _reset_store(self) -> PyTree:
        """(Re)initialize the client store for a fresh run; returns the
        packed ``(capacity, …)`` state pytree. Call from init_state."""
        return self.store.reset(self._store_template())

    def _ensure_round(self, state, idx):
        """Make one working set resident and translate global ids →
        store slots. ``idx`` is the raw padded id array — padding id 0
        rides along deliberately, so the dense plane's masked ±0.0
        scatter-adds land on the same client's row in both planes."""
        clients, stats = self.store.ensure(self._state_clients(state),
                                           np.asarray(idx).reshape(-1))
        self._emit_store_counters(stats)
        return (self._with_clients(state, clients),
                self.store.slots(np.asarray(idx)))

    def _emit_store_counters(self, stats: dict) -> None:
        """Stream one ensure call's hit/miss/evict/restore (+ prefetch,
        when enabled) deltas into telemetry (host-side only — never
        touches an RNG stream, so telemetry-on stays bit-identical to
        off)."""
        if self.telemetry is None:
            return
        for k, v in stats.items():
            self.telemetry.counter(f"client_store_{k}", int(v))

    # -- scenario plumbing (mobility / links / churn, scenarios/) ---------
    def attach_scenario(self, spec, seed: int = 0) -> None:
        """Attach an environment scenario (name or ScenarioConfig).

        For the infrastructure-based baselines the scenario contributes
        client churn (availability gates selection) and wireless round
        pricing against a central base station — they never read the
        connectivity graph, so the scenario runs in **positions-only**
        mode: mobility advances positions (identical RNG stream) but the
        O(n²) adjacency/degree/component stack is skipped entirely.
        Graph-walking trainers override this with
        :meth:`_attach_walking_scenario`.
        """
        from ..scenarios import build_scenario

        self.scenario = build_scenario(spec, self.n_clients, seed=seed,
                                       positions_only=True)
        self.scenario.telemetry = self.telemetry

    def _attach_walking_scenario(self, spec, seed: int, *,
                                 min_degree: int = 5, regen_every: int = 10,
                                 transition: str = "degree",
                                 walk_policy: str | None = None,
                                 walk_bias: float = 1.0,
                                 label_weights=None) -> None:
        """Shared attach path for the graph-walking trainers (RWSADMM,
        Walkman, fleets): build the full-stack scenario, expose it under
        the DynamicGraph contract, and reset a random-walk server on it.
        Callers that track a seed should update it before delegating.

        ``walk_policy``/``walk_bias``/``label_weights`` configure the
        importance-biased walk policies (``core.markov.WALK_POLICIES``,
        see ``docs/walks.md``); the defaults keep the walker on the
        unbiased ``transition`` chain, bit-identical to the seed path.
        """
        from ..core.markov import RandomWalkServer
        from ..scenarios import build_scenario

        self.scenario = build_scenario(
            spec, self.n_clients, seed=seed,
            min_degree=min_degree, regen_every=regen_every,
        )
        self.dyn_graph = self.scenario   # DynamicGraph-compatible facade
        self.walker = RandomWalkServer(transition=transition, seed=seed + 1,
                                       policy=walk_policy,
                                       bias_gamma=float(walk_bias))
        if label_weights is not None:
            self.walker.set_label_weights(label_weights)
        self.walker.reset(self.dyn_graph.current())
        self.scenario.telemetry = self.telemetry

    # -- static-analysis capture (repro.analysis) -------------------------
    def _audit_record(self, name: str, fn, args, kwargs=None) -> None:
        """Register one jitted closure call for the jaxpr auditor."""
        if self._audit_capture:
            self._audit_entries.append(
                (name, fn, tuple(args), dict(kwargs or {})))

    @contextlib.contextmanager
    def capture_jitted(self):
        """Arm closure capture: every jitted step call made inside the
        context is recorded as ``(name, fn, args, kwargs)`` — the jaxpr
        auditor traces these to assert the compiled-path invariants
        (no f64, no baked constants, donation, no callbacks)."""
        self._audit_capture, self._audit_entries = True, []
        try:
            yield self._audit_entries
        finally:
            self._audit_capture = False

    def set_telemetry(self, run) -> None:
        """Attach (or detach, ``None``) a ``TelemetryRun``: the trainer
        and its scenario emit phase spans / events into it. Never
        touches any RNG stream, so trajectories are unchanged."""
        self.telemetry = run
        if self.scenario is not None:
            self.scenario.telemetry = run
        if self.store is not None:
            self.store.telemetry = run

    def _phase(self, name: str, **meta):
        """A phase-timer span against the attached telemetry run, or a
        record-nowhere span when telemetry is off."""
        if self.telemetry is None:
            from ..telemetry import null_phase

            return null_phase()
        return self.telemetry.phase(name, **meta)

    def select_clients(self, rnd: int, rng: np.random.Generator,
                       m: int) -> np.ndarray:
        """Uniform client selection, churn-aware when a scenario is
        attached. Without a scenario this consumes ``rng`` exactly like
        the legacy ``rng.choice(n, m, replace=False)`` call."""
        if self.scenario is None:
            return rng.choice(self.n_clients, size=m, replace=False)
        if rnd > 0:
            self.scenario.step()
        avail = self.scenario.availability()
        pool = (np.flatnonzero(avail) if avail is not None
                else np.arange(self.n_clients))
        if len(pool) == 0:
            pool = np.arange(self.n_clients)
        # Jitted round bodies need fixed shapes: when churn leaves fewer
        # than m clients awake, fill the cohort by resampling the pool
        # (duplicates just reweight the average).
        replace = len(pool) < m
        return rng.choice(pool, size=m, replace=replace)

    def scenario_round_costs(self, members: np.ndarray) -> dict:
        """Wireless latency/energy for one baseline round (base-station
        topology); {} when no scenario is attached. Priced over all
        cohort slots — duplicates from churn resampling count as
        distinct transfers, matching comm_bytes_per_round's ledger."""
        if self.scenario is None:
            return {}
        lat, en = self.scenario.price_star_round(
            np.asarray(members), self.params_bytes())
        return {"latency_s": lat, "energy_j": en}

    # -- abstract ----------------------------------------------------------
    def init_state(self, key):  # pragma: no cover - interface
        raise NotImplementedError

    def round(self, state, rnd: int, rng: np.random.Generator):
        """One communication round. Returns (state, metrics dict)."""
        raise NotImplementedError  # pragma: no cover

    # -- communication accounting ------------------------------------------
    def params_bytes(self) -> int:
        """Bytes of one model copy (cached — init is host-side and slow).

        Deliberately n-independent: one template ``model.init``, never a
        per-client iteration, so the communication ledger works the same
        under the lazy client plane at n = 10⁶ as on the dense plane."""
        cached = getattr(self, "_params_bytes", None)
        if cached is None:
            from ..core import tree as t

            cached = t.n_bytes(self.model.init(jax.random.PRNGKey(0)))
            self._params_bytes = cached
        return cached

    def comm_bytes_per_round(self, participants: int) -> int:
        """Default: each participant downloads + uploads one model copy."""
        return int(2 * participants * self.params_bytes())
