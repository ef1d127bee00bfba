"""RWSADMM federated trainer (paper Algorithm 1 + Eq. 31 multi-client zone).

Host side per round k:
  1. advance the dynamic graph (regenerated every ``regen_every`` rounds),
  2. the mobile server random-walks to client i_k  (Markov chain, Eq. 2),
  3. the active zone S(i_k) ⊆ N(i_k) is formed (up to ``zone_size``),
  4. one compiled SPMD zone round runs: stochastic grads at the active
     clients' x'_j, closed-form x/z updates, incremental y update,
  5. κ ← 0.99 κ.

The compiled round has *fixed shapes*: zones are padded to ``zone_size``
with a mask; padded slots contribute zero deltas via scatter-add, so a
whole training run reuses a single XLA executable.

Two drivers share that round body:

* **eager** — :meth:`round`: one XLA dispatch + one host sync per round
  (the classic loop; dispatch overhead dominates for small models).
* **scan** — :meth:`schedule` precomputes the whole random-walk / zone /
  key schedule as fixed-shape arrays (``core.markov.zone_schedule``),
  then :meth:`run_chunk` runs R rounds as ONE ``lax.scan`` executable
  with no per-round host round-trips; metrics come back stacked.
  ``engine="scan_fused"`` additionally routes the closed-form triple
  update through the masked multi-client Pallas kernel
  (``kernels.rwsadmm_update``) so the Eq. 31 zone round is one HBM pass.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import markov, rwsadmm
from ..core.markov import ZoneSchedule
from ..core.rwsadmm import ClientState, RWSADMMHparams, ServerState
from ..kernels.rwsadmm_update import ops as fused_ops
from ..scenarios import ScenarioConfig
from .base import DeviceData, TrainerBase, sample_batch

SCAN_ENGINES = ("scan", "scan_fused")      # compiled lax.scan drivers
ENGINES = ("eager",) + SCAN_ENGINES        # everything run_simulation takes


class RWSADMMState(NamedTuple):
    clients: ClientState      # stacked (n, ...)
    server: ServerState
    visited: jnp.ndarray      # (n,) bool — who holds a personalized model


class RWSADMMTrainer(TrainerBase):
    name = "rwsadmm"
    personalized = True

    def __init__(
        self,
        model,
        data: DeviceData,
        hp: RWSADMMHparams = RWSADMMHparams(),
        *,
        batch_size: int = 20,
        zone_size: int = 8,
        min_degree: int = 5,
        regen_every: int = 10,
        transition: str = "degree",
        warm_init: bool = True,
        solver: str = "prox_sgd",   # "prox_sgd" (Eq. 9, K steps) |
                                    # "closed_form" (Eq. 10/11, one step)
        inner_steps: int = 10,
        inner_lr: float = 0.05,
        dp_clip: float | None = None,     # l2 clip on uploaded Δc (DP)
        dp_noise: float = 1.0,            # Gaussian noise multiplier σ
        scenario: ScenarioConfig | str | None = None,
        batched_walk: bool = False,       # inverse-cdf walk sampling in
                                          # schedule() (RNG-stream break
                                          # vs eager; see markov)
        walk_policy: str | None = None,   # markov.WALK_POLICIES; None →
                                          # the unbiased ``transition``
        walk_bias: float = 1.0,           # staleness exponent / label-
                                          # skew sharpening γ
        store_capacity: int = 4096,       # lazy plane: resident slots in
                                          # the bounded LRU client store
        prefetch: bool = False,           # lazy plane: stage the next
                                          # chunk's dataset rows on a
                                          # host thread (bit-identical)
        mesh=None,                        # Mesh/FLSharding: shard the
                                          # client plane's leading axis
                                          # over the mesh "data" axis
        telemetry=None,                   # TelemetryRun or None (off)
        seed: int = 0,
    ):
        # Lazy client plane: when ``data`` is a ClientDataFactory, the
        # base builds the bounded (store_capacity, …) LRU ClientStore —
        # client x/z pytrees and datasets materialize on first visit
        # instead of as (n, …) stacks (docs/performance.md §7), pinned
        # bit-identical to the dense plane (tests/test_lazy_plane).
        super().__init__(model, data, batch_size, telemetry=telemetry,
                         store_capacity=store_capacity,
                         prefetch=prefetch, mesh=mesh)
        self.hp = hp
        self.solver = solver
        self.dp_clip = dp_clip
        self.dp_noise = dp_noise
        self.batched_walk = bool(batched_walk)
        self.inner_steps = int(inner_steps)
        self.inner_lr = float(inner_lr)
        self.zone_size = int(min(zone_size, self.n_clients))
        self.warm_init = warm_init
        self._seed = int(seed)
        self._min_degree = int(min_degree)
        self._regen_every = int(regen_every)
        self._transition = transition
        self.walk_policy = walk_policy
        self.walk_bias = float(walk_bias)
        # Static flag: biased policies thread the per-round importance
        # weight into the Eq. 31 y-update (Walk-for-Learning correction);
        # uniform policies keep the seed computation graph untouched.
        self._use_iw = walk_policy in markov.BIASED_POLICIES
        # The environment: mobility + links + churn behind the old
        # DynamicGraph contract. scenario=None builds "static_regen"
        # from the legacy min_degree/regen_every knobs — bit-for-bit
        # the seed behavior. A named or explicit ScenarioConfig is
        # authoritative: its own mobility knobs override those kwargs.
        self.attach_scenario(scenario, seed=seed)
        # update_wrapper names the partial so jax's compile logs (and
        # the analysis compile-budget sentinel) see jit(_round_impl)
        # instead of jit(<unnamed wrapped function>).
        _round = functools.partial(self._round_impl)
        functools.update_wrapper(_round, self._round_impl)
        self._round_fn = jax.jit(_round)
        self._chunk_fns: dict = {}   # engine -> jitted lax.scan driver
        self._chunk_shapes: set = set()   # (engine, R) already compiled

    def attach_scenario(self, spec, seed: int | None = None) -> None:
        """(Re)build the environment and reset the walker onto it.

        ``seed`` (when given) becomes the trainer's RNG seed so every
        derived stream — scenario layers, walker, fleet walkers —
        reseeds consistently.
        """
        seed = self._seed if seed is None else seed
        self._seed = seed
        self._attach_walking_scenario(
            spec, seed, min_degree=self._min_degree,
            regen_every=self._regen_every, transition=self._transition,
            walk_policy=self.walk_policy, walk_bias=self.walk_bias,
            label_weights=self._label_skew_weights(),
        )
        # Per-client service clock for the staleness round metrics
        # (round index of each client's last zone participation).
        self._last_served = np.full(self.n_clients, -1, dtype=np.int64)

    def _label_skew_weights(self) -> np.ndarray | None:
        """Per-client data utilities for the ``label_skew`` walk policy,
        from the padded device label arrays (None for other policies)."""
        if self.walk_policy != "label_skew":
            return None
        if self.data is None:
            raise ValueError(
                "walk_policy='label_skew' needs the per-client label "
                "histograms of the dense client plane; the lazy plane "
                "never materializes them")
        from ..data import partition

        hist = partition.padded_label_histograms(
            np.asarray(self.data.y_train), np.asarray(self.data.n_train))
        return partition.label_skew_weights(hist, gamma=self.walk_bias)

    def _staleness_metrics(self, idx, mask, rnd: int) -> dict:
        """Update the per-client service clock with one round's zone and
        report the staleness distribution (rounds since last service;
        never-served clients count rnd + 1). Integer math shared by the
        eager driver and ``chunk_round_metrics``, so both engines emit
        identical values (pinned in the scan-driver tests)."""
        served = np.asarray(idx)[np.asarray(mask) > 0]
        self._last_served[served] = rnd
        stale = rnd - self._last_served
        return {"staleness_p50": float(np.median(stale)),
                "staleness_max": int(stale.max())}

    def _price(self, graph, i_k, idx, mask):
        return self.scenario.price_round(graph, int(i_k), idx, mask,
                                         self.params_bytes())

    def _price_schedule(self, graphs, clients, idx, mask):
        return self.scenario.price_schedule(graphs, clients, idx, mask,
                                            self.params_bytes())

    # ------------------------------------------------------------------
    def init_state(self, key) -> RWSADMMState:
        params = self.model.init(key)
        if self.store is not None:
            return self._init_state_lazy(params)
        if self.warm_init:
            clients, server = rwsadmm.init_states_warm(
                params, self.hp, self.n_clients
            )
        else:
            clients, server = rwsadmm.init_states(
                params, self.hp, self.n_clients
            )
        visited = jnp.zeros((self.n_clients,), bool)
        if self.fl_sharding is not None:
            # Data-parallel client plane: the (n, …) stacks split over
            # the mesh "data" axis, the walking token replicates. The
            # jitted round/chunk bodies propagate these placements.
            clients = self.fl_sharding.shard_rows(clients)
            server = self.fl_sharding.replicate(server)
            visited = self.fl_sharding.shard_rows(visited)
        return RWSADMMState(clients=clients, server=server,
                            visited=visited)

    def _init_state_lazy(self, params) -> RWSADMMState:
        """Packed-store twin of the dense init: every client's dense
        init row is IDENTICAL (warm: x=params, z=0; cold: x=z=0), so
        the store pre-fills all capacity slots from that one template —
        lazy materialization is bit-for-bit dense init by construction.
        ``clients`` leaves are (capacity, …); ``visited`` stays a dense
        (n,) bool (1 bit of truth per client costs ~n bytes, not the
        O(n·p) the packed plane removes)."""
        from ..core import tree as t

        zeros = t.zeros_like(params)
        template = (ClientState(x=params, z=zeros) if self.warm_init
                    else ClientState(x=zeros, z=zeros))
        # The store shards the packed rows itself when built with a
        # sharding (capacity axis over "data").
        clients = self.store.reset(template)
        server = ServerState(
            y=params if self.warm_init else zeros,
            kappa=jnp.asarray(self.hp.kappa, jnp.float32),
            round=jnp.asarray(0, jnp.int32),
        )
        visited = jnp.zeros((self.n_clients,), bool)
        if self.fl_sharding is not None:
            server = self.fl_sharding.replicate(server)
            visited = self.fl_sharding.shard_rows(visited)
        return RWSADMMState(clients=clients, server=server,
                            visited=visited)

    # ------------------------------------------------------------------
    def _round_impl(self, state: RWSADMMState, zone_idx, zone_mask, n_i,
                    key, iw=None, gid=None, *, data: DeviceData,
                    use_fused: bool = False):
        # ``data`` always arrives as a traced argument. Dense plane: the
        # stacked DeviceData, zone_idx are global client ids and gid is
        # None (an empty pytree under jit). Baking the dataset in as a
        # closure constant would copy it into every executable and, at
        # the paper CNN's 0.5 GB, cost the TPU compiler seconds per
        # program. Lazy plane: the packed store data, zone_idx are STORE
        # SLOTS and ``gid`` carries the global ids (visited-set
        # bookkeeping) — a closure over ``self.store.data`` would also
        # freeze whatever rows were resident at trace time.
        clients, server = state.clients, state.server
        hp, kappa = self.hp, server.kappa
        # Named scopes split the round on a device trace; they change
        # only the ops' op_name metadata, never the computation.

        # Gather active clients' ADMM variables: (Z, ...)
        with jax.named_scope("rwsadmm.zone_update"):
            gather = lambda t: jax.tree_util.tree_map(
                lambda l: l[zone_idx], t)
            act = ClientState(x=gather(clients.x), z=gather(clients.z))

        with jax.named_scope("rwsadmm.grad"):
            keys = jax.random.split(key, self.zone_size)
        y_new = None   # set early by the fused kernel, late by the jnp fold

        if self.solver == "closed_form":
            # One-step stochastic linearization (Eq. 10/11).
            def one_grad(params, client, k):
                xb, yb = sample_batch(data, client, k, self.batch_size)
                return self.value_and_grad_fn(params, xb, yb, k)

            with jax.named_scope("rwsadmm.grad"):
                losses, grads = jax.vmap(one_grad)(act.x, zone_idx, keys)
            with jax.named_scope("rwsadmm.zone_update"):
                if use_fused:
                    # Whole zone round (Eq. 31) in one HBM pass: x/z
                    # updates for every active client + the masked y fold.
                    x_f, z_f, y_new = fused_ops.rwsadmm_zone_fused_update(
                        act.x, act.z, server.y, grads, zone_mask, kappa,
                        beta=hp.beta, eps_half=hp.eps_half,
                        n_total=float(self.n_clients),
                    )
                    new_act = ClientState(x=x_f, z=z_f)
                else:
                    upd = jax.vmap(
                        lambda c, g: rwsadmm.client_round(c, server.y, g,
                                                          hp, kappa)
                    )
                    new_act, c_new, c_old = upd(act, grads)
        else:
            # Iterative solver of the x-subproblem (Eq. 9): K stochastic
            # subgradient steps, warm-started at the client's stored x'.
            eta = self.inner_lr

            def solve_one(c: ClientState, client, k):
                def body(x, kk):
                    xb, yb = sample_batch(data, client, kk,
                                          self.batch_size)
                    loss, gf = self.value_and_grad_fn(x, xb, yb, kk)
                    g = rwsadmm.subproblem_grad(x, server.y, c.z, gf, hp)
                    x = jax.tree_util.tree_map(
                        lambda a, b: a - eta * b, x, g
                    )
                    return x, loss

                with jax.named_scope("rwsadmm.grad"):
                    kks = jax.random.split(k, self.inner_steps)
                    x_new, losses_ = jax.lax.scan(body, c.x, kks)
                with jax.named_scope("rwsadmm.zone_update"):
                    z_new = rwsadmm.z_update(x_new, server.y, c.z, hp,
                                             kappa)
                    c_old_ = rwsadmm.contribution(c.x, c.z, server.y, hp)
                    c_new_ = rwsadmm.contribution(x_new, z_new, server.y,
                                                  hp)
                return (ClientState(x=x_new, z=z_new), c_new_, c_old_,
                        losses_[-1])

            new_act, c_new, c_old, losses = jax.vmap(solve_one)(
                act, zone_idx, keys
            )

        # Masked incremental y-update:  y += (1/n) Σ_active (c_new − c_old)
        # (1/n, not the printed 1/n_i — see core.rwsadmm.y_update docstring.)
        m = zone_mask  # (Z,)
        n_total = float(self.n_clients)

        with jax.named_scope("rwsadmm.zone_update"):
            if y_new is None:
                if self.dp_clip is not None:
                    # DP uploads: clip + noise each active client's Δc
                    # before it reaches the walking token
                    # (core/privacy.py).
                    from ..core import privacy

                    dkeys = jax.random.split(jax.random.fold_in(key, 97),
                                             self.zone_size)
                    deltas = jax.vmap(
                        lambda k_, cn, co: privacy.privatize_delta(
                            k_, cn, co, clip=self.dp_clip,
                            noise_multiplier=self.dp_noise)
                    )(dkeys, c_new, c_old)
                else:
                    deltas = jax.tree_util.tree_map(
                        lambda cn, co: cn - co, c_new, c_old)

                def fold(y, d):
                    mm = m.reshape((-1,) + (1,) * (d.ndim - 1))
                    delta = jnp.sum(mm * d, axis=0) / n_total
                    # Importance-weight correction (biased walk
                    # policies): the zone fold is scaled by 1/(n π_{i_k})
                    # so the y-update estimator stays unbiased under the
                    # biased visit distribution (docs/walks.md). iw=None
                    # (uniform policies) keeps the seed computation graph
                    # unchanged.
                    return y + (delta if iw is None else iw * delta)

                y_new = jax.tree_util.tree_map(fold, server.y, deltas)
            elif iw is not None:
                # Fused-kernel path: the Pallas kernel already folded the
                # unweighted zone delta into y; rescale it post hoc.
                y_new = jax.tree_util.tree_map(
                    lambda y0, y1: y0 + iw * (y1 - y0), server.y, y_new)

        # Scatter active deltas back (duplicate-free: zone indices unique,
        # padded slots masked to zero so .add is a no-op for them).
        def scatter(full, old_act, new_act_):
            mm = m.reshape((-1,) + (1,) * (new_act_.ndim - 1))
            return full.at[zone_idx].add(mm * (new_act_ - old_act))

        with jax.named_scope("rwsadmm.scatter"):
            clients = ClientState(
                x=jax.tree_util.tree_map(scatter, clients.x, act.x,
                                         new_act.x),
                z=jax.tree_util.tree_map(scatter, clients.z, act.z,
                                         new_act.z),
            )
        server = ServerState(
            y=y_new,
            kappa=server.kappa * hp.kappa_decay,
            round=server.round + 1,
        )
        with jax.named_scope("rwsadmm.scatter"):
            visited = state.visited.at[
                zone_idx if gid is None else gid].max(m > 0)
        zone_loss = jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)
        return RWSADMMState(clients, server, visited), zone_loss

    # ------------------------------------------------------------------
    def round(self, state: RWSADMMState, rnd: int, rng: np.random.Generator):
        """Eager driver: one dispatch + one host sync per round."""
        graph = self.dyn_graph.step() if rnd > 0 else self.dyn_graph.current()
        i_k = self.walker.step(graph) if rnd > 0 else self.walker.position
        idx, mask, n_i = markov.plan_zone_round(
            graph, int(i_k), self.zone_size, rng,
            avail=self.scenario.availability(),
        )
        n_active = int(mask.sum())
        latency_s, energy_j = self._price(graph, i_k, idx, mask)

        key = markov.round_key(rng)
        state, zone_idx, kwargs = self._round_plane(state, idx)
        args = [state, jnp.asarray(zone_idx), jnp.asarray(mask),
                jnp.asarray(float(n_i)), key]
        if self._use_iw:
            # The weight recorded at the walker's latest visit — the
            # same float the schedule's iw column carries for this round.
            args.append(jnp.asarray(self.walker.weight_history[-1],
                                    jnp.float32))
        self._audit_record("round", self._round_fn, args, kwargs)
        state, zone_loss = self._round_fn(*args, **kwargs)
        metrics = {
            "round": rnd,
            "client": int(i_k),
            "zone": n_active,
            "n_i": int(n_i),
            "train_loss": float(zone_loss),
            "kappa": float(state.server.kappa),
            "comm_bytes": self.comm_bytes_per_round(n_active),
            "latency_s": latency_s,
            "energy_j": energy_j,
            **self._staleness_metrics(idx, mask, rnd),
        }
        return state, metrics

    # ------------------------------------------------------------------
    # Lazy client plane plumbing (client_plane="lazy").
    # ------------------------------------------------------------------
    def _state_clients(self, state):
        """Where the packed client pytree lives in this trainer's state
        (the fleet wraps it one level deeper)."""
        return state.clients

    def _state_visited(self, state):
        return state.visited

    def _with_clients(self, state, clients):
        return state._replace(clients=clients)

    def _round_plane(self, state, idx):
        """(state, zone ids, data kwargs) for one eager round: the lazy
        plane makes the zone resident and hands the step store slots,
        the global ids and the packed rows; the dense plane hands it the
        global ids and the stacked dataset."""
        if self.store is None:
            return state, idx, {"data": self.data}
        state, zone_idx = self._ensure_round(state, idx)
        return state, zone_idx, {"gid": jnp.asarray(idx),
                                 "data": self.store.data}

    def prefetch_chunk(self, sched) -> int:
        """Hand the NEXT chunk's working set to the store's async
        staging pipeline (no-op unless ``prefetch=True``): dataset rows
        for its predicted misses materialize on a host thread while the
        current chunk executes (``run_simulation`` drives this —
        docs/performance.md §8)."""
        if self.store is None or not self.store.prefetch_enabled:
            return 0
        return self.store.prefetch(np.asarray(sched.idx).reshape(-1))

    # ------------------------------------------------------------------
    # Compiled multi-round (lax.scan) driver.
    # ------------------------------------------------------------------
    def schedule(self, rounds: int, rng: np.random.Generator,
                 *, start_round: int = 0) -> ZoneSchedule:
        """Precompute the next ``rounds`` zone rounds as fixed-shape
        arrays, consuming the graph/walker/sim RNGs exactly as the eager
        driver would (so chunked scans replay eager runs draw-for-draw).
        """
        return markov.zone_schedule(
            self.dyn_graph, self.walker, rounds, self.zone_size, rng,
            start_round=start_round, price=self._price_schedule,
            batched_walk=self.batched_walk, phase=self._phase,
        )

    def chunk_is_cold(self, engine: str, rounds: int | None = None
                      ) -> bool:
        """True when the next ``run_chunk(engine=…)`` call at this chunk
        length will trace + compile a fresh executable (jit caches by
        engine and by the scan length) — the telemetry phase timers tag
        such spans ``includes_compile`` so the report CLI can separate
        compile cost from steady-state chunk throughput."""
        return (engine, rounds) not in self._chunk_shapes

    def _engine_use_fused(self, engine: str) -> bool:
        """Validate a scan engine name; True when it takes the fused
        (Pallas zone kernel) hot path. Shared with the fleet driver."""
        if engine not in SCAN_ENGINES:
            raise ValueError(
                f"engine must be one of {'|'.join(SCAN_ENGINES)}, "
                f"got {engine}")
        use_fused = engine == "scan_fused"
        if use_fused and self.solver != "closed_form":
            raise ValueError(
                "scan_fused fuses the closed-form triple update; use "
                "solver='closed_form' (prox_sgd has no closed-form x step)")
        if use_fused and self.dp_clip is not None:
            raise ValueError("scan_fused does not support DP uploads; "
                             "use engine='scan'")
        return use_fused

    def chunk_round_metrics(self, sched: ZoneSchedule, stacked: dict,
                            start_round: int) -> list[dict]:
        """Rebuild per-round metric dicts from a finished chunk — the
        host-side mirror of what :meth:`round` emits, so both engines
        share one ``round_metrics`` schema (asserted in tests)."""
        losses = np.asarray(stacked["train_loss"])
        kappas = np.asarray(stacked["kappa"])
        out = []
        for j in range(sched.rounds):
            n_active = int(sched.active[j])
            entry = {
                "round": start_round + j,
                "client": int(sched.clients[j]),
                "zone": n_active,
                "n_i": int(sched.n_i[j]),
                "train_loss": float(losses[j]),
                "kappa": float(kappas[j]),
                "comm_bytes": self.comm_bytes_per_round(n_active),
            }
            if sched.latency_s is not None:
                entry["latency_s"] = float(sched.latency_s[j])
                entry["energy_j"] = float(sched.energy_j[j])
            entry.update(self._staleness_metrics(
                sched.idx[j], sched.mask[j], start_round + j))
            out.append(entry)
        return out

    def run_chunk(self, state: RWSADMMState, sched: ZoneSchedule,
                  engine: str = "scan"):
        """Run a whole schedule chunk as ONE compiled ``lax.scan``.

        No host sync inside the chunk; per-round metrics come back as
        stacked device arrays. Returns (state, {"train_loss": (R,),
        "kappa": (R,)}).
        """
        use_fused = self._engine_use_fused(engine)
        lazy = self.store is not None
        if lazy:
            # The chunk's whole visited set (padding ids included) is
            # gathered from the precomputed schedule BEFORE the scan, so
            # the compiled body only carries the (capacity, …) packed
            # pytree + packed data; ids enter the scan pre-translated
            # to slots, with the global ids riding along for the
            # visited-set update.
            with self._phase("ensure", rounds=int(sched.rounds)):
                state, slot_idx = self._ensure_round(state, sched.idx)

        fn = self._chunk_fns.get(engine)
        if fn is None:
            round_fn = functools.partial(self._round_impl,
                                         use_fused=use_fused)

            if lazy:
                use_iw = self._use_iw

                def chunk(state, data, idx, gidx, mask, n_i, keys,
                          iws=None):
                    def body(carry, per):
                        i_r, g_r, m_r, ni_r, k_r = per[:5]
                        w_r = per[5] if use_iw else None
                        new_state, loss = round_fn(carry, i_r, m_r, ni_r,
                                                   k_r, w_r, gid=g_r,
                                                   data=data)
                        return new_state, (loss, new_state.server.kappa)

                    cols = (idx, gidx, mask, n_i, keys)
                    if use_iw:
                        cols = cols + (iws,)
                    return jax.lax.scan(body, state, cols)
            elif self._use_iw:
                # Biased walk policy: the schedule's per-round importance
                # weights ride along as one more scan input.
                def chunk(state, data, idx, mask, n_i, keys, iws):
                    def body(carry, per_round):
                        i_r, m_r, ni_r, k_r, w_r = per_round
                        new_state, loss = round_fn(carry, i_r, m_r, ni_r,
                                                   k_r, w_r, data=data)
                        return new_state, (loss, new_state.server.kappa)

                    return jax.lax.scan(
                        body, state, (idx, mask, n_i, keys, iws))
            else:
                def chunk(state, data, idx, mask, n_i, keys):
                    def body(carry, per_round):
                        i_r, m_r, ni_r, k_r = per_round
                        new_state, loss = round_fn(carry, i_r, m_r, ni_r,
                                                   k_r, data=data)
                        return new_state, (loss, new_state.server.kappa)

                    return jax.lax.scan(
                        body, state, (idx, mask, n_i, keys))

            if self.fl_sharding is not None:
                # Sharded plane: donate the chunk carry so XLA reuses
                # the per-device client-row buffers in place instead of
                # doubling resident state for every chunk. Opt-in only —
                # the default path keeps the input state alive (tests
                # reuse states across engines).
                fn = jax.jit(chunk, donate_argnums=(0,))
            else:
                fn = jax.jit(chunk)
            self._chunk_fns[engine] = fn

        if lazy:
            args = [self.store.data, jnp.asarray(slot_idx),
                    jnp.asarray(sched.idx)]
        else:
            args = [self.data, jnp.asarray(sched.idx)]
        args += [jnp.asarray(sched.mask), jnp.asarray(sched.n_i),
                 jnp.asarray(sched.keys)]
        if self._use_iw:
            args.append(jnp.asarray(sched.iw, jnp.float32))
        self._audit_record(f"chunk:{engine}", fn, [state] + args)
        final, (losses, kappas) = fn(state, *args)
        self._chunk_shapes.add((engine, sched.rounds))
        return final, {"train_loss": losses, "kappa": kappas}

    # ------------------------------------------------------------------
    def _lazy_personalized_rows(self, state):
        """Per-slot personalization for the resident-set eval, mirroring
        :meth:`personalized_params`: slots whose client the walk has
        visited evaluate their x row, the rest the token y (what the
        mobile server would hand them)."""
        store = self.store
        occ = store.gid_of >= 0                          # (capacity,)
        occ_ids = np.where(occ, np.maximum(store.gid_of, 0), 0)
        visited_slot = jnp.asarray(
            np.asarray(self._state_visited(state))[occ_ids] & occ)
        clients = self._state_clients(state)
        y = self._eval_token(state)

        def pers_leaf(x, y_):
            v = visited_slot.reshape((-1,) + (1,) * y_.ndim)
            return jnp.where(v, x, y_[None])

        return jax.tree_util.tree_map(pers_leaf, clients.x, y)

    def _eval_token(self, state):
        """The token unvisited clients evaluate against (the fleet
        substitutes its rendezvous mean)."""
        return state.server.y

    def personalized_params(self, state: RWSADMMState):
        """x_i for visited clients; unvisited clients fall back to the
        server token y (what the mobile server would hand them)."""
        if self.store is not None:
            raise NotImplementedError(
                "personalized_params would materialize an (n, …) stack; "
                "under client_plane='lazy' use evaluate() (resident-set "
                "metrics) or read rows off trainer.store")
        def leaf(x, y):
            v = state.visited.reshape((-1,) + (1,) * (y.ndim))
            return jnp.where(v, x, y[None])

        return jax.tree_util.tree_map(leaf, state.clients.x, state.server.y)

    def global_params(self, state: RWSADMMState):
        return state.server.y

    def comm_bytes_per_round(self, participants: int) -> int:
        # Server broadcasts y once into the zone; each active client
        # uploads its contribution delta. O(1) in n — the paper's claim.
        return int((1 + participants) * self.params_bytes())

    # -- diagnostics -----------------------------------------------------
    def lyapunov(self, state: RWSADMMState, key) -> dict:
        """L_β and constraint residuals (Eq. 8 / Eq. 7) for monitoring."""
        if self.store is not None:
            raise NotImplementedError(
                "lyapunov iterates all n clients' data — a dense-plane "
                "diagnostic; run it on a dense twin at small n")
        losses = []
        for c in range(self.n_clients):
            xi = jax.tree_util.tree_map(lambda l: l[c], state.clients.x)
            losses.append(self._train_loss_client(xi, c, key))
        losses = jnp.stack(losses)
        l_beta = rwsadmm.augmented_lagrangian(
            state.server.y, state.clients, losses, self.hp
        )
        viol = rwsadmm.constraint_violation(
            state.server.y, state.clients.x, self.hp
        )
        return {"L_beta": float(l_beta), "violation": float(viol)}
