"""Fleet-RWSADMM (beyond-paper): multiple mobile servers, compiled.

The paper's scenario has ONE tactical vehicle; its §6 scalability
discussion motivates more. Here K walkers each carry their own token y_k
and run independent random walks over the same dynamic graph; every
``sync_every`` rounds the fleet rendezvouses (satellite link) and tokens
average — between syncs, communication stays strictly local/O(1) per
vehicle. Client states (x_i, z_i) are shared: a client updates against
whichever vehicle reaches it.

Two fleet modes:

* ``fleet_mode="roundrobin"`` (default) — the walkers take turns: round
  r is served by walker ``r % K`` against its own token. One wall step
  moves every walker once per K rounds, so coverage (hitting time) drops
  ~K× in wall time while per-round compute stays identical to the
  single-walker trainer. With ``n_walkers=1`` this degenerates to the
  single-walker RWSADMM trajectory exactly (pinned in tests).
* ``fleet_mode="simultaneous"`` — every wall step moves ALL K walkers
  and serves K zones at once: the masked Eq. 31 update runs vmapped over
  the walker axis through the batched multi-zone Pallas kernel
  (``engine="scan_fused"``), with deterministic conflict resolution when
  zones overlap a client (lowest walker index wins —
  ``markov.plan_fleet_zone_round``). This is the fleet's scalability
  workload: K× the zone throughput per wall step in one device program.

State layout: tokens live as ONE stacked ``(K, …)`` pytree, so walker
selection is a ``dynamic_index``, the rendezvous average is a
``jnp.mean`` over the walker axis, and the whole ``FleetState`` stays
device-resident — which is what lets ``schedule()``/``run_chunk()``
compile R fleet rounds into a single ``lax.scan`` executable
(``engine="scan" | "scan_fused"``), trajectory-identical to the eager
fleet. Effects vs a single walker: hitting time drops ~K× (coverage),
and the averaged tokens keep a consensus anchor; with sync_every → ∞
the fleet degenerates into K independent token streams.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import markov, rwsadmm
from ..core.markov import FleetZoneSchedule, RandomWalkServer
from ..core.rwsadmm import ClientState, RWSADMMHparams, ServerState
from ..kernels.rwsadmm_update import ops as fused_ops
from .base import DeviceData, sample_batch
from .rwsadmm_trainer import RWSADMMState, RWSADMMTrainer

FLEET_MODES = ("roundrobin", "simultaneous")


class FleetState(NamedTuple):
    """Fully device-resident fleet state.

    base:   clients + server bookkeeping (κ, round counter, visited);
            ``base.server.y`` mirrors the most recent active walker's
            token (walker 0's view in simultaneous mode) — evaluation
            goes through :meth:`FleetRWSADMMTrainer.personalized_params`,
            which substitutes the fleet-mean token.
    tokens: stacked ``(K, …)`` pytree — one y token per walker.
    """

    base: RWSADMMState
    tokens: Any


def _rendezvous(tokens, sync):
    """Masked fleet rendezvous: where ``sync`` > 0 every walker's token
    is replaced by the fleet mean over the stacked walker axis
    (satellite-link averaging), else pass-through. The same compiled op
    serves the eager step and the scan body, so the two engines'
    trajectories pin bit-for-bit; ``jnp.mean`` over a stacked axis is
    also walker-order invariant up to fp reduction order (tested)."""
    return jax.tree_util.tree_map(
        lambda t: jnp.where(sync > 0,
                            jnp.mean(t, axis=0, keepdims=True), t),
        tokens)


class FleetRWSADMMTrainer(RWSADMMTrainer):
    name = "rwsadmm_fleet"

    def __init__(self, model, data: DeviceData,
                 hp: RWSADMMHparams = RWSADMMHparams(), *,
                 n_walkers: int = 3, sync_every: int = 20,
                 fleet_mode: str = "roundrobin", **kw):
        self.n_walkers = int(n_walkers)
        self.sync_every = int(sync_every)
        if fleet_mode not in FLEET_MODES:
            raise ValueError(
                f"fleet_mode must be one of {'|'.join(FLEET_MODES)}, "
                f"got {fleet_mode!r}")
        self.fleet_mode = fleet_mode
        self._fleet_step_fns: dict = {}    # (mode, use_fused) -> jit step
        self._fleet_chunk_fns: dict = {}   # (mode, engine) -> jit scan
        # super().__init__ attaches the scenario, which (via our
        # attach_scenario override) also builds the walker fleet.
        super().__init__(model, data, hp, **kw)
        if self.fleet_mode == "simultaneous":
            if self.solver != "closed_form":
                raise ValueError(
                    "simultaneous fleet mode vmaps the closed-form Eq. 31 "
                    "zone update over walkers; use solver='closed_form'")
            if self.dp_clip is not None:
                raise ValueError("simultaneous fleet mode does not "
                                 "support DP uploads")

    def _reset_fleet(self) -> None:
        # Walker k's stream is seed + 1 + 10k: walker 0 replays the
        # single-walker trainer's walker (seed + 1) draw-for-draw, so an
        # n_walkers=1 fleet is trajectory-identical to RWSADMMTrainer
        # (pinned in tests); the stride keeps the streams disjoint from
        # the scenario seeds derived nearby.
        self.walkers = [RandomWalkServer(transition=self.walker.transition,
                                         seed=self._seed + 1 + 10 * k,
                                         policy=self.walker.policy,
                                         bias_gamma=self.walker.bias_gamma)
                        for k in range(self.n_walkers)]
        for w in self.walkers:
            w.set_label_weights(self.walker.label_weights)
            w.reset(self.dyn_graph.current())

    def attach_scenario(self, spec, seed: int | None = None) -> None:
        # The RWSADMM attach path (shared _attach_walking_scenario
        # helper) builds the full-stack scenario + lead walker; the
        # fleet then fans out K walkers over the same graph.
        super().attach_scenario(spec, seed=seed)
        if hasattr(self, "n_walkers"):   # re-attach after construction
            self._reset_fleet()

    def init_state(self, key) -> FleetState:
        base = super().init_state(key)
        tokens = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (self.n_walkers,) + l.shape),
            base.server.y)
        if self.fl_sharding is not None:
            # The (K, …) token stack has a walker (not client) leading
            # axis — it replicates like the single-server token.
            tokens = self.fl_sharding.replicate(tokens)
        return FleetState(base=base, tokens=tokens)

    # ------------------------------------------------------------------
    # Lazy client-plane hooks: the fleet nests the client stack/visited
    # mask one level down (state.base), and lazy evaluation measures the
    # global model against the fleet-mean token rather than one walker's.
    # ------------------------------------------------------------------
    def _state_clients(self, state):
        return state.base.clients

    def _state_visited(self, state):
        return state.base.visited

    def _with_clients(self, state, clients):
        return state._replace(base=state.base._replace(clients=clients))

    def _eval_token(self, state):
        return self.global_params(state)

    # ------------------------------------------------------------------
    # Compiled step bodies — ONE jitted function per (mode, fused) pair
    # serves both the eager driver and the lax.scan chunk body, so the
    # engines run literally the same computation per round.
    # ------------------------------------------------------------------
    def _rr_step_impl(self, state: FleetState, idx, mask, n_i, a, sync,
                      key, iw=None, gid=None, *, data: DeviceData,
                      use_fused: bool = False):
        """Round-robin fleet round: walker ``a`` serves one zone against
        its own token (dynamic_index into the stack), then an optional
        rendezvous averages the stack. ``iw`` (biased walk policies) is
        the active walker's importance weight, threaded into the shared
        Eq. 31 round body's y fold; ``data`` is the traced dataset and
        ``gid`` the lazy plane's global ids (see
        :meth:`RWSADMMTrainer._round_impl`)."""
        y_k = jax.tree_util.tree_map(
            lambda t: jax.lax.dynamic_index_in_dim(t, a, 0, keepdims=False),
            state.tokens)
        base = RWSADMMState(
            clients=state.base.clients,
            server=ServerState(y=y_k, kappa=state.base.server.kappa,
                               round=state.base.server.round),
            visited=state.base.visited)
        new_base, loss = self._round_impl(base, idx, mask, n_i, key, iw,
                                          gid, data=data,
                                          use_fused=use_fused)
        tokens = jax.tree_util.tree_map(
            lambda t, y: jax.lax.dynamic_update_index_in_dim(t, y, a, 0),
            state.tokens, new_base.server.y)
        return FleetState(base=new_base,
                          tokens=_rendezvous(tokens, sync)), loss

    def _sim_step_impl(self, state: FleetState, idx, mask, n_i, sync,
                       key, iw=None, gid=None, *, data: DeviceData,
                       use_fused: bool = False):
        """Simultaneous fleet wall step: K disjoint zones (idx/mask are
        (K, Z)) update in one vmapped Eq. 31 pass, each against its own
        walker's token; κ decays once per wall step. ``iw`` (biased walk
        policies) carries each walker's importance weight (K,); the
        per-walker token folds are rescaled by it post hoc. ``data`` is
        the traced dataset (dense stack or packed store rows). Lazy
        plane: ``idx`` holds store slots, ``gid`` the (K, Z) global
        ids."""
        clients = state.base.clients
        hp, kappa = self.hp, state.base.server.kappa
        k_walkers, zone = idx.shape
        gather = lambda t: jax.tree_util.tree_map(lambda l: l[idx], t)
        act = ClientState(x=gather(clients.x), z=gather(clients.z))
        keys = jax.random.split(key, k_walkers * zone).reshape(
            k_walkers, zone, -1)

        def one_grad(params, client, kk):
            xb, yb = sample_batch(data, client, kk, self.batch_size)
            return self.value_and_grad_fn(params, xb, yb, kk)

        losses, grads = jax.vmap(jax.vmap(one_grad))(act.x, idx, keys)
        if use_fused:
            # All K zones' Eq. 31 triple updates in ONE kernel launch.
            x_f, z_f, y_new = fused_ops.rwsadmm_multizone_fused_update(
                act.x, act.z, state.tokens, grads, mask, kappa,
                beta=hp.beta, eps_half=hp.eps_half,
                n_total=float(self.n_clients))
            new_act = ClientState(x=x_f, z=z_f)
        else:
            new_act, y_new = rwsadmm.multizone_round_masked(
                act, state.tokens, grads, mask, hp, kappa,
                float(self.n_clients))
        if iw is not None:
            # Walk-for-Learning correction per walker: rescale each
            # token's zone fold by its walker's importance weight.
            y_new = jax.tree_util.tree_map(
                lambda y0, y1: y0 + iw.reshape(
                    (-1,) + (1,) * (y1.ndim - 1)) * (y1 - y0),
                state.tokens, y_new)

        # Scatter all K zones back in one add: the planner guarantees
        # the zones are disjoint, padded slots carry zero deltas.
        idx_f = idx.reshape(-1)
        m_f = mask.reshape(-1)

        def scatter(full, old_l, new_l):
            fo = old_l.reshape((-1,) + old_l.shape[2:])
            fn = new_l.reshape((-1,) + new_l.shape[2:])
            mm = m_f.reshape((-1,) + (1,) * (fn.ndim - 1))
            return full.at[idx_f].add(mm * (fn - fo))

        clients = ClientState(
            x=jax.tree_util.tree_map(scatter, clients.x, act.x, new_act.x),
            z=jax.tree_util.tree_map(scatter, clients.z, act.z, new_act.z))
        tokens = _rendezvous(y_new, sync)
        server = ServerState(
            y=jax.tree_util.tree_map(lambda t: t[0], tokens),
            kappa=kappa * hp.kappa_decay,
            round=state.base.server.round + 1)
        visited = state.base.visited.at[
            idx_f if gid is None else gid.reshape(-1)].max(m_f > 0)
        loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return FleetState(base=RWSADMMState(clients, server, visited),
                          tokens=tokens), loss

    def _fleet_step_fn(self, mode: str, use_fused: bool):
        fn = self._fleet_step_fns.get((mode, use_fused))
        if fn is None:
            impl = (self._rr_step_impl if mode == "roundrobin"
                    else self._sim_step_impl)
            # named partial: compile logs + the analysis compile-budget
            # sentinel key counts by jit(<closure name>)
            step = functools.partial(impl, use_fused=use_fused)
            functools.update_wrapper(step, impl)
            fn = jax.jit(step)
            self._fleet_step_fns[(mode, use_fused)] = fn
        return fn

    # ------------------------------------------------------------------
    # Eager driver.
    # ------------------------------------------------------------------
    def round(self, state: FleetState, rnd: int, rng: np.random.Generator):
        if self.fleet_mode == "simultaneous":
            return self._round_simultaneous(state, rnd, rng)
        k = rnd % self.n_walkers
        graph = (self.dyn_graph.step() if rnd >= self.n_walkers
                 else self.dyn_graph.current())
        walker = self.walkers[k]
        i_k = walker.step(graph) if rnd >= self.n_walkers \
            else walker.position
        idx, mask, n_i = markov.plan_zone_round(
            graph, int(i_k), self.zone_size, rng,
            avail=self.scenario.availability())
        n_active = int(mask.sum())
        latency_s, energy_j = self._price(graph, i_k, idx, mask)
        key = markov.round_key(rng)
        sync = float((rnd + 1) % max(self.sync_every, 1) == 0)
        state, zone_idx, kwargs = self._round_plane(state, idx)
        args = [state, jnp.asarray(zone_idx), jnp.asarray(mask),
                jnp.asarray(float(n_i)), jnp.asarray(k, jnp.int32),
                jnp.asarray(sync, jnp.float32), key]
        if self._use_iw:
            args.append(jnp.asarray(walker.weight_history[-1],
                                    jnp.float32))
        step_fn = self._fleet_step_fn("roundrobin", False)
        self._audit_record("round:roundrobin", step_fn, args, kwargs)
        state, zone_loss = step_fn(*args, **kwargs)
        metrics = {
            "round": rnd, "walker": k, "client": int(i_k),
            "zone": n_active, "n_i": int(n_i),
            "train_loss": float(zone_loss),
            "kappa": float(state.base.server.kappa),
            "comm_bytes": self.comm_bytes_per_round(n_active),
            "latency_s": latency_s,
            "energy_j": energy_j,
            **self._staleness_metrics(idx, mask, rnd),
        }
        return state, metrics

    def _round_simultaneous(self, state: FleetState, rnd: int,
                            rng: np.random.Generator):
        graph = (self.dyn_graph.step() if rnd > 0
                 else self.dyn_graph.current())
        if rnd > 0:
            positions = np.array([w.step(graph) for w in self.walkers])
        else:
            positions = np.array([w.position for w in self.walkers])
        idx, mask, n_i = markov.plan_fleet_zone_round(
            graph, positions, self.zone_size, rng,
            avail=self.scenario.availability())
        key = markov.round_key(rng)
        sync = float((rnd + 1) % max(self.sync_every, 1) == 0)
        state, zone_idx, kwargs = self._round_plane(state, idx)
        args = [state, jnp.asarray(zone_idx), jnp.asarray(mask),
                jnp.asarray(n_i), jnp.asarray(sync, jnp.float32), key]
        if self._use_iw:
            args.append(jnp.asarray(
                np.array([w.weight_history[-1] for w in self.walkers]),
                jnp.float32))
        step_fn = self._fleet_step_fn("simultaneous", False)
        self._audit_record("round:simultaneous", step_fn, args, kwargs)
        state, loss = step_fn(*args, **kwargs)
        lat_kw, en_kw = self._price_fleet_schedule(
            [graph], positions[None], idx[None], mask[None])
        active = mask.sum(axis=1).astype(int)
        metrics = {
            "round": rnd,
            "clients": tuple(int(c) for c in positions),
            "zone": int(active.sum()), "n_i": int(n_i.sum()),
            "train_loss": float(loss),
            "kappa": float(state.base.server.kappa),
            # idle walkers (all-padding zone: every client claimed by an
            # earlier walker) transmit nothing — the wireless ledger
            # already prices them at zero, so the byte ledger agrees.
            "comm_bytes": int(sum(self.comm_bytes_per_round(int(a))
                                  for a in active if a)),
            "latency_s": float(lat_kw.max()),   # zones served in parallel
            "energy_j": float(en_kw.sum()),
            **self._staleness_metrics(idx, mask, rnd),
        }
        return state, metrics

    # ------------------------------------------------------------------
    # Compiled multi-round (lax.scan) driver.
    # ------------------------------------------------------------------
    def _price_fleet_schedule(self, graphs, clients, idx, mask):
        """Per-walker pricing of a simultaneous window: (R, K) columns."""
        return self.scenario.price_fleet_schedule(
            graphs, clients, idx, mask, self.params_bytes())

    def schedule(self, rounds: int, rng: np.random.Generator,
                 *, start_round: int = 0) -> FleetZoneSchedule:
        """Precompute ``rounds`` fleet rounds (active walker, per-walker
        positions, zone plan(s), sync mask, keys, pricing) consuming the
        graph/walker/sim RNGs exactly as the eager fleet driver would."""
        return markov.fleet_zone_schedule(
            self.dyn_graph, self.walkers, rounds, self.zone_size, rng,
            start_round=start_round, sync_every=self.sync_every,
            mode=self.fleet_mode, price=self._price_schedule,
            price_fleet=self._price_fleet_schedule,
            batched_walk=self.batched_walk)

    def run_chunk(self, state: FleetState, sched: FleetZoneSchedule,
                  engine: str = "scan"):
        """Run a whole fleet schedule chunk as ONE compiled ``lax.scan``
        (round-robin: per-round walker index + sync flag ride along as
        scan inputs; simultaneous: the walker axis rides inside idx/mask).
        Returns (state, {"train_loss": (R,), "kappa": (R,)})."""
        use_fused = self._engine_use_fused(engine)
        mode = getattr(sched, "mode", "roundrobin")
        lazy = self.store is not None
        if lazy:
            # Chunk visited set (both fleet modes' idx layouts flatten
            # the same way) resident before the scan; ids pre-translated
            # to slots, global ids ride along for the visited update.
            with self._phase("ensure", rounds=int(sched.rounds)):
                state, slot_idx = self._ensure_round(state, sched.idx)
        fn = self._fleet_chunk_fns.get((mode, engine))
        if fn is None:
            step = functools.partial(
                self._rr_step_impl if mode == "roundrobin"
                else self._sim_step_impl,
                use_fused=use_fused)
            use_iw = self._use_iw
            if mode == "roundrobin" and lazy:
                def chunk(state, data, idx, gidx, mask, n_i, keys,
                          walker, sync, iws=None):
                    def body(carry, per):
                        i_r, g_r, m_r, ni_r, k_r, a_r, s_r = per[:7]
                        w_r = per[7] if use_iw else None
                        new_state, loss = step(carry, i_r, m_r, ni_r,
                                               a_r, s_r, k_r, w_r,
                                               gid=g_r, data=data)
                        return new_state, (loss,
                                           new_state.base.server.kappa)

                    cols = (idx, gidx, mask, n_i, keys, walker, sync)
                    if use_iw:
                        cols = cols + (iws,)
                    return jax.lax.scan(body, state, cols)
            elif mode == "roundrobin":
                def chunk(state, data, idx, mask, n_i, keys, walker, sync,
                          iws=None):
                    def body(carry, per):
                        i_r, m_r, ni_r, k_r, a_r, s_r = per[:6]
                        w_r = per[6] if use_iw else None
                        new_state, loss = step(carry, i_r, m_r, ni_r,
                                               a_r, s_r, k_r, w_r,
                                               data=data)
                        return new_state, (loss,
                                           new_state.base.server.kappa)

                    cols = (idx, mask, n_i, keys, walker, sync)
                    if use_iw:
                        cols = cols + (iws,)
                    return jax.lax.scan(body, state, cols)
            elif lazy:
                def chunk(state, data, idx, gidx, mask, n_i, keys, sync,
                          iws=None):
                    def body(carry, per):
                        i_r, g_r, m_r, ni_r, k_r, s_r = per[:6]
                        w_r = per[6] if use_iw else None
                        new_state, loss = step(carry, i_r, m_r, ni_r,
                                               s_r, k_r, w_r,
                                               gid=g_r, data=data)
                        return new_state, (loss,
                                           new_state.base.server.kappa)

                    cols = (idx, gidx, mask, n_i, keys, sync)
                    if use_iw:
                        cols = cols + (iws,)
                    return jax.lax.scan(body, state, cols)
            else:
                def chunk(state, data, idx, mask, n_i, keys, sync,
                          iws=None):
                    def body(carry, per):
                        i_r, m_r, ni_r, k_r, s_r = per[:5]
                        w_r = per[5] if use_iw else None
                        new_state, loss = step(carry, i_r, m_r, ni_r,
                                               s_r, k_r, w_r, data=data)
                        return new_state, (loss,
                                           new_state.base.server.kappa)

                    cols = (idx, mask, n_i, keys, sync)
                    if use_iw:
                        cols = cols + (iws,)
                    return jax.lax.scan(body, state, cols)
            if self.fl_sharding is not None:
                # Sharded plane: donate the chunk carry (see the base
                # trainer's run_chunk) — opt-in, default path unchanged.
                fn = jax.jit(chunk, donate_argnums=(0,))
            else:
                fn = jax.jit(chunk)
            self._fleet_chunk_fns[(mode, engine)] = fn

        if lazy:
            args = [self.store.data, jnp.asarray(slot_idx),
                    jnp.asarray(sched.idx)]
        else:
            args = [self.data, jnp.asarray(sched.idx)]
        args += [jnp.asarray(sched.mask), jnp.asarray(sched.n_i),
                 jnp.asarray(sched.keys)]
        if mode == "roundrobin":
            args.append(jnp.asarray(sched.walker))
        args.append(jnp.asarray(sched.sync))
        if self._use_iw:
            args.append(jnp.asarray(sched.iw, jnp.float32))
        self._audit_record(f"chunk:{mode}:{engine}", fn, [state] + args)
        final, (losses, kappas) = fn(state, *args)
        self._chunk_shapes.add((engine, sched.rounds))
        return final, {"train_loss": losses, "kappa": kappas}

    def chunk_round_metrics(self, sched: FleetZoneSchedule, stacked: dict,
                            start_round: int) -> list[dict]:
        if getattr(sched, "mode", "roundrobin") == "roundrobin":
            entries = super().chunk_round_metrics(sched, stacked,
                                                  start_round)
            for j, entry in enumerate(entries):
                entry["walker"] = int(sched.walker[j])
            return entries
        losses = np.asarray(stacked["train_loss"])
        kappas = np.asarray(stacked["kappa"])
        out = []
        for j in range(sched.rounds):
            per_active = np.asarray(sched.active[j])       # (K,)
            entry = {
                "round": start_round + j,
                "clients": tuple(int(c) for c in sched.clients[j]),
                "zone": int(per_active.sum()),
                "n_i": int(np.asarray(sched.n_i[j]).sum()),
                "train_loss": float(losses[j]),
                "kappa": float(kappas[j]),
                "comm_bytes": int(sum(self.comm_bytes_per_round(int(a))
                                      for a in per_active if a)),
            }
            if sched.latency_s is not None:
                entry["latency_s"] = float(sched.latency_s[j])
                entry["energy_j"] = float(sched.energy_j[j])
            entry.update(self._staleness_metrics(
                sched.idx[j], sched.mask[j], start_round + j))
            out.append(entry)
        return out

    # ------------------------------------------------------------------
    def personalized_params(self, state: FleetState):
        """Visited clients keep their x_i; unvisited clients fall back to
        the fleet-mean token (what a rendezvous would hand them)."""
        base = state.base._replace(
            server=state.base.server._replace(y=self.global_params(state)))
        return super().personalized_params(base)

    def global_params(self, state: FleetState):
        return jax.tree_util.tree_map(lambda t: jnp.mean(t, axis=0),
                                      state.tokens)

    def fleet_hitting_time(self) -> int | None:
        """WALL-CLOCK steps until the union of walker visits covers all
        clients (the K vehicles move simultaneously in the field, so one
        wall step = one move of every walker — the fleet's coverage
        advantage is ≈K× in wall time, not in total rounds)."""
        counts = sum(w.visit_counts for w in self.walkers
                     if w.visit_counts is not None)
        if counts is None or (counts == 0).any():
            return None
        seen: set[int] = set()
        hists = [w.history for w in self.walkers]
        for step in range(max(len(h) for h in hists)):
            for h in hists:
                if step < len(h):
                    seen.add(h[step])
            if len(seen) == self.n_clients:
                return step
        return None
