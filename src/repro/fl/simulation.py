"""Generic FL simulation runner: drives any trainer for R rounds, records
convergence history, communication totals, and wall time.

Two execution engines:

* ``engine="eager"`` (default, any trainer): one ``trainer.round`` call —
  i.e. one XLA dispatch plus one blocking host sync — per round.
* ``engine="scan" | "scan_fused"`` (trainers exposing ``schedule`` /
  ``run_chunk``, currently RWSADMM): the random-walk / zone schedule for a
  whole eval window is precomputed host-side, then the window runs as ONE
  compiled ``lax.scan`` executable; per-round metrics come back as stacked
  arrays with a single device→host sync per window. Same trajectories as
  eager (the schedule replays the eager driver's RNG draws), minus the
  per-round dispatch overhead that dominates wall-clock for small models.

Both engines emit ``round_metrics`` under one schema
(``fl.base.normalize_round_metrics`` / ``validate_round_metrics``):
every entry has at least ``round`` and ``comm_bytes``, plus whatever the
trainer adds (``train_loss``, ``kappa``, wireless ``latency_s`` /
``energy_j`` from the scenario subsystem, …) — key sets are identical
between engines for the same trainer (asserted in
``tests/test_scan_driver.py``).

``scenario=`` overrides the trainer's environment (a name from the
``scenarios`` registry or a ``ScenarioConfig``) before the run starts.

``telemetry=`` (a ``repro.telemetry.TelemetryRun``, default ``None``)
records the run: manifest config, per-round ``round`` events, the
walk/zone ``visit`` trace, eval ``snapshot`` events, and fenced
``phase`` spans (``schedule`` / ``scan_chunk`` / ``readback`` /
``eval`` / ``round_eager``), written out at the end of the call.
Telemetry never touches an RNG stream or adds device
syncs beyond the fences the drivers already imply, so telemetry-on
trajectories are bit-identical to telemetry-off (pinned in
``tests/test_telemetry.py``). Render a recorded run with
``python -m repro.telemetry.report runs/<id>``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import numpy as np

from ..telemetry import (
    maybe_trace,
    telemetry_print,
    visit_events_from_round,
    visit_events_from_schedule,
)
from .base import TrainerBase, normalize_round_metrics


@dataclasses.dataclass
class SimulationResult:
    algo: str
    history: list[dict]             # eval snapshots (sparse, every eval_every)
    round_metrics: list[dict]       # per-round metrics (train loss etc.)
    final: dict                     # last eval snapshot
    total_comm_bytes: int
    wall_time_s: float
    total_latency_s: float = 0.0    # wireless cost totals (0 when the
    total_energy_j: float = 0.0     # trainer prices no scenario comm)

    def curve(self, key: str = "acc") -> tuple[np.ndarray, np.ndarray]:
        rounds = np.array([h["round"] for h in self.history])
        vals = np.array([h.get(key, np.nan) for h in self.history])
        return rounds, vals


def _snapshot(trainer, state, rnd: int, total_comm: int,
              history: list[dict], verbose: bool, tag: str,
              telemetry=None) -> None:
    """Eval the current state and append the snapshot (shared by both
    engines so the history shape can never diverge between them)."""
    with trainer._phase("eval", round=rnd):
        snap = trainer.evaluate(state)
    snap["round"] = rnd
    snap["comm_bytes_total"] = total_comm
    history.append(snap)
    if telemetry is not None:
        telemetry.snapshot(snap)
    if verbose:
        # Not every trainer evaluates accuracy (eval-disabled baselines
        # omit "acc" entirely) — format what the snapshot actually has.
        acc = snap.get("acc")
        acc_s = f"acc={acc:.4f}  " if acc is not None else ""
        telemetry_print(f"[{tag}] round {rnd:4d}  {acc_s}"
                        f"comm={total_comm / 1e6:.1f}MB")


def _result(trainer, history, round_metrics, total_comm,
            wall: float) -> SimulationResult:
    return SimulationResult(
        algo=trainer.name,
        history=history,
        round_metrics=round_metrics,
        final=history[-1] if history else {},
        total_comm_bytes=total_comm,
        wall_time_s=wall,
        total_latency_s=float(sum(m.get("latency_s", 0.0)
                                  for m in round_metrics)),
        total_energy_j=float(sum(m.get("energy_j", 0.0)
                                 for m in round_metrics)),
    )


def _finalize_telemetry(telemetry, result: SimulationResult) -> None:
    telemetry.counter("total_comm_bytes", result.total_comm_bytes)
    telemetry.counter("total_latency_s", result.total_latency_s)
    telemetry.counter("total_energy_j", result.total_energy_j)
    telemetry.counter("wall_time_s", round(result.wall_time_s, 6))
    telemetry.flush()


def run_simulation(
    trainer: TrainerBase,
    *,
    rounds: int = 100,
    eval_every: int = 10,
    seed: int = 0,
    verbose: bool = False,
    engine: str = "eager",
    scenario=None,
    telemetry=None,
) -> SimulationResult:
    if scenario is not None:
        trainer.attach_scenario(scenario, seed=seed)
    if telemetry is not None:
        trainer.set_telemetry(telemetry)
        telemetry.update_manifest(config={
            "algo": trainer.name, "engine": engine, "rounds": rounds,
            "eval_every": eval_every, "sim_seed": seed,
            "n_clients": trainer.n_clients,
        })
        if telemetry.manifest.get("seed") is None:
            telemetry.update_manifest(seed=seed)
    if engine != "eager":
        return _run_simulation_scan(
            trainer, rounds=rounds, eval_every=eval_every, seed=seed,
            verbose=verbose, engine=engine, telemetry=telemetry,
        )
    rng = np.random.default_rng(seed)
    with trainer._phase("init_state") as sp:
        state = trainer.init_state(jax.random.PRNGKey(seed))
        if telemetry is not None:
            sp.fence(state)
    history: list[dict] = []
    round_metrics: list[dict] = []
    total_comm = 0
    t0 = time.perf_counter()
    with maybe_trace(telemetry):
        for r in range(rounds):
            with trainer._phase("round_eager", round=r):
                state, metrics = trainer.round(state, r, rng)
            metrics = normalize_round_metrics(metrics, r)
            total_comm += int(metrics["comm_bytes"])
            round_metrics.append(metrics)
            if telemetry is not None:
                telemetry.round(metrics)
                for v in visit_events_from_round(metrics):
                    telemetry.visit(**v)
            if (r + 1) % eval_every == 0 or r == rounds - 1:
                _snapshot(trainer, state, r + 1, total_comm, history,
                          verbose, trainer.name, telemetry)
    wall = time.perf_counter() - t0
    result = _result(trainer, history, round_metrics, total_comm, wall)
    if telemetry is not None:
        _finalize_telemetry(telemetry, result)
    return result


def _run_simulation_scan(
    trainer: Any,
    *,
    rounds: int,
    eval_every: int,
    seed: int,
    verbose: bool,
    engine: str,
    telemetry=None,
) -> SimulationResult:
    """Chunked scan driver: one compiled executable per eval window."""
    if not (hasattr(trainer, "schedule") and hasattr(trainer, "run_chunk")
            and hasattr(trainer, "chunk_round_metrics")):
        raise ValueError(
            f"trainer {trainer.name!r} has no scan driver (needs "
            ".schedule/.run_chunk/.chunk_round_metrics); "
            "use engine='eager'")
    rng = np.random.default_rng(seed)
    with trainer._phase("init_state") as sp:
        state = trainer.init_state(jax.random.PRNGKey(seed))
        if telemetry is not None:
            sp.fence(state)
    history: list[dict] = []
    round_metrics: list[dict] = []
    total_comm = 0
    # Async prefetch (lazy plane, opt-in): while one window's compiled
    # scan executes on device, the host precomputes the NEXT window's
    # schedule and hands its ids to the store's staging thread, so the
    # following ensure() starts from pre-materialized rows. Schedule
    # draws stay in exactly the same rng order (windows are scheduled
    # strictly left to right; metrics/eval consume no rng), so
    # prefetch-on trajectories are bit-identical to prefetch-off
    # (pinned in tests/test_lazy_plane.py).
    prefetching = (getattr(trainer, "store", None) is not None
                   and trainer.store.prefetch_enabled
                   and hasattr(trainer, "prefetch_chunk"))
    sched = None
    t0 = time.perf_counter()
    r = 0
    with maybe_trace(telemetry):
        while r < rounds:
            # Align chunks to eval boundaries so snapshots land on the
            # same rounds as the eager driver.
            r_next = min(((r // eval_every) + 1) * eval_every, rounds)
            if sched is None:   # not handed over by a prefetch iteration
                with trainer._phase("schedule", round=r,
                                    chunk_rounds=r_next - r):
                    sched = trainer.schedule(r_next - r, rng,
                                             start_round=r)
            sched_next = None
            with trainer._phase("scan_chunk", round=r, engine=engine,
                                chunk_rounds=r_next - r,
                                includes_compile=trainer.chunk_is_cold(
                                    engine, r_next - r)) as sp:
                state, stacked = trainer.run_chunk(state, sched,
                                                   engine=engine)
                if prefetching and r_next < rounds:
                    # The chunk is dispatched (async) — overlap the next
                    # window's host work behind it, then fence.
                    r_nn = min(((r_next // eval_every) + 1) * eval_every,
                               rounds)
                    with trainer._phase("schedule", round=r_next,
                                        chunk_rounds=r_nn - r_next):
                        sched_next = trainer.schedule(
                            r_nn - r_next, rng, start_round=r_next)
                    trainer.prefetch_chunk(sched_next)
                if telemetry is not None:
                    sp.fence((state, stacked))
            # The trainer rebuilds the per-round metric entries (one
            # device→host sync per window): single-walker and fleet
            # schedules carry different columns (active walker, K zones,
            # per-walker pricing), so the schema lives with the trainer.
            with trainer._phase("readback", round=r,
                                chunk_rounds=r_next - r):
                entries = [normalize_round_metrics(e, r + j) for j, e in
                           enumerate(trainer.chunk_round_metrics(
                               sched, stacked, r))]
            for entry in entries:
                total_comm += int(entry["comm_bytes"])
                round_metrics.append(entry)
            if telemetry is not None:
                # Walk/zone trace: one vectorized pass over the chunk's
                # already-materialized host schedule arrays.
                for entry in entries:
                    telemetry.round(entry)
                for v in visit_events_from_schedule(sched, r, entries):
                    telemetry.visit(**v)
            r = r_next
            sched = sched_next
            if r % eval_every == 0 or r == rounds:
                _snapshot(trainer, state, r, total_comm, history, verbose,
                          f"{trainer.name}/{engine}", telemetry)
    wall = time.perf_counter() - t0
    result = _result(trainer, history, round_metrics, total_comm, wall)
    if telemetry is not None:
        _finalize_telemetry(telemetry, result)
    return result
