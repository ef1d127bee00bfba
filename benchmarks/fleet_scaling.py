"""Fleet scaling: eager vs scan round throughput for K mobile servers.

The fleet extension (multiple walkers, ``fl.fleet_trainer``) is the
repo's beyond-paper scalability workload; this benchmark measures the
compiled fleet driver's win over the eager per-round loop at K ∈
{1, 3, 5} walkers, both fleet modes:

  roundrobin   — one zone per round, walkers take turns (K× coverage
                 per wall step at single-walker round cost),
  simultaneous — K zones per wall step through the batched multi-zone
                 kernel (K× zone throughput per round).

Timed region for the scan engines includes schedule precomputation
(graphs, K random walks, zone plans, sync mask, keys, pricing) — the
honest end-to-end cost per chunk. Also reports the fleet hitting time
(wall steps until the union of walker visits covers every client) next
to a single walker's, the ~K× coverage claim. Emits CSV rows:

  fleet_scaling/{mode}/n{N}/K{K}/{engine},{us_per_round},rounds_per_s=...
  fleet_scaling/{mode}/n{N}/K{K}/speedup,...,scan_vs_eager=...x

Rows are also written machine-readably (name, n, K, engine,
us_per_round, peak_rss_mb) into BENCH_scaling.json at the repo root —
the diffable perf trajectory across PRs.

Smoke (CI, < 2 min):  python -m benchmarks.fleet_scaling --smoke
Full:                 python -m benchmarks.fleet_scaling
(full run covers the acceptance bar: scan ≥ 5× eager at n=100, K=3.)
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.core.rwsadmm import RWSADMMHparams
from repro.fl.fleet_trainer import FleetRWSADMMTrainer
from repro.fl.rwsadmm_trainer import ENGINES
from repro.launch.compile_cache import enable_compile_cache
from repro.models.small import get_model

from .common import (
    bench_row,
    emit,
    reset_peak_rss,
    synthetic_fed,
    write_bench_rows,
)


def make_fleet(n_clients: int, k: int, mode: str,
               seed: int = 0) -> FleetRWSADMMTrainer:
    data, shape = synthetic_fed(n_clients, seed=seed)
    model = get_model("mlr", shape)
    return FleetRWSADMMTrainer(
        model, data, RWSADMMHparams(beta=10.0, kappa=0.001, epsilon=1e-5),
        n_walkers=k, sync_every=10, fleet_mode=mode,
        zone_size=8, batch_size=20, solver="closed_form", seed=seed,
    )


def bench_engine(trainer: FleetRWSADMMTrainer, engine: str,
                 rounds: int) -> float:
    """Measured rounds/sec (after a warmup pass that compiles)."""
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if engine == "eager":
        state, _ = trainer.round(state, 0, rng)          # compile
        jax.block_until_ready(state.base.server.y)
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            state, _ = trainer.round(state, r, rng)
        jax.block_until_ready(state.base.server.y)
        dt = time.perf_counter() - t0
    else:
        sched = trainer.schedule(rounds, rng, start_round=0)
        state, _ = trainer.run_chunk(state, sched, engine=engine)
        jax.block_until_ready(state.base.server.y)       # compile
        t0 = time.perf_counter()
        sched = trainer.schedule(rounds, rng, start_round=rounds)
        state, stacked = trainer.run_chunk(state, sched, engine=engine)
        jax.block_until_ready(stacked["train_loss"])
        dt = time.perf_counter() - t0
    return rounds / dt


def hitting_times(n_clients: int, walkers=(1, 3, 5),
                  rounds: int = 4000) -> dict:
    """Fleet wall-clock hitting time vs K (the ~K× coverage claim).
    Walk-only: steps the schedules without training rounds."""
    out: dict = {}
    for k in walkers:
        trainer = make_fleet(n_clients, k, "simultaneous")
        # Walk-only: step every walker through the graph schedule
        # directly (same per-walker streams as a full fleet schedule)
        # without paying zone planning / pricing / key materialization.
        graphs = trainer.dyn_graph.schedule(rounds, include_current=True)
        for w in trainer.walkers:
            w.walk_schedule(graphs[1:], advance_first=True)
        t = trainer.fleet_hitting_time()
        out[k] = t
        emit(f"fleet_scaling/hitting_time/n{n_clients}/K{k}",
             0.0, f"wall_steps={t}")
    return out


def run(rounds: int, clients, walkers, modes) -> dict:
    results: dict = {}
    json_rows = []
    for mode in modes:
        for n in clients:
            for k in walkers:
                per_engine: dict = {}
                for engine in ENGINES:
                    reset_peak_rss()
                    trainer = make_fleet(n, k, mode)
                    rps = bench_engine(trainer, engine, rounds)
                    per_engine[engine] = rps
                    name = f"fleet_scaling/{mode}/n{n}/K{k}/{engine}"
                    emit(name, 1e6 / rps, f"rounds_per_s={rps:.1f}")
                    json_rows.append(bench_row(
                        name, n=n, k=k, engine=engine,
                        us_per_round=1e6 / rps, mode=mode))
                speed = per_engine["scan"] / per_engine["eager"]
                speed_f = per_engine["scan_fused"] / per_engine["eager"]
                emit(f"fleet_scaling/{mode}/n{n}/K{k}/speedup", 0.0,
                     f"scan_vs_eager={speed:.1f}x "
                     f"scan_fused_vs_eager={speed_f:.1f}x")
                results[(mode, n, k)] = per_engine
    write_bench_rows(json_rows)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=200,
                    help="timed rounds per engine (after compile warmup)")
    ap.add_argument("--clients", type=int, nargs="+", default=[100])
    ap.add_argument("--walkers", type=int, nargs="+", default=[1, 3, 5])
    ap.add_argument("--modes", nargs="+",
                    default=["roundrobin", "simultaneous"])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: short run, exits nonzero unless "
                    "scan beats eager at every K")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.smoke:
        results = run(rounds=40, clients=(40,), walkers=(1, 3, 5),
                      modes=("roundrobin",))
        hitting_times(40, walkers=(1, 3, 5), rounds=600)
        bad = [key for key, eng in results.items()
               if eng["scan"] <= eng["eager"]]
        if bad:
            print(f"FAIL: scan did not beat eager at {bad}",
                  file=sys.stderr)
            sys.exit(1)
        return
    run(rounds=args.rounds, clients=tuple(args.clients),
        walkers=tuple(args.walkers), modes=tuple(args.modes))
    hitting_times(max(args.clients), walkers=tuple(args.walkers))


if __name__ == "__main__":
    main()
