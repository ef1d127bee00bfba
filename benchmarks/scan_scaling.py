"""Rounds-per-second scaling: eager vs scan vs scan_fused drivers.

The paper's headline claims (fast convergence, O(1) communication,
scalability) are wall-clock claims at thousands-of-rounds scale; this
benchmark measures the simulator's round throughput at n ∈ {20, 100, 500}
clients for the three RWSADMM execution engines:

  eager      — one XLA dispatch + one host sync per round (seed driver),
  scan       — whole chunk of R rounds as ONE lax.scan executable,
  scan_fused — scan + the masked multi-client Pallas zone kernel.

Timed region for scan engines includes the host-side schedule
precomputation (graphs, random walk, zone padding, keys) — the honest
end-to-end cost per chunk. Emits CSV rows:

  scan_scaling/n{N}/{engine},{us_per_round},rounds_per_s=...
  scan_scaling/n{N}/speedup,...,scan_vs_eager=...x

A second, large-n section measures the **control plane alone** (the
64-round mobility + link-dropout rollout, walk, zone planning, pricing)
at n ∈ {2000, 10000, 50000} on the sparse neighbor-list backend — the
O(n·k) lane that unblocked these sizes (the dense lane is measured at
the smallest n for reference; beyond that it is memory-blocked):

  scan_scaling/control_plane/n{N}/{backend},{us_per_round},peak_rss_mb=...

Both sections also write machine-readable rows (name, n, K, engine,
us_per_round, peak_rss_mb) into BENCH_scaling.json at the repo root, so
perf regressions are diffable across PRs.

A third section (``--lazy``) runs full TRAINING rounds at n ∈ {100k, 1M}
on the lazy client plane — bounded LRU client store + on-demand dataset
materialization (docs/performance.md §7) over the sparse control plane —
and certifies the bounded footprint via the peak_rss_mb column:

  scan_scaling/lazy_plane/n{N}/scan,{us_per_round},peak_rss_mb=...

Smoke (CI, <2 min):  python -m benchmarks.scan_scaling --rounds 30 \
    --clients 20 --no-control-plane
Sparse smoke (CI):   python -m benchmarks.scan_scaling --control-plane \
    --cp-clients 10000 --assert-rss-mb 1024
Lazy smoke (CI):     python -m benchmarks.scan_scaling --lazy \
    --lazy-clients 100000 --assert-rss-mb 2048
Full:                python -m benchmarks.scan_scaling && \
    python -m benchmarks.scan_scaling --lazy
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

from repro.core.rwsadmm import RWSADMMHparams
from repro.fl.rwsadmm_trainer import ENGINES, RWSADMMTrainer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.small import get_model

from .common import (
    bench_row,
    control_plane_rate,
    emit,
    peak_rss_mb,
    reset_peak_rss,
    run_peak_rss_mb,
    synthetic_fed,
    write_bench_rows,
)


def make_trainer(n_clients: int, seed: int = 0) -> RWSADMMTrainer:
    # The paper's Synthetic(0.5, 0.5) MLR setting (§5): the strongly
    # convex workload whose per-round compute is small enough that the
    # eager loop is dispatch-bound — the regime the scan driver targets.
    data, shape = synthetic_fed(n_clients, seed=seed)
    model = get_model("mlr", shape)
    return RWSADMMTrainer(
        model, data, RWSADMMHparams(beta=10.0, kappa=0.001, epsilon=1e-5),
        zone_size=8, batch_size=20, solver="closed_form", seed=seed,
    )


def bench_engine(trainer: RWSADMMTrainer, engine: str, rounds: int) -> float:
    """Returns measured rounds/sec (after a warmup pass that compiles)."""
    state = trainer.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if engine == "eager":
        state, _ = trainer.round(state, 0, rng)          # compile
        jax.block_until_ready(state.server.y)
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            state, _ = trainer.round(state, r, rng)
        jax.block_until_ready(state.server.y)
        dt = time.perf_counter() - t0
    else:
        sched = trainer.schedule(rounds, rng, start_round=0)
        state, _ = trainer.run_chunk(state, sched, engine=engine)  # compile
        jax.block_until_ready(state.server.y)
        t0 = time.perf_counter()
        sched = trainer.schedule(rounds, rng, start_round=rounds)
        state, stacked = trainer.run_chunk(state, sched, engine=engine)
        jax.block_until_ready(stacked["train_loss"])
        dt = time.perf_counter() - t0
    return rounds / dt


def run(rounds: int = 200, clients=(20, 100, 500)) -> dict:
    """Prints CSV rows; returns {n: {engine: rounds_per_s}}."""
    results: dict = {}
    json_rows = []
    for n in clients:
        per_engine: dict = {}
        for engine in ENGINES:
            reset_peak_rss()
            trainer = make_trainer(n)
            rps = bench_engine(trainer, engine, rounds)
            per_engine[engine] = rps
            emit(f"scan_scaling/n{n}/{engine}", 1e6 / rps,
                 f"rounds_per_s={rps:.1f}")
            json_rows.append(bench_row(
                f"scan_scaling/n{n}/{engine}", n=n, engine=engine,
                us_per_round=1e6 / rps))
        speed = per_engine["scan"] / per_engine["eager"]
        speed_f = per_engine["scan_fused"] / per_engine["eager"]
        emit(f"scan_scaling/n{n}/speedup", 0.0,
             f"scan_vs_eager={speed:.1f}x "
             f"scan_fused_vs_eager={speed_f:.1f}x")
        results[n] = per_engine
    write_bench_rows(json_rows)
    return results


def control_plane(clients=(2000, 10000, 50000), rounds: int = 64,
                  dense_reference: bool = True) -> dict:
    """Large-n control-plane columns on the sparse neighbor-list
    backend (+ a dense reference at the smallest n, chunked so its
    (R, n, n) stacks stay bounded). Returns {(n, backend): s_per_round}
    and appends rows to BENCH_scaling.json."""
    results: dict = {}
    json_rows = []
    todo = [(n, "sparse") for n in clients]
    if dense_reference and clients:
        # Dense last: its multi-GB footprint stays out of the sparse
        # rows even where the per-phase watermark reset (clear_refs)
        # is unavailable and peaks are monotone across phases.
        todo.append((min(clients), "dense"))
    for n, backend in todo:
        kw = {"rollout_chunk": 8} if backend == "dense" else {}
        sec = control_plane_rate(n, rounds=rounds, backend=backend, **kw)
        name = f"scan_scaling/control_plane/n{n}/{backend}"
        emit(name, sec * 1e6,
             f"rounds_per_s={1.0 / sec:.1f} "
             f"peak_rss_mb={peak_rss_mb():.0f}")
        json_rows.append(bench_row(name, n=n, engine=backend,
                                   us_per_round=sec * 1e6,
                                   rounds=rounds))
        results[(n, backend)] = sec
    write_bench_rows(json_rows)
    return results


def lazy_plane_trainer(n: int, capacity: int = 1024, *, mesh=None,
                       prefetch: bool = False) -> RWSADMMTrainer:
    """The lazy-plane training workload at population ``n``: the paper's
    Synthetic(0.5, 0.5) MLR clients behind a ``ClientDataFactory``, a
    bounded ``capacity``-slot LRU store, and the sparse gauss-markov +
    link-dropout scenario with the radio range shrunk so the expected
    degree stays ~12. ``mesh`` (an ``FLSharding``) shards the packed
    store rows over its "data" axis."""
    import dataclasses as _dc

    from repro.data import synthetic_lr_factory
    from repro.scenarios import (
        LinkConfig,
        MobilityConfig,
        ScenarioConfig,
    )

    # Narrower count tail than the paper default (mean_samples 2.0 vs
    # 4.0): packed store rows are max_train wide, and one 1-in-a-million
    # lognormal straggler would pad every slot's row.
    factory = synthetic_lr_factory(
        n_clients=n, seed=0, min_samples=20, mean_samples=2.0)
    model = get_model("mlr", (60,))
    radio = float(np.sqrt(12.0 / (np.pi * n)))
    cfg = ScenarioConfig(
        name="bench_lazy_gm_sparse",
        mobility=MobilityConfig(model="gauss_markov", radio_range=radio),
        links=LinkConfig(enabled=True, dropout=True),
        graph_backend="sparse", neighbor_k_max=32)
    # Small rollout chunks: the (chunk, n, k_max) neighbor-list stacks
    # are the biggest transient at n = 10⁶ (≈0.5 GB each at chunk 8) —
    # the store itself stays capacity-bounded.
    cfg = _dc.replace(cfg, rollout_chunk=8)
    return RWSADMMTrainer(
        model, factory,
        RWSADMMHparams(beta=10.0, kappa=0.001, epsilon=1e-5),
        zone_size=8, batch_size=20, solver="closed_form",
        scenario=cfg, seed=0, store_capacity=capacity,
        prefetch=prefetch, mesh=mesh)


def lazy_plane(clients=(100_000, 1_000_000), rounds: int = 32,
               capacity: int = 1024, *, shard_devices: int | None = None,
               prefetch: bool = False) -> dict:
    """Full TRAINING rounds at n up to 10⁶ on the lazy client plane:
    bounded LRU store + on-demand dataset materialization + sparse
    control plane, scan engine. The dense plane would need the (n, …)
    client stack and the (n, m, d) dataset stack — ~300 GB at n = 10⁶
    for this workload — while the lazy plane's footprint is set by
    ``capacity`` (store rows) plus the O(n·k) control plane, which is
    what the ``peak_rss_mb`` column certifies. Returns {n: s_per_round}
    and appends rows to BENCH_scaling.json.

    ``shard_devices``: place the packed store over a "data" mesh of
    that many devices — row tag ``scan_shard{d}``. ``prefetch``: stage
    each next chunk's dataset rows on a host thread while the current
    chunk executes (row tag suffix ``_prefetch``); the timed region
    then pipelines schedule → prefetch → chunk exactly like
    ``run_simulation`` does."""
    results: dict = {}
    json_rows = []
    for n in clients:
        reset_peak_rss()
        mesh = None
        if shard_devices:
            from repro.fl.sharding import FLSharding

            mesh = FLSharding(n_devices=shard_devices)
        trainer = lazy_plane_trainer(n, capacity, mesh=mesh,
                                     prefetch=prefetch)
        state = trainer.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        sched = trainer.schedule(rounds, rng, start_round=0)
        state, _ = trainer.run_chunk(state, sched, engine="scan")
        jax.block_until_ready(trainer.global_params(state))
        # Timed region: TWO pipelined chunks (schedule + ensure + scan),
        # the steady-state structure run_simulation drives — with
        # prefetch the next window's schedule/staging hides behind the
        # executing chunk, without it each window schedules up front.
        n_chunks, sched_next, r0 = 2, None, rounds
        t0 = time.perf_counter()
        for w in range(n_chunks):
            if sched_next is None:
                sched_next = trainer.schedule(rounds, rng, start_round=r0)
            sched, sched_next = sched_next, None
            r0 += rounds
            state, stacked = trainer.run_chunk(state, sched,
                                               engine="scan")
            if prefetch and w + 1 < n_chunks:
                sched_next = trainer.schedule(rounds, rng,
                                              start_round=r0)
                trainer.prefetch_chunk(sched_next)
            jax.block_until_ready(stacked["train_loss"])
        sec = (time.perf_counter() - t0) / (n_chunks * rounds)
        c = trainer.store.counters
        tag = "scan" + (f"_shard{shard_devices}" if shard_devices
                        else "") + ("_prefetch" if prefetch else "")
        name = f"scan_scaling/lazy_plane/n{n}/{tag}"
        emit(name, sec * 1e6,
             f"rounds_per_s={1.0 / sec:.1f} "
             f"peak_rss_mb={peak_rss_mb():.0f} "
             f"resident={trainer.store.n_resident}/{capacity} "
             f"miss={c['misses']} evict={c['evictions']}")
        extra = {}
        if shard_devices:
            extra["mesh"] = f"data:{shard_devices}"
        if prefetch:
            extra["prefetch_hits"] = c["prefetch_hits"]
            extra["prefetch_misses"] = c["prefetch_misses"]
        json_rows.append(bench_row(
            name, n=n, engine="scan", us_per_round=sec * 1e6,
            rounds=(n_chunks + 1) * rounds, capacity=capacity,
            resident=trainer.store.n_resident,
            store_misses=c["misses"], store_evictions=c["evictions"],
            **extra))
        results[n] = sec
        del trainer, state, sched, stacked
    write_bench_rows(json_rows)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=200,
                    help="timed rounds per engine (after compile warmup)")
    ap.add_argument("--clients", type=int, nargs="+",
                    default=[20, 100, 500])
    ap.add_argument("--control-plane", action="store_true",
                    help="run ONLY the large-n control-plane columns")
    ap.add_argument("--no-control-plane", action="store_true",
                    help="skip the large-n control-plane columns")
    ap.add_argument("--cp-clients", type=int, nargs="+",
                    default=[2000, 10000, 50000],
                    help="control-plane client counts")
    ap.add_argument("--cp-rounds", type=int, default=64,
                    help="control-plane rollout window")
    ap.add_argument("--lazy", action="store_true",
                    help="run ONLY the lazy client-plane training rows")
    ap.add_argument("--lazy-clients", type=int, nargs="+",
                    default=[100_000, 1_000_000],
                    help="lazy-plane client counts")
    ap.add_argument("--lazy-rounds", type=int, default=32,
                    help="lazy-plane timed rounds (one scan chunk)")
    ap.add_argument("--lazy-capacity", type=int, default=1024,
                    help="lazy-plane store capacity (resident slots)")
    ap.add_argument("--shard-devices", type=int, default=None,
                    help="lazy plane: shard the packed store over this "
                    "many devices (under JAX_PLATFORMS=cpu: virtual host "
                    "devices, olmax-style harness, re-execs under "
                    "tcmalloc)")
    ap.add_argument("--prefetch", action="store_true",
                    help="lazy plane: async next-chunk dataset staging")
    ap.add_argument("--assert-rss-mb", type=float, default=None,
                    help="exit nonzero if peak RSS exceeds this (the "
                    "sparse-backend / lazy-plane CI memory gate)")
    args = ap.parse_args()
    if args.shard_devices and os.environ.get("JAX_PLATFORMS") == "cpu":
        from .common import ensure_multidevice_harness

        # CPU rehearsal: virtual host devices. Must precede the first
        # computation (backend init). On an accelerator the store shards
        # over the real devices, and FLSharding refuses a count the
        # host does not have.
        ensure_multidevice_harness(args.shard_devices,
                                   "benchmarks.scan_scaling")
    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.lazy:
        lazy_plane(clients=tuple(args.lazy_clients),
                   rounds=args.lazy_rounds,
                   capacity=args.lazy_capacity,
                   shard_devices=args.shard_devices,
                   prefetch=args.prefetch)
    else:
        if not args.control_plane:
            run(rounds=args.rounds, clients=tuple(args.clients))
        if args.control_plane or not args.no_control_plane:
            control_plane(clients=tuple(args.cp_clients),
                          rounds=args.cp_rounds,
                          dense_reference=not args.control_plane)
    if args.assert_rss_mb is not None:
        # Gate on the max over every measured phase, not the most
        # recent one (phases reset the kernel watermark) — and note the
        # dense reference phase alone needs several GB, so the gate is
        # meant for --control-plane runs (which skip it).
        peak_rss_mb()
        rss = run_peak_rss_mb()
        if rss > args.assert_rss_mb:
            print(f"FAIL: peak RSS {rss:.0f} MB > "
                  f"{args.assert_rss_mb:.0f} MB", file=sys.stderr)
            sys.exit(1)
        print(f"# peak RSS {rss:.0f} MB <= {args.assert_rss_mb:.0f} MB")


if __name__ == "__main__":
    main()
