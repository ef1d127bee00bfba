"""Shared benchmark fixtures: datasets, trainer builders, CSV/JSON
helpers, and the large-n control-plane probe."""
from __future__ import annotations

import os
import resource
import sys
import time

import numpy as np

from repro.baselines import (
    APFLTrainer,
    DittoTrainer,
    FedAvgTrainer,
    PerFedAvgTrainer,
    PFedMeTrainer,
    WalkmanTrainer,
)
from repro.core.rwsadmm import RWSADMMHparams
from repro.data import (
    make_image_dataset,
    make_synthetic_lr,
    pathological_split,
)
from repro.data.loader import build_federated, build_federated_from_pairs
from repro.fl.base import to_device_data
from repro.fl.rwsadmm_trainer import RWSADMMTrainer


def mnist_like_fed(n_clients: int = 20, n_samples: int = 3000,
                   seed: int = 0):
    imgs, labels = make_image_dataset(n_samples, seed=seed)
    idx = pathological_split(labels, n_clients, seed=seed)
    return to_device_data(build_federated(imgs, labels, idx)), (28, 28, 1)


def cifar_like_fed(n_clients: int = 20, n_samples: int = 3000,
                   seed: int = 0):
    imgs, labels = make_image_dataset(
        n_samples, shape=(32, 32, 3), noise=0.6, seed=seed)
    idx = pathological_split(labels, n_clients, seed=seed)
    return to_device_data(build_federated(imgs, labels, idx)), (32, 32, 3)


def synthetic_fed(n_clients: int = 50, seed: int = 0):
    pairs = make_synthetic_lr(n_clients, seed=seed)
    return to_device_data(build_federated_from_pairs(pairs)), (60,)


def make_trainer(algo: str, model, data, *, beta: float = 1.0,
                 kappa: float = 0.001, zone: int = 8, seed: int = 0):
    if algo == "rwsadmm":
        return RWSADMMTrainer(
            model, data,
            RWSADMMHparams(beta=beta, kappa=kappa, epsilon=1e-5),
            zone_size=zone, batch_size=32, seed=seed)
    if algo == "rwsadmm_cf":
        return RWSADMMTrainer(
            model, data, RWSADMMHparams(beta=10.0, kappa=kappa,
                                        epsilon=1e-5),
            zone_size=zone, solver="closed_form", seed=seed)
    cls = {
        "fedavg": FedAvgTrainer, "perfedavg": PerFedAvgTrainer,
        "pfedme": PFedMeTrainer, "ditto": DittoTrainer,
        "apfl": APFLTrainer,
    }.get(algo)
    if cls is not None:
        return cls(model, data, clients_per_round=min(10, data.n_clients))
    if algo == "walkman":
        return WalkmanTrainer(model, data, beta=3.0, seed=seed)
    raise ValueError(algo)


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")


# ---------------------------------------------------------------------------
# Machine-readable trajectory: BENCH_scaling.json at the repo root.
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(REPO_ROOT, "BENCH_scaling.json")


def reset_peak_rss() -> None:
    """Reset the kernel's peak-RSS watermark (Linux ``clear_refs``) so
    each benchmark phase records ITS OWN peak instead of inheriting the
    process-wide high-water mark of whatever ran before it in the same
    harness process. Best-effort: silently a no-op where unsupported
    (then peaks are monotone across phases — still an upper bound)."""
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5")
    except OSError:
        pass


_run_peak_mb = 0.0


def peak_rss_mb() -> float:
    """Peak resident set in MB since the last :func:`reset_peak_rss`
    (VmHWM on Linux; falls back to ``ru_maxrss``, which is KB on Linux
    and bytes on macOS) — the peak-memory column of the scaling
    benchmarks. Every observation also feeds :func:`run_peak_rss_mb`."""
    global _run_peak_mb
    mb = None
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    mb = int(line.split()[1]) / 1024.0
                    break
    except OSError:
        pass
    if mb is None:
        import sys

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        mb = ru / (1024.0 ** 2) if sys.platform == "darwin" \
            else ru / 1024.0
    _run_peak_mb = max(_run_peak_mb, mb)
    return mb


def run_peak_rss_mb() -> float:
    """Max over every :func:`peak_rss_mb` observation this process —
    what a memory GATE should assert on: per-phase watermark resets
    make :func:`peak_rss_mb` report only the most recent phase, so
    asserting on the last reading would let an earlier phase's blow-up
    slip through."""
    return _run_peak_mb


def runtime_stamp() -> dict:
    """The jax runtime columns every bench row carries: backend name,
    visible device count, and the mesh the row ran under (``None`` =
    unsharded; sharded rows overwrite it with e.g. ``"data:8"``). Rows
    are only comparable across PRs within one runtime shape — these
    columns make that shape diffable."""
    try:
        import jax

        return {"jax_backend": jax.default_backend(),
                "device_count": int(jax.device_count()),
                "mesh": None}
    except Exception:   # jax-free tooling contexts
        return {"jax_backend": None, "device_count": None, "mesh": None}


def bench_row(name: str, *, n: int, engine: str, us_per_round: float,
              k: int = 1, **extra) -> dict:
    """One BENCH_scaling.json record (schema: name, n, K, engine,
    us_per_round, peak_rss_mb, jax_backend, device_count, mesh +
    free-form extras)."""
    row = {"name": name, "n": int(n), "K": int(k), "engine": engine,
           "us_per_round": round(float(us_per_round), 1),
           "peak_rss_mb": round(peak_rss_mb(), 1)}
    row.update(runtime_stamp())
    row.update(extra)
    return row


def backfill_bench_rows(path: str | None = None) -> str:
    """One-off migration: re-emit every existing BENCH_scaling.json row
    through the atomic writer with the :func:`runtime_stamp` columns
    backfilled. Historical rows all ran single-device CPU, so missing
    columns get exactly that; rows that already carry the columns are
    untouched."""
    from repro.telemetry import atomic_write_json, load_bench_rows

    path = path or BENCH_JSON
    rows = load_bench_rows(path)
    for r in rows:
        r.setdefault("jax_backend", "cpu")
        r.setdefault("device_count", 1)
        r.setdefault("mesh", None)
    return atomic_write_json(path, rows)


def ensure_multidevice_harness(count: int, module: str) -> None:
    """Olmax-style multi-device CPU harness (SNIPPETS §1–2): make this
    bench process see ``count`` host platform devices and run under
    tcmalloc. Only for a process that already runs on the CPU
    (``JAX_PLATFORMS=cpu``); raises otherwise. Call FIRST THING in
    ``main()`` — the XLA flag only takes effect before the jax backend
    initializes. tcmalloc can only load at process start, so when the
    library exists but is not preloaded the process re-execs itself ONCE
    (``python -m module argv…``) with the full env from
    ``launch.hostdevices``."""
    from repro.launch.hostdevices import (
        ensure_host_platform_devices,
        host_device_env,
    )

    env = host_device_env(count)     # raises unless JAX_PLATFORMS=cpu
    ensure_host_platform_devices(count)
    want = env.get("LD_PRELOAD")
    if (want and want != os.environ.get("LD_PRELOAD")
            and os.environ.get("_REPRO_BENCH_REEXEC") != "1"):
        env["_REPRO_BENCH_REEXEC"] = "1"
        sys.stdout.flush()
        sys.stderr.flush()
        os.execve(sys.executable,
                  [sys.executable, "-m", module] + sys.argv[1:], env)


def write_bench_rows(rows: list[dict], path: str | None = None) -> str:
    """Merge rows into ``BENCH_scaling.json`` keyed by
    ``(name, n, K, engine)`` (so partial benchmark runs update their own
    rows without clobbering the rest) and return the path. The write
    goes through the telemetry artifacts layer — temp file +
    ``os.replace`` — so an interrupted bench can never truncate the
    repo-root trajectory file. The CSV on stdout stays the human view;
    this file is the diffable perf trajectory across PRs."""
    from repro.telemetry import (
        atomic_write_json,
        load_bench_rows,
        merge_bench_rows,
    )

    path = path or BENCH_JSON
    merged = merge_bench_rows(load_bench_rows(path), rows)
    return atomic_write_json(path, merged)


def control_plane_rate(n: int, rounds: int = 64, *,
                       mobility: str = "gauss_markov",
                       backend: str = "sparse", dropout: bool = True,
                       k_max: int = 32, zone_size: int = 8,
                       target_degree: float = 12.0,
                       rollout_chunk: int | None = 32,
                       seed: int = 0) -> float:
    """Seconds/round of pure control-plane work at scale: scenario
    rollout (mobility + link dropouts + churn-free), random-walk
    stepping, zone planning, key derivation, and wireless pricing — no
    training rounds. The radio range shrinks with n so the expected
    degree stays ~``target_degree`` (the physical regime the sparse
    backend targets: local radios, growing fleets)."""
    import dataclasses

    from repro.core import markov
    from repro.core.markov import RandomWalkServer
    from repro.scenarios import (
        LinkConfig,
        MobilityConfig,
        Scenario,
        ScenarioConfig,
    )

    reset_peak_rss()
    radio = float(np.sqrt(target_degree / (np.pi * n)))
    cfg = ScenarioConfig(
        name=f"bench_{mobility}_{backend}",
        mobility=MobilityConfig(model=mobility, radio_range=radio),
        links=LinkConfig(enabled=dropout, dropout=dropout),
        graph_backend=backend, neighbor_k_max=k_max)
    if rollout_chunk is not None:
        cfg = dataclasses.replace(cfg, rollout_chunk=rollout_chunk)
    scenario = Scenario(n, cfg, seed=seed)
    walker = RandomWalkServer(seed=seed + 1)
    walker.reset(scenario.current())
    rng = np.random.default_rng(seed)

    def price(graphs, clients, idx, mask):
        return scenario.price_schedule(graphs, clients, idx, mask, 2048)

    t0 = time.perf_counter()
    markov.zone_schedule(scenario, walker, rounds, zone_size, rng,
                         price=price)
    return (time.perf_counter() - t0) / rounds
