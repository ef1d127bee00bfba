"""Smoke run of the RWSADMM training path on a TPU.

Drives ``run_simulation`` with ``RWSADMMTrainer`` and
``FleetRWSADMMTrainer`` at the full width of the paper CNN (1 068 266
parameters, (32, 32, 3) inputs, 10 classes), then the lazy client plane
at n = 10⁵, and checks the engines against each other:

  cnn_dense  n = 100 clients over 50 000 synthetic images with the
             pathological 2-labels-per-client split, zone 8, seed 0.
             prox_sgd under scan (the accuracy path), then closed_form
             under eager, scan and scan_fused; scan_fused must run the
             Pallas zone kernel through Mosaic (``tpu_custom_call``).
  fleet_sim  K = 3 simultaneous walkers, closed_form, same CNN and
             clients, scan vs scan_fused (the multi-zone kernel).
  lazy       n = 10⁵ clients behind the bounded LRU store (capacity
             1024), sparse gauss-markov + dropout, two 8-round chunks.

Timings, the process's peak device bytes and the engine differences are
printed on earlier lines; they are informational, not a benchmark. The
last line is ``{"ok": true, "device": {...}}``. Any failed phase or check
exits nonzero, and with no TPU the script exits nonzero before running
anything.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # only: lazy plane sharded over four
                                     # devices vs one device

The phase functions take their sizes, so the tests rehearse them at a
tiny size on CPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

#: engine-equivalence limits on whole runs (float32; the engines fuse
#: and reorder the Eq. 31 fold differently, and training amplifies the
#: last-bit differences): |Δ train_loss| / max(1, |loss|) per round and
#: |Δ personalized acc| at each eval
LOSS_RTOL = 1e-3
ACC_TOL = 1e-2
#: one fused update vs the jnp oracle on N(0, 1) float32 rows
KERNEL_TOL = 1e-5
#: closed-form ADMM penalty β for the CNN (step 1/β). At β = 10, the
#: MLR setting, the CNN's closed-form step diverges at n = 100: train
#: losses reach 1e4 on CPU and ~1e9 on a v5e, where no engine
#: comparison means anything. β = 100 keeps them bounded.
CNN_CF_BETA = 100.0
#: sharded vs one-device eval (docs/performance.md §8: per-device
#: partial sums reorder the reduction)
SHARD_EVAL_TOL = 1e-6


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _report(label: str, **fields) -> None:
    print(f"chip_smoke {label}: " + " ".join(
        f"{k}={v}" for k, v in fields.items()), flush=True)


def _peak_bytes():
    """Process-wide peak device bytes so far (None where the backend
    does not report it, e.g. CPU)."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")


def _run(trainer, engine: str, rounds: int, eval_every: int, run_dir: str,
         seed: int = 0):
    """``run_simulation`` with a telemetry run attached; returns the
    result and the fenced wall seconds of each chunk (eager: each
    round), compile included in the first."""
    from repro.fl.simulation import run_simulation
    from repro.telemetry import TelemetryRun, read_events

    shutil.rmtree(run_dir, ignore_errors=True)
    with TelemetryRun(run_dir, seed=seed) as tel:
        res = run_simulation(trainer, rounds=rounds, eval_every=eval_every,
                             seed=seed, engine=engine, telemetry=tel)
    trainer.set_telemetry(None)
    spans = [e["seconds"] for e in read_events(tel.events_path)
             if e["t"] == "phase"
             and e["name"] in ("scan_chunk", "round_eager")]
    return res, spans


def _report_run(label: str, res, spans) -> None:
    losses = [m["train_loss"] for m in res.round_metrics]
    _report(label, first_chunk_s=spans[0], warm_chunk_s=spans[1:],
            train_loss_max=max(losses),
            final_acc=res.final.get("acc_personalized", res.final["acc"]),
            peak_bytes_in_use=_peak_bytes())
    _check(all(math.isfinite(v) for v in losses),
           f"{label}: non-finite train_loss")


def _compare(label: str, res_a, res_b, rounds: int | None = None) -> dict:
    """|Δ train_loss| per round over the first ``rounds`` rounds, relative
    to max(1, |loss|), and the largest |Δ personalized acc| at every eval
    both runs share; checked against LOSS_RTOL / ACC_TOL."""
    n = rounds or min(len(res_a.round_metrics), len(res_b.round_metrics))
    d_loss = [abs(a["train_loss"] - b["train_loss"])
              for a, b in zip(res_a.round_metrics[:n],
                              res_b.round_metrics[:n])]
    r_loss = max(d / max(1.0, abs(a["train_loss"]))
                 for d, a in zip(d_loss, res_a.round_metrics))
    d_acc = max(abs(a["acc_personalized"] - b["acc_personalized"])
                for a, b in zip(res_a.history, res_b.history))
    _report(label, abs_d_train_loss_per_round=d_loss,
            max_rel_d_train_loss=r_loss, loss_rtol=LOSS_RTOL,
            max_abs_d_acc_personalized=d_acc, acc_tol=ACC_TOL)
    _check(r_loss <= LOSS_RTOL, f"{label}: relative |Δ train_loss| "
           f"{r_loss} > {LOSS_RTOL}")
    _check(d_acc <= ACC_TOL, f"{label}: |Δ acc| {d_acc} > {ACC_TOL}")
    return {"d_train_loss": max(d_loss), "rel_d_train_loss": r_loss,
            "d_acc": d_acc}


def _chunk_text(trainer, engine: str, rounds: int) -> str:
    """Compiled text of one fresh ``run_chunk(engine=…)`` call, taken
    from the trainer's own jitted-closure capture."""
    import jax
    import numpy as np

    state = trainer.init_state(jax.random.PRNGKey(0))
    with trainer.capture_jitted() as entries:
        trainer.run_chunk(state, trainer.schedule(
            rounds, np.random.default_rng(0)), engine=engine)
    _, fn, args, kwargs = entries[-1]
    return fn.lower(*args, **kwargs).compile().as_text()


def _check_kernel(label: str, trainer, rounds: int) -> None:
    """The scan_fused chunk must hold the Mosaic kernel on a TPU (on CPU
    the kernel is interpreted and lowers to plain HLO)."""
    import jax

    found = "tpu_custom_call" in _chunk_text(trainer, "scan_fused", rounds)
    _report(label, tpu_custom_call=found)
    if jax.default_backend() == "tpu":
        _check(found, f"{label}: no tpu_custom_call in the scan_fused "
               "chunk — the Pallas kernel did not compile through Mosaic")


def _kernel_vs_oracle(label: str, n_params: int, zone: int,
                      walkers: int | None = None, seed: int = 0) -> float:
    """One fused zone update (``walkers``: the multi-zone kernel) on
    random float32 rows of the model's width against the pure-jnp
    oracle in ``core.rwsadmm``; returns max |Δ| over x⁺, z⁺, y⁺ and
    checks it against KERNEL_TOL."""
    import jax
    import jax.numpy as jnp

    from repro.core import rwsadmm
    from repro.kernels.rwsadmm_update import ops

    hp = rwsadmm.RWSADMMHparams(beta=CNN_CF_BETA, kappa=0.001,
                                epsilon=1e-5)
    lead = (zone,) if walkers is None else (walkers, zone)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x, z, g = ({"w": jax.random.normal(k, lead + (n_params,))}
               for k in ks[:3])
    y = {"w": jax.random.normal(ks[3], lead[:-1] + (n_params,))}
    mask = (jax.random.uniform(ks[4], lead) < 0.75).astype(jnp.float32)
    kappa, n_total = jnp.float32(hp.kappa), 100.0
    if walkers is None:
        got = ops.rwsadmm_zone_fused_update(
            x, z, y, g, mask, kappa, beta=hp.beta, eps_half=hp.eps_half,
            n_total=n_total)
        act, y_ref = rwsadmm.zone_round_masked(
            rwsadmm.ClientState(x=x, z=z), y, g, mask, hp, kappa, n_total)
    else:
        got = ops.rwsadmm_multizone_fused_update(
            x, z, y, g, mask, kappa, beta=hp.beta, eps_half=hp.eps_half,
            n_total=n_total)
        act, y_ref = rwsadmm.multizone_round_masked(
            rwsadmm.ClientState(x=x, z=z), y, g, mask, hp, kappa, n_total)
    diff = max(float(jnp.max(jnp.abs(a["w"] - b["w"])))
               for a, b in zip(got, (act.x, act.z, y_ref)))
    _report(label, kernel_vs_oracle_max_abs_d=diff, kernel_tol=KERNEL_TOL)
    _check(diff <= KERNEL_TOL, f"{label}: kernel differs from the jnp "
           f"oracle by {diff} > {KERNEL_TOL}")
    return diff


def cnn_clients(n_clients: int, n_samples: int, shape=(32, 32, 3),
                seed: int = 0):
    """Synthetic CIFAR-shaped images split 2 labels per client, stacked
    on the device, and the paper CNN for them."""
    from repro.data import make_image_dataset, pathological_split
    from repro.data.loader import build_federated
    from repro.fl.base import to_device_data
    from repro.models.small import get_model

    imgs, labels = make_image_dataset(n_samples, shape=shape, noise=0.6,
                                      seed=seed)
    idx = pathological_split(labels, n_clients, seed=seed)
    data = to_device_data(build_federated(imgs, labels, idx))
    return data, get_model("cnn", shape)


def cnn_dense(n_clients: int = 100, n_samples: int = 50_000,
              shape=(32, 32, 3), *, rounds: int = 20, eager_rounds: int = 5,
              eval_every: int = 5, zone: int = 8, seed: int = 0,
              run_root: str = os.path.join(REPO, "runs", "chip_smoke")
              ) -> dict:
    """Single-walker RWSADMM on the dense client plane at the CNN's full
    width: prox_sgd/scan, then closed_form under eager, scan and
    scan_fused on fresh trainers with one seed."""
    from repro.core.rwsadmm import RWSADMMHparams
    from repro.fl.rwsadmm_trainer import RWSADMMTrainer

    data, model = cnn_clients(n_clients, n_samples, shape, seed)

    def trainer(solver: str):
        if solver == "prox_sgd":
            hp = RWSADMMHparams(beta=1.0, kappa=0.001, epsilon=1e-5)
            return RWSADMMTrainer(model, data, hp, zone_size=zone,
                                  batch_size=32, seed=seed)
        hp = RWSADMMHparams(beta=CNN_CF_BETA, kappa=0.001, epsilon=1e-5)
        return RWSADMMTrainer(model, data, hp, zone_size=zone,
                              solver="closed_form", seed=seed)

    out = {}
    res, spans = _run(trainer("prox_sgd"), "scan", rounds, eval_every,
                      os.path.join(run_root, "cnn_prox_scan"), seed)
    _report_run("cnn_dense/prox_sgd/scan", res, spans)
    out["prox_sgd/scan"] = res
    for engine, r in (("eager", eager_rounds), ("scan", rounds),
                      ("scan_fused", rounds)):
        tr = trainer("closed_form")
        res, spans = _run(tr, engine, r, eval_every,
                          os.path.join(run_root, f"cnn_cf_{engine}"), seed)
        _report_run(f"cnn_dense/closed_form/{engine}", res, spans)
        out[engine] = res
    _check_kernel("cnn_dense/closed_form/scan_fused", tr, eval_every)
    out["kernel_vs_oracle"] = _kernel_vs_oracle(
        "cnn_dense zone kernel", tr.params_bytes() // 4, zone)
    out["fused_vs_scan"] = _compare("cnn_dense scan_fused vs scan",
                                    out["scan_fused"], out["scan"])
    out["scan_vs_eager"] = _compare("cnn_dense scan vs eager", out["scan"],
                                    out["eager"], rounds=eager_rounds)
    return out


def fleet_sim(n_clients: int = 100, n_samples: int = 50_000,
              shape=(32, 32, 3), *, walkers: int = 3, rounds: int = 10,
              eval_every: int = 5, zone: int = 8, seed: int = 0,
              run_root: str = os.path.join(REPO, "runs", "chip_smoke")
              ) -> dict:
    """Simultaneous fleet (K walkers, K zones per wall step) under scan
    and scan_fused — the multi-zone Pallas kernel's path."""
    from repro.core.rwsadmm import RWSADMMHparams
    from repro.fl.fleet_trainer import FleetRWSADMMTrainer

    data, model = cnn_clients(n_clients, n_samples, shape, seed)
    out = {}
    for engine in ("scan", "scan_fused"):
        tr = FleetRWSADMMTrainer(
            model, data, RWSADMMHparams(beta=CNN_CF_BETA, kappa=0.001,
                                        epsilon=1e-5),
            n_walkers=walkers, fleet_mode="simultaneous", zone_size=zone,
            solver="closed_form", seed=seed)
        res, spans = _run(tr, engine, rounds, eval_every,
                          os.path.join(run_root, f"fleet_sim_{engine}"),
                          seed)
        _report_run(f"fleet_sim/K{walkers}/{engine}", res, spans)
        out[engine] = res
    _check_kernel(f"fleet_sim/K{walkers}/scan_fused", tr, eval_every)
    out["kernel_vs_oracle"] = _kernel_vs_oracle(
        f"fleet_sim K{walkers} multi-zone kernel", tr.params_bytes() // 4,
        zone, walkers)
    out["fused_vs_scan"] = _compare(f"fleet_sim K{walkers} scan_fused vs "
                                    "scan", out["scan_fused"], out["scan"])
    return out


def lazy(n_clients: int = 100_000, *, capacity: int = 1024,
         chunk_rounds: int = 8, chunks: int = 2, mesh=None,
         run_root: str = os.path.join(REPO, "runs", "chip_smoke"),
         label: str = "lazy"):
    """The lazy client plane of ``benchmarks/scan_scaling.py --lazy``:
    ``chunks`` scan chunks of ``chunk_rounds`` rounds through
    ``run_simulation``. Returns (trainer, result)."""
    from benchmarks.scan_scaling import lazy_plane_trainer

    tr = lazy_plane_trainer(n_clients, capacity, mesh=mesh)
    res, spans = _run(tr, "scan", chunks * chunk_rounds, chunk_rounds,
                      os.path.join(run_root, label))
    _report(label, first_chunk_s=spans[0], warm_chunk_s=spans[1:],
            final_acc=res.final["acc"],
            eval_clients=res.final["eval_clients"],
            resident=tr.store.n_resident,
            peak_bytes_in_use=_peak_bytes())
    _check(len(res.round_metrics) == chunks * chunk_rounds,
           f"{label}: ran {len(res.round_metrics)} rounds")
    return tr, res


def sharded_lazy(n_devices: int = 4, n_clients: int = 100_000, *,
                 capacity: int = 1024, chunk_rounds: int = 8,
                 chunks: int = 2,
                 run_root: str = os.path.join(REPO, "runs", "chip_smoke")
                 ) -> dict:
    """The lazy phase with the packed store sharded over ``n_devices``
    against the same run on one device: round_metrics must be equal,
    eval within SHARD_EVAL_TOL, and the store rows must sit on every
    device of the mesh."""
    import jax

    from repro.fl.sharding import FLSharding

    _check(capacity % n_devices == 0,
           f"capacity {capacity} must divide over {n_devices} devices")
    tr_s, res_s = lazy(n_clients, capacity=capacity,
                       chunk_rounds=chunk_rounds, chunks=chunks,
                       mesh=FLSharding(n_devices=n_devices),
                       run_root=run_root, label=f"lazy/shard{n_devices}")
    _, res_1 = lazy(n_clients, capacity=capacity, chunk_rounds=chunk_rounds,
                    chunks=chunks, run_root=run_root, label="lazy/one_device")

    rows = jax.tree_util.tree_leaves(tr_s.store.data)[0]
    devices = {s.device for s in rows.addressable_shards}
    shard_rows = {s.data.shape[0] for s in rows.addressable_shards}
    exact = res_s.round_metrics == res_1.round_metrics
    d_eval = max(abs(a[k] - b[k])
                 for a, b in zip(res_s.history, res_1.history)
                 for k in a if isinstance(a[k], float))
    _report(f"lazy shard{n_devices} vs one_device",
            round_metrics_equal=exact, max_abs_d_eval=d_eval,
            eval_tol=SHARD_EVAL_TOL, store_devices=len(devices),
            rows_per_device=sorted(shard_rows))
    _check(exact, "sharded round_metrics differ from one device")
    _check(len(res_s.history) == len(res_1.history) and
           d_eval <= SHARD_EVAL_TOL,
           f"sharded eval differs by {d_eval} > {SHARD_EVAL_TOL}")
    _check(len(devices) == n_devices
           and shard_rows == {capacity // n_devices},
           f"store rows on {len(devices)} devices, {shard_rows} rows "
           f"each; want {n_devices} × {capacity // n_devices}")
    return {"round_metrics_equal": exact, "d_eval": d_eval,
            "store_devices": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lazy plane sharded over four "
                    "devices against one device")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _report("setup", device_kind=dev.device_kind, devices=len(devices),
            compile_cache=cache, cache_entries_at_start=entries)

    t0 = time.perf_counter()
    if args.four_chips:
        sharded_lazy(n_devices=4)
    else:
        cnn_dense()
        fleet_sim()
        lazy()
    _report("done", wall_s=time.perf_counter() - t0,
            cache_entries_at_end=len(os.listdir(cache))
            if os.path.isdir(cache) else 0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
