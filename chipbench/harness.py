"""One run of one cell of ``BENCHMARK.json``.

A cell is a configuration (``configs/<config>.json``: model, data,
population, client plane, solver) under a traffic mix
(``traffic/<traffic>.json``: walkers, zone size, mobility, eval
cadence). Set-up builds the data and the weights from the seed, the
trainer from both files, and drives ``run_simulation`` twice: once to
compile the cell's chunk and eval, once to measure a rate. The window
is one ``run_simulation`` call of a whole number of eval windows sized
to last about ``seconds``, with no telemetry and no profiler attached,
and with a compile counter around it.

Correctness compares what ``run_simulation`` produced against the
plain reference in ``reference.py``: the first ``run_simulation`` call
of set-up, of ``CHECKED_CHUNKS`` eval windows so that a chunk's handover
to the next is inside the comparison (its per-round losses, its eval
snapshots, the state after its last chunk). The trainer, its compiled
chunk and eval are the ones the window drives. Only the dense client
plane is supported.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import time

import jax
import numpy as np

from . import check, data, reference, trace
from .compiles import compile_log

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT = os.path.join(REPO, ".chipbench_out")
#: longest traced window; the trace grows with the device ops it holds
TRACE_SECONDS = 5.0
#: eval windows (chunks) of set-up's first call that are compared
CHECKED_CHUNKS = 2
#: device ops that only contain other ops (a scan's loop, a call)
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* ")


class WindowCompiled(RuntimeError):
    """Something compiled inside the measured window."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_bench(root: str = REPO) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: str = REPO) -> Cell:
    """The cell named ``workload`` with its configuration and traffic
    files read, and the metrics that apply to it."""
    try:
        w = next(w for w in bench["workloads"] if w["name"] == workload)
    except StopIteration:
        raise ValueError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {[w['name'] for w in bench['workloads']]}"
                         ) from None
    c = next(c for c in bench["configs"] if c["name"] == w["config"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, c["file"])),
        traffic=_load_json(os.path.join(root, "chipbench", "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str, root: str = REPO):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- build --
@dataclasses.dataclass
class Built:
    trainer: object
    weights: dict
    dense: tuple                 # DeviceData columns
    solver: reference.Solver
    fleet: bool
    sim_seeds: list


def _scenario(cfg: dict, traffic: dict, n: int, name: str):
    from repro.scenarios import LinkConfig, MobilityConfig, ScenarioConfig

    mob = traffic.get("mobility")
    if mob is None:
        return None              # static_regen from min_degree/regen_every
    mob = dict(mob)
    degree = mob.pop("expected_degree", None)
    if degree is not None:       # radio range for that expected degree
        mob["radio_range"] = float(np.sqrt(degree / (np.pi * n)))
    return ScenarioConfig(name=name, mobility=MobilityConfig(**mob),
                          links=LinkConfig(**traffic.get("links", {})),
                          **cfg.get("control_plane", {}))


def build(cell: Cell, seed: int) -> Built:
    from repro.core.rwsadmm import RWSADMMHparams
    from repro.fl.base import DeviceData
    from repro.fl.fleet_trainer import FleetRWSADMMTrainer
    from repro.fl.rwsadmm_trainer import RWSADMMTrainer
    from repro.models.small import get_model

    cfg, tr = cell.config, cell.traffic
    s_data, s_w, s_trainer, s_sim = data.sub_seeds(seed, 4)
    mcfg, sol = cfg["model"], cfg["solver"]
    weights = data.init_weights(jax.random.PRNGKey(s_w),
                                data.model_key(mcfg))
    shape = tuple(mcfg.get("input_shape") or (mcfg["features"],))
    model = dataclasses.replace(
        get_model(mcfg["kind"], shape, int(mcfg["n_classes"])),
        init=lambda key: weights)
    n = int(cfg["n_clients"])
    if cfg["plane"] != "dense":
        raise ValueError(f"{cfg['name']}: only the dense client plane is "
                         f"supported, not {cfg['plane']!r}")
    make = (data.stacked_images if cfg["data"]["kind"] == "synthetic_images"
            else data.stacked_synthetic)
    dense = make(cfg["data"], n, s_data)
    hp = RWSADMMHparams(beta=float(sol["beta"]), kappa=float(sol["kappa"]),
                        kappa_decay=float(sol["kappa_decay"]),
                        epsilon=float(sol["epsilon"]))
    kw = dict(zone_size=int(tr["zone_size"]),
              batch_size=int(sol["batch_size"]), solver=sol["kind"],
              seed=s_trainer, scenario=_scenario(cfg, tr, n, cell.name),
              min_degree=int(tr.get("min_degree", 5)),
              regen_every=int(tr.get("regen_every", 10)),
              prefetch=bool(tr.get("prefetch", False)))
    fleet = int(tr["walkers"]) > 1
    if fleet:
        trainer = FleetRWSADMMTrainer(
            model, DeviceData(*dense), hp, n_walkers=int(tr["walkers"]),
            fleet_mode=tr["fleet_mode"], sync_every=int(tr["sync_every"]),
            **kw)
    else:
        trainer = RWSADMMTrainer(model, DeviceData(*dense), hp, **kw)
    solver = reference.Solver(
        model=mcfg["kind"], beta=hp.beta, kappa=hp.kappa,
        kappa_decay=hp.kappa_decay, eps_half=hp.eps_half, n_total=float(n),
        batch=int(sol["batch_size"]))
    return Built(trainer, weights, dense, solver, fleet,
                 [s_sim + i for i in range(3)])


# ------------------------------------------------------------- recorder --
class Recorder:
    """Wraps the trainer's ``schedule``, ``run_chunk`` and ``evaluate``
    as instance attributes. While ``on`` it keeps the schedules built
    and the latest state a chunk returned; while ``annotate`` it names
    each call on the profiler's timeline, so that idle gaps can be put
    down to what the host did; while ``ends`` is a list it gets the
    clock reading at the return of each ``run_chunk`` call."""

    def __init__(self, trainer):
        self.on = self.annotate = False
        self.schedules: list = []
        self.state = None
        self.ends: list | None = None
        for name in ("schedule", "run_chunk", "evaluate"):
            self._wrap(trainer, name)

    def _wrap(self, obj, name: str) -> None:
        fn = getattr(obj, name)

        def wrapped(*args, **kwargs):
            if self.annotate:
                with jax.profiler.TraceAnnotation("chipbench." + name):
                    out = fn(*args, **kwargs)
            else:
                out = fn(*args, **kwargs)
            if self.on and name == "schedule":
                self.schedules.append(out)
            elif name == "run_chunk":
                if self.ends is not None:
                    self.ends.append(time.perf_counter())
                if self.on:
                    self.state = out[0]
            return out

        setattr(obj, name, wrapped)

    def start(self) -> None:
        self.on, self.schedules, self.state = True, [], None

    def stop(self) -> None:
        self.on = False


# ---------------------------------------------------------- program side --
def _state_parts(state):
    """(x, z, tokens) of an RWSADMM or fleet state; tokens (K, …)."""
    base = getattr(state, "base", state)
    tokens = getattr(state, "tokens", None)
    if tokens is None:
        tokens = jax.tree_util.tree_map(lambda l: l[None], base.server.y)
    return base.clients.x, base.clients.z, tokens


def _sched_dict(s, fleet: bool) -> dict:
    sync = (np.asarray(s.sync, np.float32) if fleet
            else np.zeros((s.rounds,), np.float32))
    return {"idx": np.asarray(s.idx), "mask": np.asarray(s.mask),
            "keys": np.asarray(s.keys), "sync": sync}


def zone_faults(schedules, n: int) -> int:
    """Rounds whose zones hold an id out of range or a client twice."""
    bad = 0
    for s in schedules:
        idx = np.asarray(s["idx"]).reshape(s["idx"].shape[0], -1)
        live = np.asarray(s["mask"]).reshape(idx.shape) > 0
        for ids, m in zip(idx, live):
            ok = ((ids >= 0) & (ids < n)).all()
            bad += int(not ok or len(set(ids[m])) != int(m.sum()))
    return bad


def _losses(res) -> np.ndarray:
    return np.asarray([m["train_loss"] for m in res.round_metrics],
                      np.float64)


# ------------------------------------------------------------------ run --
class Run:
    """One seeded run of a cell, in the order its phases happen."""

    def __init__(self, cell: Cell, seed: int):
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        # Every program goes to the persistent cache, however quickly it
        # compiled, so that a second run of a cell compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.cell = cell
        self.b = build(cell, seed)
        self.n = int(cell.config["n_clients"])
        self.E = int(cell.traffic["eval_every"])
        self.rec = Recorder(self.b.trainer)
        self.checked = self.prog = None

    def simulate(self, rounds: int, sim_seed: int, telemetry=None):
        from repro.fl.simulation import run_simulation

        return run_simulation(
            self.b.trainer, rounds=rounds, eval_every=self.E, seed=sim_seed,
            engine=self.cell.config["solver"]["engine"], telemetry=telemetry)

    def warm_up(self) -> float:
        """Compile the chunk and the eval of the cell's own sizes, keep
        the first call's outputs, and return a measured rate in
        rounds/s."""
        b, rec = self.b, self.rec
        rec.start()
        warm = self.simulate(CHECKED_CHUNKS * self.E, b.sim_seeds[0])
        rec.stop()
        x, z, tokens = _state_parts(rec.state)
        self.checked = [_sched_dict(s, b.fleet) for s in rec.schedules]
        self.prog = reference.Outputs(
            losses=_losses(warm), evals=list(warm.history),
            norms=reference.change_norms(x, z, tokens, b.weights,
                                         np.arange(self.n)))
        rec.state = None
        t0 = time.perf_counter()
        self.simulate(self.E, b.sim_seeds[1])
        return self.E / (time.perf_counter() - t0)

    def window(self, rounds: int, telemetry=None):
        """The measured ``run_simulation`` call."""
        return self.simulate(rounds, self.b.sim_seeds[2], telemetry)

    def free_program(self) -> None:
        """Drop the trainer and its state before the reference runs."""
        self.b.trainer = self.rec = None
        gc.collect()

    def reference(self, **variant) -> reference.Outputs:
        """The reference's outputs over the checked schedules, on the
        host's CPU; ``variant`` sets ``dtype`` / ``half_batch``."""
        b = self.b
        sv = dataclasses.replace(b.solver, **variant)
        cpu = jax.devices("cpu")[0]
        weights = jax.device_put(b.weights, cpu)
        with jax.default_device(cpu):
            xt, yt, nt, xe, ye, me = jax.device_put(b.dense, cpu)
            return reference.simulate(sv, weights, (xt, yt, nt),
                                      self.checked, fleet=b.fleet,
                                      eval_data=(xe, ye, me))

    def numbers(self, ref, prog=None) -> dict:
        """The numbers compared: ``prog`` (default: the program's
        outputs) against the reference's."""
        out = check.compare(self.prog if prog is None else prog, ref)
        out["zone_faults"] = float(zone_faults(self.checked, self.n))
        return out


def run_cell(cell: Cell, seed: int, seconds: float, trace_on: bool, *,
             t_start: float, limits: dict | None = None) -> dict:
    """Set-up, window and comparison of one run; the result line."""
    run = Run(cell, seed)
    rate = run.warm_up()
    span = min(seconds, TRACE_SECONDS) if trace_on else seconds
    rounds = run.E * max(1, round(span * rate / run.E))
    setup_s = time.perf_counter() - t_start
    if trace_on:
        res, layer, breakdown, busy_s, window_s = _traced_window(run, rounds)
    else:
        run.rec.ends = []
        with compile_log() as compiles:
            t0 = time.perf_counter()
            res = run.window(rounds)
            wall = time.perf_counter() - t0
        if compiles:
            raise WindowCompiled(f"compiled inside the window: "
                                 f"{dict(compiles)}")
        # Seconds from one chunk's dispatch to the next (a host stall
        # shows as one long interval, a slower chip as all of them).
        step = np.diff([t0] + run.rec.ends)
        print(f"window chunks={len(step)} median_s={np.median(step)!r} "
              f"slowest_s={step.max()!r} at={int(step.argmax())}",
              file=sys.stderr)
    devices = jax.devices()[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    losses = _losses(res)
    attempted, failed = len(losses), int((~np.isfinite(losses)).sum())
    del res
    run.free_program()
    numbers = run.numbers(run.reference())
    print("readings " + " ".join(f"{k}={v!r}" for k, v in numbers.items()),
          file=sys.stderr)
    ok, shown = check.verdict(numbers, check.load_limits(cell.name)
                              if limits is None else limits)
    dev = jax.devices()[0]
    out = {"correct": bool(ok and failed == 0), "attempted": attempted,
           "failed": failed, "metrics": {},
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if trace_on:
        out["metrics"] = layer
        out["device"].update(busy_s=busy_s, window_s=window_s)
        out["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {"rounds_per_s": rounds / wall, "peak_device_bytes": peak,
                  "setup_s": setup_s}
        out["metrics"] = {k: {"value": values[k], "unit": units[k]}
                          for k in units}
    out["checks"] = shown
    return out


def _traced_window(run: Run, rounds: int):
    """A window of its own under ``jax.profiler`` and the program's
    telemetry; returns (result, per-layer metrics, breakdown, busy_s,
    window_s)."""
    from repro.telemetry import TelemetryRun, read_events

    from . import counts
    from .peaks import peak

    cell = run.cell
    tel_dir = os.path.join(OUT, "runs", cell.name)
    prof_dir = os.path.join(OUT, "trace", cell.name)
    for d in (tel_dir, prof_dir):
        shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    run.rec.annotate = True
    with TelemetryRun(tel_dir) as tel:
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                res = run.window(rounds, telemetry=tel)
        finally:
            jax.profiler.stop_trace()
    run.rec.annotate = False
    run.b.trainer.set_telemetry(None)
    events = trace.load(prof_dir)
    (win,) = trace.select(events, name="^chipbench\\.window$")
    devs = trace.planes(events)[:cell.chips]
    window_s = (win.end - win.start) / 1e9
    sol, mcfg = cell.config["solver"], cell.config["model"]
    ctx = {
        "events": events, "devices": devs, "window": (win.start, win.end),
        "rounds": rounds, "chips": cell.chips,
        "telemetry": list(read_events(tel.events_path)),
        "round_flops": counts.round_flops(mcfg, cell.traffic,
                                          int(sol["batch_size"])),
        "round_bytes": counts.round_bytes(mcfg, cell.traffic,
                                          int(sol["batch_size"])),
        "peak": peak(jax.devices()[0].device_kind),
    }
    from .readers import device_ns

    busy_s = (device_ns(ctx) or 0.0) / 1e9
    layer = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            layer[m["name"]] = {"value": v, "unit": m["unit"]}
    return res, layer, breakdown_of(events, devs, win), busy_s, window_s


def breakdown_of(events, devs, win) -> dict:
    """The device ops that took most time (averaged over the devices)
    and the longest idle gaps of the first device, each labelled by the
    host event that overlaps it most."""
    ops = [e for e in trace.select(events, plane=trace.DEVICE_PREFIX,
                                   line=trace.OPS_LINE)
           if e.plane in devs and e.end > win.start and e.start < win.end]
    # Named by the HLO instruction alone; loops and calls are left out,
    # as the ops inside them are listed themselves.
    per = trace.by_name([dataclasses.replace(e, name=e.name.split(" = ")[0])
                         for e in ops if not CONTAINER.match(e.name)])
    top = sorted(per.items(), key=lambda t: -t[1])[:10]
    host = [e for e in events if e.plane.startswith("/host:")
            and e.name != "chipbench.window"]
    gaps = []
    if devs:
        busy = [(e.start, e.end) for e in ops if e.plane == devs[0]]
        gaps = sorted(trace.gaps(busy, win.start, win.end),
                      key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[k, v / len(devs) / 1e9] for k, v in top],
            "idle_gaps": [[trace.label(g, host), (g[1] - g[0]) / 1e9]
                          for g in gaps]}
