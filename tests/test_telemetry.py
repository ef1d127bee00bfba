"""Telemetry subsystem: recorder/event-schema round-trips, the report
CLI, and — the hard requirement — telemetry-on runs bit-identical to
telemetry-off (single walker + K=3 fleet, eager + scan engines, dense +
sparse graph backends): the recorder must never touch an RNG stream or
perturb the computation graph.
"""
import dataclasses
import json
import os

import pytest

from repro.core.rwsadmm import RWSADMMHparams
from repro.data import make_image_dataset, pathological_split
from repro.data.loader import build_federated
from repro.fl.base import (
    to_device_data,
    validate_round_metrics,
)
from repro.fl.fleet_trainer import FleetRWSADMMTrainer
from repro.fl.rwsadmm_trainer import RWSADMMTrainer
from repro.fl.simulation import run_simulation
from repro.models.small import get_model
from repro.scenarios import get_scenario_config
from repro.telemetry import (
    TelemetryError,
    TelemetryRun,
    atomic_write_json,
    load_bench_rows,
    manifest_fingerprint,
    merge_bench_rows,
    read_events,
    split_by_type,
    validate_event,
)
from repro.telemetry.report import render_report, summarize
from repro.telemetry.smoke import smoke_run


@pytest.fixture(scope="module")
def fed():
    imgs, labels = make_image_dataset(400, seed=0)
    parts = pathological_split(labels, 8, seed=0)
    data = to_device_data(build_federated(imgs, labels, parts))
    model = get_model("mlr", (28, 28, 1))
    return data, model


def _scenario(backend: str):
    return dataclasses.replace(get_scenario_config("lossy_links"),
                               graph_backend=backend, neighbor_k_max=8)


def _make_trainer(fed, backend: str, fleet: int = 0):
    data, model = fed
    kw = dict(zone_size=4, batch_size=16, solver="closed_form",
              scenario=_scenario(backend), seed=0)
    if fleet:
        return FleetRWSADMMTrainer(model, data, RWSADMMHparams(beta=10.0),
                                   n_walkers=fleet, sync_every=3, **kw)
    return RWSADMMTrainer(model, data, RWSADMMHparams(beta=10.0), **kw)


def _run(fed, *, engine, backend, fleet=0, telemetry=None, rounds=8):
    tr = _make_trainer(fed, backend, fleet)
    return run_simulation(tr, rounds=rounds, eval_every=4, seed=0,
                          engine=engine, telemetry=telemetry)


# ------------------------------------------------ bit-identical pins ----
@pytest.mark.parametrize("engine", ["eager", "scan"])
@pytest.mark.parametrize("backend", ["dense", "sparse"])
@pytest.mark.parametrize("fleet", [0, 3])
def test_telemetry_on_is_bit_identical(fed, tmp_path, engine, backend,
                                       fleet):
    """Recording a run must not change it: identical histories and
    round_metrics (exact float equality — same draws, same executables)
    with telemetry on vs off, across engines, backends, and the K=3
    fleet."""
    res_off = _run(fed, engine=engine, backend=backend, fleet=fleet)
    with TelemetryRun(str(tmp_path / "run"), seed=0) as tel:
        res_on = _run(fed, engine=engine, backend=backend, fleet=fleet,
                      telemetry=tel)
    assert len(res_off.round_metrics) == len(res_on.round_metrics)
    for m0, m1 in zip(res_off.round_metrics, res_on.round_metrics):
        assert m0 == m1
    assert [h["round"] for h in res_off.history] \
        == [h["round"] for h in res_on.history]
    for h0, h1 in zip(res_off.history, res_on.history):
        assert h0 == h1
    assert res_off.total_comm_bytes == res_on.total_comm_bytes
    # ...and the recorder actually recorded every event type.
    b = split_by_type(read_events(tel.events_path))
    assert len(b["round"]) == 8
    assert b["visit"], "walk trace missing"
    assert b["snapshot"] and b["phase"] and b["counter"]


def test_visit_trace_identical_across_engines(fed, tmp_path):
    """The walk/zone trace is engine-invariant: eager and scan emit the
    same visit events (clients, zones, pricing) for the same seed."""
    streams = {}
    for engine in ("eager", "scan"):
        with TelemetryRun(str(tmp_path / engine), seed=0) as tel:
            _run(fed, engine=engine, backend="dense", telemetry=tel)
        streams[engine] = [e for e in read_events(tel.events_path)
                           if e["t"] == "visit"]
    assert streams["eager"] == streams["scan"]


# ------------------------------------------------ event schema ----------
def test_event_validation():
    validate_event({"t": "visit", "round": 0, "client": 3})
    with pytest.raises(TelemetryError, match="unknown event type"):
        validate_event({"t": "nope"})
    with pytest.raises(TelemetryError, match="missing required"):
        validate_event({"t": "phase", "name": "x"})


def test_event_roundtrip_and_report(tmp_path):
    """write → read → report on a recorded 5-round run: every event
    re-validates, counts line up with the manifest, and the rendered
    summary carries all required sections."""
    run_dir = str(tmp_path / "run")
    tel = smoke_run(run_dir, rounds=5, eval_every=5)
    events = list(read_events(tel.events_path))
    for e in events:
        validate_event(e)
    b = split_by_type(events)
    assert len(b["round"]) == 5
    assert len(b["visit"]) == 5
    assert len(b["snapshot"]) == 1
    counts = tel.manifest["event_counts"]
    assert counts["round"] == 5 and counts["visit"] == 5
    assert tel.manifest["status"] == "finalized"

    report = render_report(run_dir)
    for section in ("== Run ==", "== Convergence ==",
                    "== Coverage & staleness ==", "== Communication ==",
                    "== Phase times ==", "== Counters =="):
        assert section in report, report
    assert "scan_chunk" in report and "scenario_rollout" in report

    s = summarize(run_dir)
    assert s["n_rounds"] == 5
    assert s["comm_bytes_total"] > 0
    assert s["latency_s_total"] > 0          # lossy_links prices comm
    assert s["unique_clients"] >= 1
    assert any(p["name"] == "scan_chunk" and p["includes_compile"]
               for p in s["phases"])


def test_fleet_report_has_walker_table(tmp_path):
    run_dir = str(tmp_path / "fleet")
    smoke_run(run_dir, rounds=6, eval_every=3, fleet=3)
    report = render_report(run_dir)
    assert "== Walkers ==" in report
    s = summarize(run_dir)
    assert set(s["walkers"]) == {0, 1, 2}
    assert sum(w["visits"] for w in s["walkers"].values()) == 6


# ------------------------------------------------ manifest --------------
def test_manifest_determinism_under_fixed_seed(tmp_path):
    """Two runs of the same seeded workload agree on the deterministic
    manifest fingerprint (config/seed/git/jax/packages) even though run
    ids and timestamps differ; a different seed changes it."""
    t1 = smoke_run(str(tmp_path / "a"), rounds=2, eval_every=2)
    t2 = smoke_run(str(tmp_path / "b"), rounds=2, eval_every=2)
    assert t1.manifest["fingerprint"] == t2.manifest["fingerprint"]
    assert t1.manifest["fingerprint"] == manifest_fingerprint(t1.manifest)
    t3 = smoke_run(str(tmp_path / "c"), rounds=2, eval_every=2, seed=1)
    assert t3.manifest["fingerprint"] != t1.manifest["fingerprint"]
    # events are identical too: sorted keys, no wall-clock fields
    # outside phase spans and the wall_time_s counter
    def det(tel):
        return [e for e in read_events(tel.events_path)
                if e["t"] != "phase"
                and e.get("name") != "wall_time_s"]

    assert det(t1) == det(t2)


def test_manifest_atomic_and_updatable(tmp_path):
    run_dir = str(tmp_path / "m")
    tel = TelemetryRun(run_dir, seed=7, config={"a": 1})
    with open(tel.manifest_path) as f:
        m = json.load(f)
    assert m["seed"] == 7 and m["config"] == {"a": 1}
    assert m["status"] == "open"
    tel.update_manifest(config={"b": 2})
    tel.close()
    with open(tel.manifest_path) as f:
        m = json.load(f)
    assert m["config"] == {"a": 1, "b": 2}    # merged, not clobbered
    assert m["status"] == "finalized"
    assert not [p for p in os.listdir(run_dir) if p.endswith(".tmp")]
    with pytest.raises(TelemetryError, match="closed"):
        tel.emit("counter", name="x", value=1)


# ------------------------------------------------ artifacts -------------
def test_bench_rows_merge_by_identity(tmp_path):
    """BENCH rows merge by (name, n, K, engine): re-measuring one row
    updates it in place, rows differing only in n/K/engine coexist."""
    path = str(tmp_path / "bench.json")
    r1 = {"name": "x", "n": 10, "K": 1, "engine": "scan",
          "us_per_round": 1.0}
    r2 = {"name": "x", "n": 20, "K": 1, "engine": "scan",
          "us_per_round": 2.0}
    atomic_write_json(path, merge_bench_rows([], [r1, r2]))
    update = {**r1, "us_per_round": 9.0}
    rows = merge_bench_rows(load_bench_rows(path), [update])
    atomic_write_json(path, rows)
    out = load_bench_rows(path)
    assert len(out) == 2
    by_n = {r["n"]: r for r in out}
    assert by_n[10]["us_per_round"] == 9.0    # updated
    assert by_n[20]["us_per_round"] == 2.0    # preserved
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def test_write_bench_rows_is_atomic_and_merging(tmp_path):
    from benchmarks import common

    path = str(tmp_path / "BENCH.json")
    common.write_bench_rows(
        [{"name": "a", "n": 1, "K": 1, "engine": "e", "us_per_round": 1}],
        path)
    common.write_bench_rows(
        [{"name": "b", "n": 1, "K": 1, "engine": "e", "us_per_round": 2}],
        path)
    rows = load_bench_rows(path)
    assert {r["name"] for r in rows} == {"a", "b"}


# ------------------------------------------------ schema validator ------
def test_round_metrics_validator(fed):
    res = _run(fed, engine="eager", backend="dense", rounds=4)
    keys = validate_round_metrics(res.round_metrics)
    assert {"round", "comm_bytes", "client", "train_loss"} <= keys
    with pytest.raises(AssertionError, match="missing required"):
        validate_round_metrics([{"round": 0}])
    with pytest.raises(AssertionError, match="key set"):
        validate_round_metrics([
            {"round": 0, "comm_bytes": 1},
            {"round": 1, "comm_bytes": 1, "extra": 2}])
    with pytest.raises(AssertionError, match="expected int"):
        validate_round_metrics([{"round": 0, "comm_bytes": 1.5}])
    with pytest.raises(AssertionError, match="round=3"):
        validate_round_metrics([{"round": 3, "comm_bytes": 1}])


# ------------------------------------------------ baselines hook --------
def test_baseline_telemetry_hook(fed, tmp_path):
    """The FedAvg-family baselines record through the same hook, and the
    snapshot print path tolerates snapshots without 'acc'."""
    from repro.baselines import FedAvgTrainer

    data, model = fed
    with TelemetryRun(str(tmp_path / "fa"), seed=0) as tel:
        tr = FedAvgTrainer(model, data, clients_per_round=4,
                           local_steps=2, telemetry=tel)
        res = run_simulation(tr, rounds=3, eval_every=3, seed=0,
                             telemetry=tel, verbose=True)
    assert len(res.round_metrics) == 3
    b = split_by_type(read_events(tel.events_path))
    assert len(b["round"]) == 3
    assert b["snapshot"]
    assert tel.manifest["config"]["algo"] == "fedavg"


def test_snapshot_without_acc_does_not_crash(fed, tmp_path, capsys):
    """verbose snapshot formatting with eval-less snapshots (no 'acc'):
    regression for the KeyError-prone f-string."""
    from repro.fl import simulation as sim

    class NoAccTrainer:
        name = "noacc"

        def evaluate(self, state):
            return {"loss_global": 1.0}

        def _phase(self, name, **meta):
            from repro.telemetry import null_phase

            return null_phase()

    hist = []
    sim._snapshot(NoAccTrainer(), None, 5, 123, hist, True, "noacc")
    assert hist[0]["round"] == 5
    assert "acc" not in hist[0]


# ------------------------------------------------ lazy-plane counters ---
@pytest.fixture(scope="module")
def fed_lazy():
    """Same partition as ``fed`` but kept as a ClientDataFactory, for
    store-backed (client_plane='lazy') trainers."""
    from repro.data import factory_from_federated

    imgs, labels = make_image_dataset(400, seed=0)
    parts = pathological_split(labels, 8, seed=0)
    f = build_federated(imgs, labels, parts)
    model = get_model("mlr", (28, 28, 1))
    return factory_from_federated(f), model


def _make_lazy_trainer(fed_lazy, capacity):
    factory, model = fed_lazy
    return RWSADMMTrainer(model, factory, RWSADMMHparams(beta=10.0),
                          zone_size=4, batch_size=16,
                          solver="closed_form",
                          scenario=_scenario("dense"), seed=0,
                          store_capacity=capacity)


def _store_counter_events(events_path):
    from repro.fl.client_store import STORE_COUNTERS

    prefix = "client_store_"
    evs = [e for e in read_events(events_path)
           if e["t"] == "counter" and e["name"].startswith(prefix)]
    order = [prefix + k for k in STORE_COUNTERS]
    # one ensure call emits the four counters in STORE_COUNTERS order
    assert [e["name"] for e in evs] \
        == order * (len(evs) // len(order))
    return evs


@pytest.mark.parametrize("engine,capacity", [("eager", 5), ("scan", 8)])
def test_lazy_telemetry_on_is_bit_identical(fed_lazy, tmp_path, engine,
                                            capacity):
    """The store's hit/miss/evict/restore counters are host-side only:
    recording them must not change a lazy run (exact float equality),
    and the counter stream must actually be present."""
    from repro.fl.client_store import STORE_COUNTERS

    res_off = run_simulation(_make_lazy_trainer(fed_lazy, capacity),
                             rounds=8, eval_every=4, seed=0,
                             engine=engine)
    with TelemetryRun(str(tmp_path / engine), seed=0) as tel:
        res_on = run_simulation(_make_lazy_trainer(fed_lazy, capacity),
                                rounds=8, eval_every=4, seed=0,
                                engine=engine, telemetry=tel)
    for m0, m1 in zip(res_off.round_metrics, res_on.round_metrics):
        assert m0 == m1
    for h0, h1 in zip(res_off.history, res_on.history):
        assert h0 == h1
    names = {e["name"] for e in _store_counter_events(tel.events_path)}
    assert names == {f"client_store_{k}" for k in STORE_COUNTERS}


def test_lazy_store_counters_match_oracle(fed_lazy, fed, tmp_path):
    """Counter exactness: the recorded per-round deltas must equal an
    independent LRU-oracle replay of the schedule's visited set (raw
    padded zone rows — padding id 0 counts, by design), and the stream
    totals must equal the store's cumulative counters."""
    import collections

    import numpy as np

    from repro.fl.client_store import STORE_COUNTERS

    capacity, rounds = 5, 8
    tr = _make_lazy_trainer(fed_lazy, capacity)
    with TelemetryRun(str(tmp_path / "run"), seed=0) as tel:
        run_simulation(tr, rounds=rounds, eval_every=4, seed=0,
                       engine="eager", telemetry=tel)
    evs = _store_counter_events(tel.events_path)
    assert len(evs) == rounds * len(STORE_COUNTERS)
    got = [{k: evs[4 * r + j]["value"]
            for j, k in enumerate(STORE_COUNTERS)}
           for r in range(rounds)]
    totals = collections.Counter()
    for d in got:
        totals.update(d)
    assert dict(totals) == tr.store.counters
    assert totals["evictions"] > 0 and totals["restores"] > 0

    # Oracle: a dense twin's schedule replays the same walk draws, so
    # its padded zone rows are exactly what the lazy run ensured.
    twin = _make_trainer(fed, "dense")
    sched = twin.schedule(rounds, np.random.default_rng(0))
    oracle: collections.OrderedDict = collections.OrderedDict()
    spilled: set = set()
    expect = []
    for r in range(rounds):
        row = np.asarray(sched.idx)[r].reshape(-1)
        uniq = list(dict.fromkeys(int(i) for i in row))
        missing = [i for i in uniq if i not in oracle]
        d = {"hits": len(uniq) - len(missing), "misses": len(missing),
             "evictions": 0, "restores": 0}
        need = len(missing) - (capacity - len(oracle))
        if need > 0:
            victims = [i for i in oracle if i not in set(uniq)][:need]
            for v in victims:
                del oracle[v]
                spilled.add(v)
            d["evictions"] = need
        for i in missing:
            if i in spilled:
                d["restores"] += 1
                spilled.discard(i)
            oracle[i] = None
        for i in uniq:
            oracle.move_to_end(i)
        expect.append(d)
    assert got == expect


# ------------------------------------------------------------ profiler ---
@pytest.mark.parametrize("profile", [False, True])
def test_maybe_trace_propagates_body_errors(tmp_path, profile):
    """The traced loop's own exception reaches the caller unchanged,
    with or without a trace being recorded."""
    from repro.telemetry import maybe_trace

    run = TelemetryRun(str(tmp_path / "run"), profile=profile)
    with pytest.raises(ZeroDivisionError):
        with maybe_trace(run):
            1 / 0
    run.close()


def test_maybe_trace_fails_when_profiler_cannot_start(tmp_path):
    """A run that asked for a trace does not silently record none: a
    second concurrent trace is refused by the profiler, and that error
    surfaces instead of a no-op."""
    from repro.telemetry import maybe_trace

    outer = TelemetryRun(str(tmp_path / "outer"), profile=True)
    inner = TelemetryRun(str(tmp_path / "inner"), profile=True)
    with maybe_trace(outer) as logdir:
        assert logdir is not None
        with pytest.raises(Exception, match="(?i)profil|trace"):
            with maybe_trace(inner):
                pass
    outer.close()
    inner.close()
    assert outer.manifest.get("profile_dir") == "profile"


# ------------------------------------------- span tree and annotations --
SCHEDULE_PARENTS = {
    "schedule": None, "scenario_rollout": "schedule",
    "mobility": "scenario_rollout", "links": "scenario_rollout",
    "walk": "schedule", "zones": "schedule", "price": "schedule",
    "scan_chunk": None, "readback": None, "eval": None,
    "init_state": None,
}


@pytest.mark.parametrize("fleet", [0, 3])
def test_schedule_spans_record_their_parent(fed, tmp_path, fleet):
    """A scan run (single walker and K = 3 fleet) emits a span for every
    layer of ``schedule`` and the chunk's readback, each with the name
    of the span open around it."""
    with TelemetryRun(str(tmp_path / "run"), seed=0) as tel:
        _run(fed, engine="scan", backend="sparse", fleet=fleet,
             telemetry=tel)
    phases = [e for e in read_events(tel.events_path) if e["t"] == "phase"]
    got = {}
    for e in phases:
        assert got.setdefault(e["name"], e["parent"]) == e["parent"], e
    assert got == SCHEDULE_PARENTS
    # one schedule and one readback per chunk (8 rounds, eval every 4)
    assert sum(e["name"] == "schedule" for e in phases) == 2
    assert sum(e["name"] == "readback" for e in phases) == 2
    mob = [e for e in phases if e["name"] == "mobility"]
    assert all(e["edges"] > 0 for e in mob)
    # the sparse min-degree floor's counters ride on the same span
    assert all(0 <= e["ring_fallbacks"] <= e["deficient"] for e in mob)
    # round 0 (single walker) or the first K rounds (round-robin fleet)
    # serve the current graph; the others are rolled out
    assert sum(e["rounds"] for e in mob) == 8 - max(fleet, 1)


def test_telemetry_off_opens_no_annotation(fed, tmp_path, monkeypatch):
    """With telemetry off no span annotates or stacks anything, and the
    trajectory stays bit-identical to a recorded run."""
    from repro.telemetry import recorder

    def refuse(name):
        raise AssertionError(f"annotation {name!r} with telemetry off")

    monkeypatch.setattr(recorder, "annotate", refuse)
    res_off = _run(fed, engine="scan", backend="dense", fleet=3)
    monkeypatch.undo()
    with TelemetryRun(str(tmp_path / "run"), seed=0) as tel:
        res_on = _run(fed, engine="scan", backend="dense", fleet=3,
                      telemetry=tel)
    assert res_off.round_metrics == res_on.round_metrics
    assert res_off.history == res_on.history


def test_parent_stack_is_per_thread(tmp_path):
    """A span opened on another thread while one is open on the main
    thread has no parent (the prefetch worker's staging span)."""
    import threading

    with TelemetryRun(str(tmp_path / "run")) as tel:
        with tel.phase("outer"):
            t = threading.Thread(target=lambda: tel.phase("worker")
                                 .__enter__().__exit__(None, None, None))
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
            with tel.phase("inner"):
                pass
    got = {e["name"]: e["parent"] for e in read_events(tel.events_path)}
    assert got == {"outer": None, "worker": None, "inner": "outer"}


def test_profiler_trace_nests_program_spans(fed, tmp_path):
    """On a CPU profiler trace of a tiny scan run, every program span is
    a ``repro.<phase>`` host event that lies inside its parent's."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = _make_trainer(fed, "dense")
    run_simulation(tr, rounds=4, eval_every=4, seed=0, engine="scan")
    d = str(tmp_path / "prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with TelemetryRun(str(tmp_path / "run"), seed=0) as tel:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            run_simulation(tr, rounds=8, eval_every=4, seed=1,
                           engine="scan", telemetry=tel)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.setdefault(e.name[6:], []).append(
                        (line.name, e.start_ns, e.start_ns + e.duration_ns))
    for name in ("schedule", "mobility", "links", "zones", "readback"):
        assert spans.get(name), sorted(spans)
    assert len(spans["schedule"]) == 2
    for name, parent in SCHEDULE_PARENTS.items():
        for line, s, e in spans.get(name, []) if parent else []:
            assert any(pl == line and ps <= s and e <= pe
                       for pl, ps, pe in spans[parent]), (name, parent)


# ------------------------------------------------ batched writes --------
def _lines(tel):
    with open(tel.events_path) as f:
        return f.read().splitlines()


def test_events_are_written_in_batches(fed, tmp_path, monkeypatch):
    """Events wait in memory until a flush: the end of each
    run_simulation call, a full buffer, or close (also on failure)."""
    from repro.telemetry import recorder

    tel = TelemetryRun(str(tmp_path / "run"), seed=0)
    tel.counter("a", 1)
    assert _lines(tel) == []
    _run(fed, engine="scan", backend="dense", rounds=4, telemetry=tel)
    n = len(_lines(tel))
    assert n == sum(tel._counts.values()) and n > 1
    monkeypatch.setattr(recorder, "FLUSH_LINES", 3)
    for i in range(5):
        tel.counter("b", i)
    assert len(_lines(tel)) == n + 3
    tel.close()
    assert len(_lines(tel)) == n + 5

    with pytest.raises(ZeroDivisionError):
        with TelemetryRun(str(tmp_path / "failed")) as bad:
            bad.counter("c", 1)
            1 / 0
    assert [json.loads(x)["name"] for x in _lines(bad)] == ["c"]
    assert bad.manifest["status"] == "failed"
