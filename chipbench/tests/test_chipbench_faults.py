"""The comparison that decides ``correct``, at a size a test can hold.

Each cell kind (one walker on the dense plane, a simultaneous fleet)
is run through the harness on the CPU with every step
but the look for a chip: a sound run is correct, and a run with the
timed path broken underneath is not — a step that returns its state
unchanged, and half of each minibatch left out with the mean taken over
the rest. The control (the reference in bfloat16 in the program's
place) is not correct either. The limits are the cells' own."""
import copy
import os
import time

import jax
import pytest

from chipbench import check, harness

BENCH = harness.load_bench()
#: the benchmark's cells, and the simultaneous fleet, which has no cell
#: yet (PERF.md, Open questions) but keeps its harness path working
FLEET = "fleet_plane"
CELLS = [w["name"] for w in BENCH["workloads"]] + [FLEET]
SEED = 2**31 + 123


def tiny(name):
    if name == FLEET:
        cell = harness.resolve(BENCH, "cnn_n100_walk1")
        cell.traffic = harness._load_json(os.path.join(
            harness.HERE, "traffic", "fleet3_sim_static_e20.json"))
    else:
        cell = harness.resolve(BENCH, name)
    cell.name = name
    cfg, tr = copy.deepcopy(cell.config), copy.deepcopy(cell.traffic)
    tr["eval_every"] = 2
    if cfg["data"]["kind"] == "synthetic_images":
        cfg["n_clients"] = 6
        cfg["data"].update(n_samples=240, input_shape=[8, 8, 3])
        cfg["model"]["input_shape"] = [8, 8, 3]
        tr["zone_size"] = 2 if tr["walkers"] > 1 else 3
    else:
        cfg["n_clients"] = 300
        tr["zone_size"] = 3
    cell.config, cell.traffic = cfg, tr
    return cell


def limits(name):
    """The cell's limits; the fleet, which has no cell, takes those of the
    cell of the same model."""
    if name == FLEET:
        return check.load_limits("cnn_n100_walk1")
    return check.load_limits(name)


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the persistent compile cache off in the test process."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def plant(fault, monkeypatch):
    from repro.fl import fleet_trainer, rwsadmm_trainer

    if fault == "state_unchanged":
        for cls, name in ((rwsadmm_trainer.RWSADMMTrainer, "_round_impl"),
                          (fleet_trainer.FleetRWSADMMTrainer,
                           "_sim_step_impl")):
            orig = getattr(cls, name)

            def frozen(self, state, *a, _orig=orig, **k):
                return state, _orig(self, state, *a, **k)[1]

            monkeypatch.setattr(cls, name, frozen)
    elif fault == "half_batch":
        orig = rwsadmm_trainer.sample_batch

        def half(data, client, key, batch_size):
            xb, yb = orig(data, client, key, batch_size)
            return xb[: batch_size // 2], yb[: batch_size // 2]

        for mod in (rwsadmm_trainer, fleet_trainer):
            monkeypatch.setattr(mod, "sample_batch", half)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_run_is_correct_only_when_sound(name, fault, no_cache, monkeypatch):
    plant(fault, monkeypatch)
    out = harness.run_cell(tiny(name), SEED, 0.5, False,
                           t_start=time.perf_counter(), limits=limits(name))
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0
    if fault is None:
        assert out["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, no_cache):
    run = harness.Run(tiny(name), SEED + 1)
    run.warm_up()
    run.free_program()
    ref = run.reference()
    sound, _ = check.verdict(run.numbers(ref), limits(name))
    control = run.reference(dtype="bfloat16")
    ok, shown = check.verdict(run.numbers(ref, prog=control), limits(name))
    assert sound and not ok, shown
