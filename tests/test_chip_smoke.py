"""Rehearsal of ``chip_smoke.py`` on CPU: every phase function at a tiny
size (interpret-mode kernels), the four-device comparison on virtual
host devices in a subprocess, and the entry point's refusal to run
without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cnn_dense_phase_tiny(smoke, tmp_path):
    out = smoke.cnn_dense(n_clients=6, n_samples=240, shape=(8, 8, 3),
                          rounds=4, eager_rounds=2, eval_every=2, zone=3,
                          run_root=str(tmp_path))
    assert len(out["scan"].round_metrics) == 4
    assert len(out["eager"].round_metrics) == 2
    assert out["fused_vs_scan"]["rel_d_train_loss"] <= smoke.LOSS_RTOL
    assert out["scan_vs_eager"]["d_acc"] <= smoke.ACC_TOL
    assert out["kernel_vs_oracle"] <= smoke.KERNEL_TOL


def test_fleet_sim_phase_tiny(smoke, tmp_path):
    out = smoke.fleet_sim(n_clients=9, n_samples=360, shape=(8, 8, 3),
                          walkers=3, rounds=4, eval_every=2, zone=2,
                          run_root=str(tmp_path))
    assert len(out["scan_fused"].round_metrics) == 4
    assert out["fused_vs_scan"]["rel_d_train_loss"] <= smoke.LOSS_RTOL
    assert out["kernel_vs_oracle"] <= smoke.KERNEL_TOL


def test_lazy_phase_tiny(smoke, tmp_path):
    tr, res = smoke.lazy(200, capacity=64, chunk_rounds=2, chunks=2,
                         run_root=str(tmp_path))
    assert len(res.round_metrics) == 4
    assert 0 < tr.store.n_resident <= 64


def test_sharded_lazy_four_virtual_devices(tmp_path):
    """The ``--four-chips`` comparison on four host devices (the flag
    must precede backend init, hence the subprocess)."""
    script = (
        "import importlib.util, json, sys\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', "
        f"{os.path.join(REPO, 'chip_smoke.py')!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "out = m.sharded_lazy(4, 200, capacity=64, chunk_rounds=2, "
        f"chunks=2, run_root={str(tmp_path)!r})\n"
        "print('RESULT' + json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    out = json.loads(line[len("RESULT"):])
    assert out["round_metrics_equal"] and out["store_devices"] == 4
    assert out["d_eval"] <= 1e-6


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(smoke, argv, capsys):
    assert smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err
