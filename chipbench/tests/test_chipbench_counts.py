"""FLOP and byte counts of a round against hand counts."""
import json
import os

from chipbench import counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_cnn_parameters_and_macs():
    m = _config("cnn_cifar10_n100")["model"]
    # conv1 5*5*3*16+16, conv2 5*5*16*32+32, fc 8*8*32*512+512, out 5130
    assert counts.n_params(m) == 1216 + 12832 + 1049088 + 5130 == 1068266
    assert counts.n_params(m) == m["params"]
    # conv1 32*32*16*75, conv2 16*16*32*400, fc 2048*512, out 512*10
    assert counts.forward_macs(m) == 1228800 + 3276800 + 1048576 + 5120
    assert abs(6 * counts.forward_macs(m) - 33.36e6) < 0.01e6


def test_cnn_round_counts():
    m = _config("cnn_cifar10_n100")["model"]
    walk = {"walkers": 1, "zone_size": 8}
    fleet = {"walkers": 3, "fleet_mode": "simultaneous", "zone_size": 8}
    assert counts.round_flops(m, walk, 20) == 8 * 20 * 6 * 5559296
    assert counts.round_flops(m, fleet, 20) == 3 * 8 * 20 * 6 * 5559296
    one = 34 * 1068266 * 4 + 8 * 20 * (32 * 32 * 3 * 4 + 4)
    assert counts.round_bytes(m, walk, 20) == one
    assert counts.round_bytes(m, fleet, 20) == 3 * one
    assert abs(counts.round_bytes(m, walk, 20) - 147.2e6) < 0.1e6


def test_mlr_counts():
    m = _config("mlr_synth_n1e4")["model"]
    assert counts.n_params(m) == 61 * 10
    assert counts.forward_macs(m) == 600
    walk = {"walkers": 1, "zone_size": 8}
    assert counts.round_flops(m, walk, 20) == 8 * 20 * 3600
    assert counts.round_bytes(m, walk, 20) == 34 * 610 * 4 + 160 * 244
