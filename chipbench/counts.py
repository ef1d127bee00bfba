"""Operations and bytes a round needs, from the configuration's shapes.

They count what the algorithm requires, whatever implements it. Each
model kind (``kinds/<kind>.py``) gives its own ``forward_macs``,
``n_params`` (the parameters of one plane row), ``round_flops`` and
``round_bytes``; the functions of the same names here find the kind by
the model's ``kind``. The helpers below are the counts of a kind whose
every weight is trained and is a plane row:

* FLOPs: the forward and backward passes of every zone member's
  minibatch (backward = 2x forward, nothing recomputed), 2 FLOPs per
  multiply-accumulate.
* Least HBM bytes of one zone's closed-form update: read x' and z' of
  the Z members and write x and z (4Z rows of P float32), read and write
  the token y (2 rows), plus the minibatch inputs the gradients read.
"""
from __future__ import annotations

from . import kinds


def _kind(model: dict):
    return kinds.load(model["kind"])


def forward_macs(model: dict) -> int:
    """Multiply-accumulates of one sample's forward pass."""
    return _kind(model).forward_macs(model)


def n_params(model: dict) -> int:
    """Parameters of one client's plane row."""
    return _kind(model).n_params(model)


def round_flops(model: dict, traffic: dict, batch: int) -> float:
    return _kind(model).round_flops(model, traffic, batch)


def round_bytes(model: dict, traffic: dict, batch: int) -> float:
    return _kind(model).round_bytes(model, traffic, batch)


def zones_per_round(traffic: dict) -> int:
    """Zones updated per round: K for a simultaneous fleet, else 1."""
    if traffic.get("fleet_mode") == "simultaneous":
        return int(traffic["walkers"])
    return 1


def all_trained_flops(traffic: dict, batch: int, macs: int) -> float:
    """FLOPs of a round whose every weight takes a gradient, from the
    forward MACs of one sample."""
    zone = int(traffic["zone_size"])
    return zones_per_round(traffic) * zone * batch * 6.0 * macs


def all_rows_bytes(traffic: dict, batch: int, row_params: int,
                   sample_bytes: int) -> float:
    """Least bytes of a round whose every weight is a plane row of
    ``row_params`` float32, from the bytes of one training sample."""
    zone = int(traffic["zone_size"])
    return zones_per_round(traffic) * ((4 * zone + 2) * row_params * 4
                                       + zone * batch * sample_bytes)
