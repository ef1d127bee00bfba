"""Operations and bytes a round needs, from the configuration's shapes.

They count what the algorithm requires, whatever implements it:

* FLOPs: the forward and backward passes of every zone member's
  minibatch (backward = 2x forward, nothing recomputed), 2 FLOPs per
  multiply-accumulate.
* Least HBM bytes of one zone's closed-form update: read x' and z' of
  the Z members and write x and z (4Z rows of P float32), read and write
  the token y (2 rows), plus the minibatch inputs the gradients read.
"""
from __future__ import annotations

import math


def forward_macs(model: dict) -> int:
    """Multiply-accumulates of one sample's forward pass."""
    if model["kind"] == "mlr":
        return int(model["features"]) * int(model["n_classes"])
    h, w, cin = model["input_shape"]
    k, c1, c2 = model["kernel"], model["conv1_channels"], model[
        "conv2_channels"]
    fc, out = model["fc_width"], model["n_classes"]
    conv1 = h * w * c1 * k * k * cin                 # SAME, stride 1
    conv2 = (h // 2) * (w // 2) * c2 * k * k * c1   # after 2x2 pooling
    dense = (h // 4) * (w // 4) * c2 * fc + fc * out
    return conv1 + conv2 + dense


def n_params(model: dict) -> int:
    if model["kind"] == "mlr":
        return (int(model["features"]) + 1) * int(model["n_classes"])
    h, w, cin = model["input_shape"]
    k, c1, c2 = model["kernel"], model["conv1_channels"], model[
        "conv2_channels"]
    fc, out = model["fc_width"], model["n_classes"]
    return ((k * k * cin + 1) * c1 + (k * k * c1 + 1) * c2
            + ((h // 4) * (w // 4) * c2 + 1) * fc + (fc + 1) * out)


def zones_per_round(traffic: dict) -> int:
    """Zones updated per round: K for a simultaneous fleet, else 1."""
    if traffic.get("fleet_mode") == "simultaneous":
        return int(traffic["walkers"])
    return 1


def round_flops(model: dict, traffic: dict, batch: int) -> float:
    zone = int(traffic["zone_size"])
    return (zones_per_round(traffic) * zone * batch * 6.0
            * forward_macs(model))


def round_bytes(model: dict, traffic: dict, batch: int) -> float:
    zone = int(traffic["zone_size"])
    p = n_params(model)
    shape = model.get("input_shape") or [model["features"]]
    sample = 4 * math.prod(shape) + 4                  # float32 x, int32 y
    return zones_per_round(traffic) * ((4 * zone + 2) * p * 4
                                       + zone * batch * sample)
