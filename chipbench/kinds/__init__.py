"""Model kinds: one module per ``model.kind`` of a configuration,
``chipbench/kinds/<kind>.py``, loaded by path from the root a cell was
read from, as ``harness.metric_reader`` loads a metric. A configuration
of a new architecture is added as files alone: its kind module, its
configuration, traffic and limits files, and entries in
``BENCHMARK.json``.

A kind module provides:

* ``weights(key, model_cfg) -> (trainable, frozen)``: the weights made
  on the device from a PRNG key. Every client and token holds a row of
  ``trainable``; ``frozen`` is a tree held once, never a plane row, or
  None.
* ``columns(config, n_clients, seed)``: the six ``DeviceData`` columns
  of the dense client plane (x_train, y_train, n_train, x_test, y_test,
  mask_test) from the benchmark's own generator.
* ``program_model(model_cfg, trainable, frozen)``: what the program's
  trainers take as ``model``. The only place a kind imports ``repro``.
* ``loss(params, frozen, xb, yb, key)``: the reference's training
  forward pass over one minibatch (dropout keyed by ``key``) and its
  mean loss.
* ``eval_stats(params, frozen, x, y, mask) -> (acc, loss)``: the
  reference's accuracy and mean loss over the test rows ``mask`` keeps.
* ``forward_macs(model_cfg)``, ``n_params(model_cfg)`` (the parameters
  of one plane row), ``round_flops(model_cfg, traffic, batch)`` and
  ``round_bytes(model_cfg, traffic, batch)``: see ``counts.py``.
* ``shrink(config, traffic) -> (config, traffic)``: copies cut to a
  size the CPU test suite holds.
"""
from __future__ import annotations

import functools
import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(kind: str, root: str = REPO):
    """The module of ``kind`` under ``root``; one per (kind, root), so
    that its jitted functions compile once in a process."""
    return _load(kind, os.path.abspath(root))


@functools.cache
def _load(kind: str, root: str):
    path = os.path.join(root, "chipbench", "kinds", kind + ".py")
    if not kind.isidentifier() or not os.path.exists(path):
        raise ValueError(f"no model kind {kind!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
