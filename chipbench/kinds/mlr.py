"""Multinomial logistic regression on Synthetic(α, β) clients (the
pFedMe / FedProx procedure, paper §5).

The benchmark's own copies of the definitions, so that a change to the
program's ``data/`` or ``models/`` cannot move the yardstick:

* clients: one independent stream per client, so that any client is
  drawn in O(1), stacked on the host into the dense plane;
* weights: normal at scale 0.01, zero bias, made on the device in one
  jitted call, in the layout ``repro.models.small`` reads; every weight
  is a plane row.
"""
from __future__ import annotations

import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, data
from chipbench.reference import accuracy, xent


# ------------------------------------------------------------- weights --
@functools.partial(jax.jit, static_argnames=("n_in", "n_out"))
def _init(key, n_in: int, n_out: int):
    return {"linear": {
        "w": jax.random.normal(key, (n_in, n_out), jnp.float32) * 0.01,
        "b": jnp.zeros((n_out,), jnp.float32)}}


def weights(key, model_cfg: dict):
    return _init(key, int(model_cfg["features"]),
                 int(model_cfg["n_classes"])), None


# ---------------------------------------------------------------- data --
class SyntheticLR:
    """Synthetic(α, β) clients, client ``k`` drawn from its own stream
    ``default_rng([seed, k])``: any client in O(1), identical on every
    draw. Sample counts (lognormal + ``min_samples``, at most
    ``max_samples``) are the one O(n) precompute."""

    def __init__(self, data_cfg: dict, n_clients: int, seed: int):
        self.n_clients = int(n_clients)
        self.alpha = float(data_cfg["alpha"])
        self.beta = float(data_cfg["beta"])
        self.features = int(data_cfg["features"])
        self.classes = int(data_cfg["n_classes"])
        self.test_frac = float(data_cfg["test_frac"])
        self.seed = int(seed)
        self.cov_sqrt = np.sqrt(np.arange(1, self.features + 1,
                                          dtype=np.float64) ** -1.2)
        counts = np.random.default_rng([self.seed, self.n_clients]).lognormal(
            float(data_cfg["mean_samples"]), 1.0, self.n_clients).astype(int)
        # Counts capped at ``max_samples``, which also fixes the padded
        # row widths, whatever the seed.
        most = int(data_cfg["max_samples"])
        self.counts = np.minimum(counts + int(data_cfg["min_samples"]), most)
        self.max_test = max(1, int(round(most * self.test_frac)))
        self.max_train = most - self.max_test

    def client(self, k: int):
        """(x_train, y_train, x_test, y_test) of client ``k``."""
        rng = np.random.default_rng([self.seed, int(k)])
        u_k = rng.normal(0.0, np.sqrt(self.alpha))
        b_k = rng.normal(0.0, np.sqrt(self.alpha))
        v_k = rng.normal(rng.normal(0.0, np.sqrt(self.beta)), 1.0,
                         self.features)
        w_k = rng.normal(u_k, 1.0, (self.features, self.classes))
        c_k = rng.normal(b_k, 1.0, self.classes)
        count = int(self.counts[k])
        x = rng.normal(v_k, self.cov_sqrt, (count, self.features))
        logits = x @ w_k + c_k
        p = np.exp(logits - logits.max(axis=1, keepdims=True))
        cdf = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
        y = np.minimum((cdf < rng.random((count, 1))).sum(axis=1),
                       self.classes - 1)
        tr, te = data.train_test(count, self.test_frac, self.seed + int(k))
        x, y = x.astype(np.float32), y.astype(np.int32)
        return x[tr], y[tr], x[te], y[te]


def columns(config: dict, n_clients: int, seed: int):
    """The dense client plane: every client drawn on the host and stacked
    into the six ``DeviceData`` columns."""
    gen = SyntheticLR(config["data"], n_clients, seed)
    f = gen.features
    xt = np.zeros((n_clients, gen.max_train, f), np.float32)
    yt = np.zeros((n_clients, gen.max_train), np.int32)
    nt = np.zeros((n_clients,), np.int32)
    xe = np.zeros((n_clients, gen.max_test, f), np.float32)
    ye = np.zeros((n_clients, gen.max_test), np.int32)
    me = np.zeros((n_clients, gen.max_test), np.float32)
    for k in range(n_clients):
        a, b, c, d = gen.client(k)
        xt[k, :len(b)], yt[k, :len(b)], nt[k] = a, b, len(b)
        xe[k, :len(d)], ye[k, :len(d)], me[k, :len(d)] = c, d, 1.0
    return tuple(jnp.asarray(v) for v in (xt, yt, nt, xe, ye, me))


# ------------------------------------------------------------- program --
def program_model(model_cfg: dict, trainable, frozen):
    from repro.models.small import make_mlr

    model = make_mlr((int(model_cfg["features"]),),
                     int(model_cfg["n_classes"]))
    return dataclasses.replace(model, init=lambda key: trainable)


# ----------------------------------------------------------- reference --
def logits(p, x):
    return x.reshape(x.shape[0], -1) @ p["linear"]["w"] + p["linear"]["b"]


def loss(params, frozen, xb, yb, key):
    return xent(logits(params, xb), yb)


def eval_stats(params, frozen, x, y, mask):
    out = logits(params, x)
    return accuracy(out, y, mask), xent(out, y, mask)


# -------------------------------------------------------------- counts --
def forward_macs(model: dict) -> int:
    return int(model["features"]) * int(model["n_classes"])


def n_params(model: dict) -> int:
    return (int(model["features"]) + 1) * int(model["n_classes"])


def round_flops(model: dict, traffic: dict, batch: int) -> float:
    return counts.all_trained_flops(traffic, batch, forward_macs(model))


def round_bytes(model: dict, traffic: dict, batch: int) -> float:
    sample = 4 * int(model["features"]) + 4          # float32 x, int32 y
    return counts.all_rows_bytes(traffic, batch, n_params(model), sample)


# ---------------------------------------------------------------- test --
def shrink(config: dict, traffic: dict):
    """300 clients, zones of 3, a snapshot every 2 rounds."""
    cfg, tr = copy.deepcopy(config), copy.deepcopy(traffic)
    tr["eval_every"] = 2
    cfg["n_clients"] = 300
    tr["zone_size"] = 3
    return cfg, tr
