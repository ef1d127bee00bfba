"""A model kind made of files alone, for ``test_chipbench_kinds.py``,
which copies it to ``chipbench/kinds/mlp.py`` under a root of its own.

The paper's MLP (App. D.1: two hidden dense layers with ReLU,
``repro.models.small.make_mlp``) on Gaussian clients, each shifted from
one shared teacher. ``model.frozen`` names layers held once beside the
planes and never trained: the trainable tree leaves them out, the
program's model and the reference close over them.
"""
from __future__ import annotations

import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.reference import accuracy, xent

LAYERS = ("fc1", "fc2", "out")


def _widths(model_cfg: dict) -> dict:
    f, h, c = (int(model_cfg[k]) for k in ("features", "hidden",
                                             "n_classes"))
    return {"fc1": (f, h), "fc2": (h, h), "out": (h, c)}


# ------------------------------------------------------------- weights --
@functools.partial(jax.jit, static_argnames=("widths",))
def _init(key, widths: tuple):
    out = {}
    for (name, (n_in, n_out)), k in zip(widths, jax.random.split(
            key, len(widths))):
        out[name] = {"w": jax.random.normal(k, (n_in, n_out), jnp.float32)
                     * jnp.sqrt(2.0 / n_in),
                     "b": jnp.zeros((n_out,), jnp.float32)}
    return out


def weights(key, model_cfg: dict):
    tree = _init(key, tuple(_widths(model_cfg).items()))
    held = set(model_cfg.get("frozen", []))
    if not held:
        return tree, None
    return ({k: v for k, v in tree.items() if k not in held},
            {k: v for k, v in tree.items() if k in held})


# ---------------------------------------------------------------- data --
@functools.partial(jax.jit, static_argnames=("n", "m_tr", "m_te", "f", "c"))
def _clients(key, n: int, m_tr: int, m_te: int, f: int, c: int,
             shift: float):
    kt, ks, kx, kn = jax.random.split(key, 4)
    teacher = jax.random.normal(kt, (f, c))
    mu = shift * jax.random.normal(ks, (n, 1, f))
    x = jax.random.normal(kx, (n, m_tr + m_te, f)) + mu
    y = jnp.argmax(x @ teacher, axis=-1).astype(jnp.int32)
    n_tr = jax.random.randint(kn, (n,), m_tr // 2, m_tr + 1)
    return (x[:, :m_tr], y[:, :m_tr], n_tr.astype(jnp.int32), x[:, m_tr:],
            y[:, m_tr:], jnp.ones((n, m_te), jnp.float32))


def columns(config: dict, n_clients: int, seed: int):
    d = config["data"]
    return _clients(jax.random.PRNGKey(seed), n_clients, int(d["train_rows"]),
                    int(d["test_rows"]), int(config["model"]["features"]),
                    int(config["model"]["n_classes"]), float(d["shift"]))


# ------------------------------------------------------------- program --
def program_model(model_cfg: dict, trainable, frozen):
    from repro.models.small import make_mlp

    model = make_mlp((int(model_cfg["features"]),),
                     int(model_cfg["n_classes"]),
                     hidden=int(model_cfg["hidden"]))
    apply = model.apply
    if frozen is not None:
        def apply(params, x, *, train=False, rng=None):
            return model.apply({**frozen, **params}, x, train=train, rng=rng)
    return dataclasses.replace(model, init=lambda key: trainable,
                               apply=apply)


# ----------------------------------------------------------- reference --
def logits(p, x):
    h = x.reshape(x.shape[0], -1)
    for name in LAYERS:
        h = h @ p[name]["w"] + p[name]["b"]
        if name != "out":
            h = jax.nn.relu(h)
    return h


def loss(params, frozen, xb, yb, key):
    return xent(logits({**(frozen or {}), **params}, xb), yb)


def eval_stats(params, frozen, x, y, mask):
    out = logits({**(frozen or {}), **params}, x)
    return accuracy(out, y, mask), xent(out, y, mask)


# -------------------------------------------------------------- counts --
def forward_macs(model: dict) -> int:
    return sum(a * b for a, b in _widths(model).values())


def n_params(model: dict) -> int:
    held = set(model.get("frozen", []))
    return sum((a + 1) * b for k, (a, b) in _widths(model).items()
               if k not in held)


def round_flops(model: dict, traffic: dict, batch: int) -> float:
    # Forward through every layer; backward (2x) through the trained ones.
    trained = sum(a * b for k, (a, b) in _widths(model).items()
                  if k not in set(model.get("frozen", [])))
    zones = counts.zones_per_round(traffic) * int(traffic["zone_size"])
    return zones * batch * 2.0 * (forward_macs(model) + 2 * trained)


def round_bytes(model: dict, traffic: dict, batch: int) -> float:
    sample = 4 * int(model["features"]) + 4
    return counts.all_rows_bytes(traffic, batch, n_params(model), sample)


# ---------------------------------------------------------------- test --
def shrink(config: dict, traffic: dict):
    """40 clients, zones of 3, a snapshot every 2 rounds."""
    cfg, tr = copy.deepcopy(config), copy.deepcopy(traffic)
    cfg["n_clients"] = 40
    tr.update(zone_size=3, eval_every=2)
    return cfg, tr
