"""The paper's CNN (App. D.1): two 5x5 SAME convolutions with ReLU and
2x2 max-pooling, FC-512, a 10-way head, dropout 25% / 50% in training,
on CIFAR-shaped synthetic images under the paper's pathological split.

The benchmark's own copies of the definitions, so that a change to the
program's ``data/`` or ``models/`` cannot move the yardstick:

* images: the recipe of ``repro.data.synthetic_images.make_image_dataset``
  (smooth class prototypes + noise + a random translation), made on the
  device in one jitted call, split 2 labels a client with a 75/25
  train/test split per client (``data.py``);
* weights: He-normal convolutions and dense layers, zero biases, made on
  the device in one jitted call, in the parameter layout
  ``repro.models.small`` reads; every weight is a plane row.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, data
from chipbench.reference import accuracy, xent


# ------------------------------------------------------------- weights --
def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)


@functools.partial(jax.jit, static_argnames=("shape",))
def _init(key, shape: tuple):
    """shape = (h, w, cin, c1, c2, fc, k, classes); float32."""
    h, w, cin, c1, c2, fc, k, n_out = shape
    k1, k2, k3, k4 = jax.random.split(key, 4)
    flat = (h // 4) * (w // 4) * c2
    return {
        "conv1": {"w": _he(k1, (k, k, cin, c1), k * k * cin),
                  "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": _he(k2, (k, k, c1, c2), k * k * c1),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc": {"w": _he(k3, (flat, fc), flat),
               "b": jnp.zeros((fc,), jnp.float32)},
        "out": {"w": _he(k4, (fc, n_out), fc),
                "b": jnp.zeros((n_out,), jnp.float32)},
    }


def weights(key, model_cfg: dict):
    h, w, c = model_cfg["input_shape"]
    return _init(key, (h, w, c, model_cfg["conv1_channels"],
                       model_cfg["conv2_channels"], model_cfg["fc_width"],
                       model_cfg["kernel"], model_cfg["n_classes"])), None


# ---------------------------------------------------------------- data --
@functools.partial(jax.jit, static_argnames=("n", "shape", "classes",
                                             "max_shift"))
def images(key, n: int, shape: tuple, classes: int, noise: float,
           max_shift: int):
    """(images (n, h, w, c) float32, labels (n,) int32) on the device."""
    kp, kl, kn, ks = jax.random.split(key, 4)
    protos = jax.random.normal(kp, (classes,) + shape, jnp.float32)
    for axis in (1, 2):            # low-frequency field: box-blur H and W
        for _ in range(3):
            protos = 0.5 * protos + 0.25 * (jnp.roll(protos, 1, axis)
                                            + jnp.roll(protos, -1, axis))
    protos = (protos - protos.min()) / (protos.max() - protos.min() + 1e-8)
    labels = jax.random.randint(kl, (n,), 0, classes, jnp.int32)
    x = protos[labels] + noise * jax.random.normal(kn, (n,) + shape)
    shifts = jax.random.randint(ks, (n, 2), -max_shift, max_shift + 1)
    h, w = shape[:2]                # roll each image by its own shift
    rows = (jnp.arange(h)[None, :] - shifts[:, :1]) % h
    cols = (jnp.arange(w)[None, :] - shifts[:, 1:]) % w
    x = x[jnp.arange(n)[:, None, None], rows[:, :, None], cols[:, None, :]]
    return jnp.clip(x, -1.0, 2.0), labels


def columns(config: dict, n_clients: int, seed: int):
    """The dense client plane on the device: the six columns of
    ``repro.fl.base.DeviceData``, padded rows zero-filled."""
    data_cfg = config["data"]
    s_img, s_split = data.sub_seeds(seed, 2)
    shape = tuple(data_cfg["input_shape"])
    x, labels = images(jax.random.PRNGKey(s_img), int(data_cfg["n_samples"]),
                       shape, int(data_cfg["n_classes"]),
                       float(data_cfg["noise"]), int(data_cfg["max_shift"]))
    parts = data.pathological_split(
        np.asarray(labels), n_clients,
        labels_per_client=int(data_cfg["labels_per_client"]),
        size_variability=float(data_cfg["size_variability"]), seed=s_split)
    frac = float(data_cfg["test_frac"])
    tr_te = [data.train_test(len(p), frac, s_split + k)
             for k, p in enumerate(parts)]
    # Rows padded to the most a client can hold, whatever the seed, so
    # that every seed runs the same shapes (and the same programs).
    base = int(data_cfg["n_samples"]) // (
        n_clients * int(data_cfg["labels_per_client"]))
    most = int(data_cfg["labels_per_client"]) * int(
        base * (1.0 + float(data_cfg["size_variability"])))
    m_te = max(1, int(round(most * frac)))
    m_tr = most - m_te
    i_tr = np.zeros((n_clients, m_tr), np.int32)
    i_te = np.zeros((n_clients, m_te), np.int32)
    n_tr = np.zeros((n_clients,), np.int32)
    mask_tr = np.zeros((n_clients, m_tr), np.float32)
    mask_te = np.zeros((n_clients, m_te), np.float32)
    for k, (p, (t, e)) in enumerate(zip(parts, tr_te)):
        i_tr[k, :len(t)], i_te[k, :len(e)] = p[t], p[e]
        n_tr[k] = len(t)
        mask_tr[k, :len(t)], mask_te[k, :len(e)] = 1.0, 1.0
    cols = _gather(x, labels, jnp.asarray(i_tr), jnp.asarray(mask_tr),
                   jnp.asarray(i_te), jnp.asarray(mask_te))
    return cols[:2] + (jnp.asarray(n_tr),) + cols[2:]


@jax.jit
def _gather(x, labels, i_tr, m_tr, i_te, m_te):
    def rows(i, m):
        mx = m.reshape(m.shape + (1,) * (x.ndim - 1))
        return (jnp.where(mx > 0, x[i], 0.0),
                jnp.where(m > 0, labels[i], 0))
    xt, yt = rows(i_tr, m_tr)
    xe, ye = rows(i_te, m_te)
    return xt, yt, xe, ye, m_te


# ------------------------------------------------------------- program --
def program_model(model_cfg: dict, trainable, frozen):
    from repro.models.small import make_cnn

    model = make_cnn(tuple(model_cfg["input_shape"]),
                     int(model_cfg["n_classes"]),
                     c1=int(model_cfg["conv1_channels"]),
                     c2=int(model_cfg["conv2_channels"]),
                     fc=int(model_cfg["fc_width"]))
    return dataclasses.replace(model, init=lambda key: trainable)


# ----------------------------------------------------------- reference --
def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf,
                                 jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1),
                                 "VALID")


def _dropout(x, key, keep):
    m = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(m, x / jnp.asarray(keep, x.dtype), 0.0).astype(x.dtype)


def logits(p, x, key=None):
    h = _pool(jax.nn.relu(_conv(x, p["conv1"]["w"], p["conv1"]["b"])))
    if key is not None:
        h = _dropout(h, jax.random.fold_in(key, 1), 0.75)
    h = _pool(jax.nn.relu(_conv(h, p["conv2"]["w"], p["conv2"]["b"])))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ p["fc"]["w"] + p["fc"]["b"])
    if key is not None:
        h = _dropout(h, jax.random.fold_in(key, 2), 0.5)
    return h @ p["out"]["w"] + p["out"]["b"]


def loss(params, frozen, xb, yb, key):
    return xent(logits(params, xb, key), yb)


def eval_stats(params, frozen, x, y, mask):
    out = logits(params, x)
    return accuracy(out, y, mask), xent(out, y, mask)


# -------------------------------------------------------------- counts --
def forward_macs(model: dict) -> int:
    """Multiply-accumulates of one sample's forward pass."""
    h, w, cin = model["input_shape"]
    k, c1, c2 = model["kernel"], model["conv1_channels"], model[
        "conv2_channels"]
    fc, out = model["fc_width"], model["n_classes"]
    conv1 = h * w * c1 * k * k * cin                 # SAME, stride 1
    conv2 = (h // 2) * (w // 2) * c2 * k * k * c1   # after 2x2 pooling
    dense = (h // 4) * (w // 4) * c2 * fc + fc * out
    return conv1 + conv2 + dense


def n_params(model: dict) -> int:
    h, w, cin = model["input_shape"]
    k, c1, c2 = model["kernel"], model["conv1_channels"], model[
        "conv2_channels"]
    fc, out = model["fc_width"], model["n_classes"]
    return ((k * k * cin + 1) * c1 + (k * k * c1 + 1) * c2
            + ((h // 4) * (w // 4) * c2 + 1) * fc + (fc + 1) * out)


def round_flops(model: dict, traffic: dict, batch: int) -> float:
    return counts.all_trained_flops(traffic, batch, forward_macs(model))


def round_bytes(model: dict, traffic: dict, batch: int) -> float:
    sample = 4 * math.prod(model["input_shape"]) + 4   # float32 x, int32 y
    return counts.all_rows_bytes(traffic, batch, n_params(model), sample)


# ---------------------------------------------------------------- test --
def shrink(config: dict, traffic: dict):
    """8x8 images, 240 samples over 6 clients, zones of 3 (2 a walker in
    a fleet), a snapshot every 2 rounds; widths as published."""
    cfg, tr = copy.deepcopy(config), copy.deepcopy(traffic)
    tr["eval_every"] = 2
    cfg["n_clients"] = 6
    cfg["data"].update(n_samples=240, input_shape=[8, 8, 3])
    cfg["model"]["input_shape"] = [8, 8, 3]
    tr["zone_size"] = 2 if tr["walkers"] > 1 else 3
    return cfg, tr
