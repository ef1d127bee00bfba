"""The benchmark's own data and weight generators, made from the seed.

Copies of the deployments' definitions, kept here so that a change to
the program's ``data/`` or ``models/`` cannot move the yardstick:

* CIFAR-shaped synthetic images (the recipe of
  ``repro.data.synthetic_images.make_image_dataset``: smooth class
  prototypes + noise + a random translation), generated on the device
  in one jitted call;
* the paper's pathological split (2 labels per client, variable sizes,
  ``repro.data.partition.pathological_split``) and a 75/25 train/test
  split per client, on the host from the labels alone;
* Synthetic(α, β) clients (pFedMe / FedProx procedure, paper §5), one
  independent stream per client so that any client is drawn in O(1);
* model weights (He-normal convolutions and dense layers, zero biases,
  MLR at scale 0.01), made on the device in one jitted call, in the
  parameter layout ``repro.models.small`` reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sub_seeds(seed: int, n: int = 4) -> list[int]:
    """``n`` independent 31-bit seeds from any whole ``seed``."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in state]


# ------------------------------------------------------------- weights --
def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)


@functools.partial(jax.jit, static_argnames=("model",))
def init_weights(key, model: tuple):
    """Weights for ``model`` = ("cnn", h, w, cin, c1, c2, fc, k, classes)
    or ("mlr", features, classes), float32 on the device."""
    kind = model[0]
    if kind == "mlr":
        _, n_in, n_out = model
        return {"linear": {
            "w": jax.random.normal(key, (n_in, n_out), jnp.float32) * 0.01,
            "b": jnp.zeros((n_out,), jnp.float32)}}
    _, h, w, cin, c1, c2, fc, k, n_out = model
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


def model_key(model_cfg: dict) -> tuple:
    """The static description ``init_weights`` takes, from a config's
    ``model`` block."""
    if model_cfg["kind"] == "mlr":
        return ("mlr", int(model_cfg["features"]),
                int(model_cfg["n_classes"]))
    h, w, c = model_cfg["input_shape"]
    return ("cnn", h, w, c, model_cfg["conv1_channels"],
            model_cfg["conv2_channels"], model_cfg["fc_width"],
            model_cfg["kernel"], model_cfg["n_classes"])


# -------------------------------------------------------------- images --
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


def pathological_split(labels: np.ndarray, n_clients: int, *,
                       labels_per_client: int, size_variability: float,
                       seed: int) -> list[np.ndarray]:
    """Per-client index arrays, each from exactly ``labels_per_client``
    classes, sizes within ±``size_variability`` of the mean."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    by_class = [np.flatnonzero(labels == c) for c in range(n_classes)]
    for idx in by_class:
        rng.shuffle(idx)
    ptr = [0] * n_classes
    pool = rng.permutation(np.tile(np.arange(n_classes), int(np.ceil(
        n_clients * labels_per_client / n_classes))))
    p = 0
    base = len(labels) // (n_clients * labels_per_client)
    out = []
    for _ in range(n_clients):
        chosen: list[int] = []
        while len(chosen) < labels_per_client:
            c = int(pool[p % len(pool)])
            p += 1
            if c not in chosen:
                chosen.append(c)
        take = []
        for c in chosen:
            frac = 1.0 + size_variability * (rng.random() * 2.0 - 1.0)
            cnt = max(4, int(base * frac))
            avail = len(by_class[c]) - ptr[c]
            if avail < cnt:        # class exhausted: recycle
                extra = rng.choice(by_class[c], size=cnt - avail)
                take.append(np.concatenate([by_class[c][ptr[c]:], extra]))
                ptr[c] = len(by_class[c])
            else:
                take.append(by_class[c][ptr[c]:ptr[c] + cnt])
                ptr[c] += cnt
        out.append(np.concatenate(take))
    return out


def train_test(n: int, test_frac: float, seed: int):
    """(train, test) positions of one client's ``n`` samples."""
    perm = np.random.default_rng(seed).permutation(n)
    n_test = max(1, int(round(n * test_frac)))
    return perm[n_test:], perm[:n_test]


def stacked_images(data_cfg: dict, n_clients: int, seed: int):
    """The dense client plane of an image config, on the device: the six
    columns of ``repro.fl.base.DeviceData`` (x_train, y_train, n_train,
    x_test, y_test, mask_test), padded rows zero-filled."""
    s_img, s_split = sub_seeds(seed, 2)
    shape = tuple(data_cfg["input_shape"])
    x, labels = images(jax.random.PRNGKey(s_img), int(data_cfg["n_samples"]),
                       shape, int(data_cfg["n_classes"]),
                       float(data_cfg["noise"]), int(data_cfg["max_shift"]))
    parts = pathological_split(
        np.asarray(labels), n_clients,
        labels_per_client=int(data_cfg["labels_per_client"]),
        size_variability=float(data_cfg["size_variability"]), seed=s_split)
    frac = float(data_cfg["test_frac"])
    tr_te = [train_test(len(p), frac, s_split + k)
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


# ------------------------------------------------- Synthetic(alpha, beta) --
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
        tr, te = train_test(count, self.test_frac, self.seed + int(k))
        x, y = x.astype(np.float32), y.astype(np.int32)
        return x[tr], y[tr], x[te], y[te]


def stacked_synthetic(data_cfg: dict, n_clients: int, seed: int):
    """The dense client plane of a Synthetic(α, β) config: every client
    drawn on the host and stacked into the six ``DeviceData`` columns."""
    gen = SyntheticLR(data_cfg, n_clients, seed)
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
