"""Plain reference of what a timed ``run_simulation`` computes.

Independent of the program: nothing here imports ``repro``. The
benchmark runs it once the measured window has closed, on the host's
CPU, where float32 is float32 whatever the matmul precision setting, or
on the accelerator at ``highest`` precision (the configuration's
``reference_device``). It replays recorded walk schedules (which client
zones were visited, with which per-round keys) through straightforward
``jax.numpy`` code:

* the model's forward pass and loss from its kind module
  (``kinds/<kind>.py``: ``loss`` and ``eval_stats``), over the
  trainable tree, with the kind's frozen tree closed over;
* per client, a minibatch drawn uniformly with replacement from its
  training rows by ``randint(key, (B,), 0, n_train)``, the training
  forward pass using the same key for dropout, the mean loss and its
  gradient;
* the RWSADMM closed-form round (paper Eq. 10/11, 15, 14/31, with
  ε/2 in the reformulated constraint and the y fold scaled by 1/n):
      s  = sign(y − x'),  c(x, z) = x − (z/β + ε/2)·sign(y − x)
      x  = y − g/β + s·(z' − βε/2)/β
      z  = z' + κβ·(x − y − ε/2)
      y ← y + Σ_active (c(x, z) − c(x', z')) / n,     κ ← 0.99 κ;
* simultaneous fleets: K disjoint zones per round, each against its own
  token, then token averaging on rendezvous rounds;
* evaluation: per client, the personalized model (x if the client was
  ever active, else the token; the fleet's mean token) and the token on
  the client's test rows, averaged over clients.

``dtype``, ``half_batch`` and ``frozen`` put the reference's
lower-precision, half-batch and state-left-unchanged variants in the
program's place for the control and fault readings.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------- one label a sample --
def xent(logits, labels, mask=None):
    """Mean cross-entropy of one label a row (over the rows ``mask``
    keeps), in float32: the loss of kinds whose sample has one label."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def accuracy(logits, labels, mask):
    hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    return jnp.sum(hit * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------- state --
@dataclasses.dataclass(frozen=True)
class Solver:
    """The closed-form solver's constants and the run's shape."""

    model: object        # the kind's module (``kinds/<kind>.py``)
    beta: float
    kappa: float
    kappa_decay: float
    eps_half: float
    n_total: float       # the population n the y fold divides by
    batch: int
    dtype: str = "float32"
    half_batch: bool = False
    frozen: bool = False     # each round returns the state it was given


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def _zone_update(sv: Solver, x, z, y, g, kappa):
    """Closed-form x/z update of one client and its contribution pair."""
    beta = jnp.asarray(sv.beta, x.dtype)
    eps = jnp.asarray(sv.eps_half, x.dtype)
    c_old = x - (z / beta + eps) * jnp.sign(y - x)
    x_new = y - g / beta + jnp.sign(y - x) * (z - beta * eps) / beta
    z_new = z + kappa * beta * (x_new - y - eps)
    c_new = x_new - (z_new / beta + eps) * jnp.sign(y - x_new)
    return x_new, z_new, c_new - c_old


def _client_grad(sv: Solver, params, frozen, data, client, key):
    x_tr, y_tr, n_tr = data
    idx = jax.random.randint(key, (sv.batch,), 0, n_tr[client])
    if sv.half_batch:
        idx = idx[: sv.batch // 2]
    xb, yb = x_tr[client, idx], y_tr[client, idx]

    def loss(p):
        return sv.model.loss(p, frozen, xb, yb, key)

    return jax.value_and_grad(loss)(params)


def _zones_step(sv: Solver, frozen, data, carry, per):
    """One round of K ≥ 1 disjoint zones: (X, Z, tokens, kappa, visited)
    with ``idx``/``mask`` (K, Zs) rows of the compact client table."""
    X, Zs, tokens, kappa, visited = carry
    idx, mask, key, sync = per
    K, zs = idx.shape
    keys = jax.random.split(key, K * zs).reshape(K, zs, -1)
    act_x = _tmap(lambda l: l[idx], X)
    act_z = _tmap(lambda l: l[idx], Zs)
    # One member at a time: a plain (unbatched) forward and backward pass.
    flat = lambda t: _tmap(lambda l: l.reshape((K * zs,) + l.shape[2:]), t)
    losses, grads = jax.lax.map(
        lambda a: _client_grad(sv, a[0], frozen, data, a[1], a[2]),
        (flat(act_x), idx.reshape(-1), keys.reshape(K * zs, -1)))
    losses = losses.reshape(K, zs)
    grads = _tmap(lambda l: l.reshape((K, zs) + l.shape[1:]), grads)

    def per_leaf(x, z, y, g):
        upd = jax.vmap(jax.vmap(
            lambda xx, zz, gg, yy: _zone_update(sv, xx, zz, yy, gg, kappa),
            in_axes=(0, 0, 0, None)), in_axes=(0, 0, 0, 0))
        x_n, z_n, dc = upd(x, z, g, y)
        mm = mask.reshape(mask.shape + (1,) * (x.ndim - 2)).astype(x.dtype)
        y_n = y + jnp.sum(mm * dc, axis=1) / jnp.asarray(sv.n_total, x.dtype)
        return x_n - x, z_n - z, y_n

    out = _tmap(per_leaf, act_x, act_z, tokens, grads)
    is_t = lambda t: isinstance(t, tuple)
    pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out, is_leaf=is_t)
    dx, dz, new_tokens = pick(0), pick(1), pick(2)
    new_tokens = _tmap(lambda t: jnp.where(sync > 0, jnp.mean(
        t, axis=0, keepdims=True).astype(t.dtype), t), new_tokens)
    flat = idx.reshape(-1)

    def scatter(full, d):
        mm = mask.reshape((-1,) + (1,) * (d.ndim - 2)).astype(d.dtype)
        return full.at[flat].add(mm * d.reshape((-1,) + d.shape[2:]))

    X = _tmap(scatter, X, dx)
    Zs = _tmap(scatter, Zs, dz)
    visited = visited.at[flat].max(mask.reshape(-1) > 0)
    loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if sv.frozen:
        return carry, loss
    return (X, Zs, new_tokens, kappa * jnp.asarray(sv.kappa_decay,
                                                   kappa.dtype),
            visited), loss


@functools.partial(jax.jit, static_argnames=("sv",))
def _run_rounds(sv: Solver, X, Zs, tokens, kappa, visited, frozen, data, idx,
                mask, keys, sync):
    (X, Zs, tokens, kappa, visited), losses = jax.lax.scan(
        functools.partial(_zones_step, sv, frozen, data),
        (X, Zs, tokens, kappa, visited), (idx, mask, keys, sync))
    return X, Zs, tokens, kappa, visited, losses


@functools.partial(jax.jit, static_argnames=("sv",))
def _first_grad_norms(sv: Solver, params, frozen, data, client, key):
    """Per-leaf norm of one client's first minibatch gradient."""
    _, g = _client_grad(sv, params, frozen, data, client, key)
    return _tmap(lambda l: jnp.sqrt(jnp.sum(l.astype(jnp.float32) ** 2)), g)


@functools.partial(jax.jit, static_argnames=("model",))
def _eval_clients(model, X, token, visited, frozen, rows, x_te, y_te, m_te):
    """Per-client (acc, loss) of the personalized model and the token,
    one client at a time over ``rows`` of the compact table."""

    def one(r):
        p = _tmap(lambda x, t: jnp.where(visited[r], x[r], t), X, token)
        return (model.eval_stats(p, frozen, x_te[r], y_te[r], m_te[r])
                + model.eval_stats(token, frozen, x_te[r], y_te[r], m_te[r]))

    return jax.lax.map(one, rows)


# ------------------------------------------------------------- driving --
@dataclasses.dataclass
class Outputs:
    """What a run is compared on (program or reference alike)."""

    losses: np.ndarray                    # (R,) zone train loss per round
    evals: list                           # per snapshot: {key: float}
    norms: dict                           # leaf path -> norm of its change
    first_grad: dict | None = None        # leaf path -> first grad norm


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def change_norms(X, Zs, tokens, w, rows) -> dict:
    """Per-leaf norms of x − w and z over ``rows`` of the client stacks,
    and of tokens − w (the token stack (K, …) for a fleet), where ``w``
    are the weights every client and token started from."""
    rows = jnp.asarray(rows)
    f32 = lambda a: jnp.asarray(a).astype(jnp.float32)

    def norm(a):
        return float(jnp.sqrt(jnp.sum(jnp.square(a))))

    names = leaf_paths(w)
    w0 = [f32(l) for l in jax.tree_util.tree_leaves(w)]
    out = {}
    for name, x, z, t, l0 in zip(names, jax.tree_util.tree_leaves(X),
                                 jax.tree_util.tree_leaves(Zs),
                                 jax.tree_util.tree_leaves(tokens), w0):
        out["x/" + name] = norm(f32(x)[rows] - l0[None])
        out["z/" + name] = norm(f32(z)[rows])
        out["y/" + name] = norm(f32(t) - l0[None])
    return out


def simulate(sv: Solver, weights, frozen, data, schedules, *, fleet: bool,
             eval_data) -> Outputs:
    """Replay ``schedules`` (one per chunk, a snapshot after each) from
    ``weights`` (the trainable tree) over every client.

    frozen: the kind's tree held once beside the planes, or None.
    data: (x_train, y_train, n_train) of the clients.
    schedules: dicts with ``idx``/``mask`` (R, Z) or (R, K, Z) of client
        ids, ``keys`` (R, 2) uint32 and ``sync`` (R,).
    eval_data: (x_test, y_test, mask_test) of the clients.
    """
    dt = jnp.dtype(sv.dtype)
    # Floating leaves in the reference's type; ids and labels as they are.
    cast = lambda t: _tmap(lambda l: l.astype(dt) if jnp.issubdtype(
        l.dtype, jnp.floating) else l, t)
    w, frozen = cast(weights), cast(frozen)
    n_rows = data[0].shape[0]
    X0 = _tmap(lambda l: jnp.broadcast_to(l, (n_rows,) + l.shape), w)
    X, Zs = X0, _tmap(jnp.zeros_like, X0)
    K = int(np.asarray(schedules[0]["idx"]).shape[1]) if fleet else 1
    tokens = _tmap(lambda l: jnp.broadcast_to(l, (K,) + l.shape), w)
    kappa = jnp.asarray(sv.kappa, dt)
    visited = jnp.zeros((n_rows,), bool)
    tdata = (cast(data[0]), data[1], data[2])
    edata = (cast(eval_data[0]), eval_data[1], eval_data[2])
    rows = jnp.arange(n_rows)
    losses, evals = [], []
    first = None
    with jax.default_matmul_precision("highest"):
        for s in schedules:
            idx = np.asarray(s["idx"], np.int32)
            mask = np.asarray(s["mask"], np.float32)
            if not fleet:
                idx, mask = idx[:, None], mask[:, None]
            if first is None:
                live = int(np.flatnonzero(mask[0].reshape(-1) > 0)[0])
                key0 = jax.random.split(jnp.asarray(s["keys"][0]),
                                        idx.shape[1] * idx.shape[2])[live]
                first = _first_grad_norms(sv, w, frozen, tdata,
                                          int(idx[0].reshape(-1)[live]), key0)
            X, Zs, tokens, kappa, visited, ls = _run_rounds(
                sv, X, Zs, tokens, kappa, visited, frozen, tdata,
                jnp.asarray(idx),
                jnp.asarray(mask.astype(dt)), jnp.asarray(s["keys"]),
                jnp.asarray(s["sync"], np.float32))
            losses.append(np.asarray(ls, np.float32))
            tok = _tmap(lambda t: jnp.mean(t.astype(jnp.float32), axis=0)
                        .astype(dt), tokens)
            acc_p, loss_p, acc_g, loss_g = (
                np.asarray(v, np.float64) for v in _eval_clients(
                    sv.model, X, tok, visited, frozen, rows, *edata))
            evals.append({"acc_personalized": float(acc_p.mean()),
                          "loss_personalized": float(loss_p.mean()),
                          "acc_global": float(acc_g.mean()),
                          "loss_global": float(loss_g.mean())})
    return Outputs(losses=np.concatenate(losses), evals=evals,
                   norms=change_norms(X, Zs, tokens, w, rows),
                   first_grad={k: float(v) for k, v in zip(
                       leaf_paths(w), jax.tree_util.tree_leaves(first))})
