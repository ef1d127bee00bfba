"""The comparison that decides ``correct``.

Numbers read, each compared against a limit of its own where
``limits/<workload>.json`` sets one (set from the program's readings
over a dozen seeds and the control's and the planted faults', see
PERF.md):

* ``loss_gap``: over every compared round, |program − reference| of the
  zone train loss, relative to the reference's.
* ``first_loss_gap``: the same for the first round alone, whose loss
  comes from the initial weights and no update: steady from seed to
  seed, and moved by anything that changes the minibatch or the loss.
* ``norm_gap``: over leaves, the gap between the program's and the
  reference's norm of one leaf's change (x − x⁰ and z over the clients
  compared, and the tokens' y − y⁰), relative to the larger of the
  reference's norm of that leaf and the median over leaves of its kind.
  Leaves whose first reference gradient is under a thousandth of the
  median leaf's are left out: they move by round-off alone.
* ``norm_gap_median``: the median over the same leaves of that gap.
* ``eval_gap``: over every compared snapshot, |program − reference| of
  the mean personalized and global test loss, relative to the
  reference's.
* ``first_eval_gap``: the same for the first snapshot alone, before the
  drift of later rounds has grown.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
EVAL_KEYS = ("loss_personalized", "loss_global")
TINY_GRAD = 1e-3


def _rel(a, b) -> float:
    a, b = float(a), float(b)
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def loss_gap(prog_losses, ref_losses) -> float:
    p, r = np.asarray(prog_losses), np.asarray(ref_losses)
    if p.shape != r.shape or p.size == 0:
        return math.inf
    return max(_rel(a, b) for a, b in zip(p, r))


def eval_gap(prog_evals, ref_evals) -> float:
    if len(prog_evals) != len(ref_evals) or not ref_evals:
        return math.inf
    return max(_rel(p[k], r[k]) for p, r in zip(prog_evals, ref_evals)
               for k in EVAL_KEYS)


def compared_leaves(first_grad: dict) -> set:
    """Leaf paths whose first reference gradient is not nought to
    rounding (at least a thousandth of the median leaf's)."""
    med = float(np.median(list(first_grad.values())))
    return {k for k, v in first_grad.items() if v >= TINY_GRAD * med}


def leaf_gaps(prog_norms: dict, ref_norms: dict, first_grad: dict) -> dict:
    """Per compared leaf, the gap between the program's and the
    reference's norm of its change, relative to the larger of the
    reference's norm of that leaf and the median over leaves of its
    kind (x, z or y)."""
    if set(prog_norms) != set(ref_norms) or not ref_norms:
        return {"all": math.inf}
    keep = compared_leaves(first_grad)
    out = {}
    for kind in ("x", "z", "y"):
        names = [n for n in ref_norms if n.split("/", 1)[0] == kind]
        med = float(np.median([ref_norms[n] for n in names]))
        for n in names:
            if n.split("/", 1)[1] in keep:
                p, r = prog_norms[n], ref_norms[n]
                out[n] = (abs(p - r) / max(r, med, 1e-30)
                          if math.isfinite(p) else math.inf)
    return out


def compare(prog, ref) -> dict:
    """The numbers compared, from two ``reference.Outputs``."""
    gaps = list(leaf_gaps(prog.norms, ref.norms, ref.first_grad).values())
    return {"loss_gap": loss_gap(prog.losses, ref.losses),
            "first_loss_gap": loss_gap(prog.losses[:1], ref.losses[:1]),
            "norm_gap": max(gaps),
            "norm_gap_median": float(np.median(gaps)),
            "eval_gap": eval_gap(prog.evals, ref.evals),
            "first_eval_gap": eval_gap(prog.evals[:1], ref.evals[:1])}


def load_limits(workload: str, root: str = os.path.dirname(HERE)) -> dict:
    """{number: limit} of a cell under ``root``; {} when its limits are
    not set yet."""
    path = os.path.join(root, "chipbench", "limits", f"{workload}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers the cell's
    limits name: each is there, finite and within its limit. A number
    with no limit (no reading separated it from a sound run) is not
    compared; a cell with no limits is never correct."""
    shown = {k: {"value": numbers.get(k, math.inf), "limit": v}
             for k, v in limits.items()}
    ok = bool(limits) and all(math.isfinite(c["value"])
                              and c["value"] <= c["limit"]
                              for c in shown.values())
    return ok, shown
