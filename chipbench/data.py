"""Helpers the benchmark's data generators share, made from the seed.

The generators themselves live with their model kinds
(``kinds/<kind>.py``). Copies of the deployments' definitions, kept here
so that a change to the program's ``data/`` cannot move the yardstick:

* independent sub-seeds of a run's seed;
* the paper's pathological split (2 labels per client, variable sizes,
  ``repro.data.partition.pathological_split``) and a 75/25 train/test
  split per client, on the host from the labels alone.
"""
from __future__ import annotations

import numpy as np


def sub_seeds(seed: int, n: int = 4) -> list[int]:
    """``n`` independent 31-bit seeds from any whole ``seed``."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in state]


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
