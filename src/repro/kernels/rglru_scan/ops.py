"""jit wrapper for the RG-LRU scan kernel (pads S/D, picks interpret)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import interpret_mode
from . import kernel


@functools.partial(jax.jit, static_argnames=("seq_block", "ch_block"))
def rglru_scan(a, b, *, seq_block: int | None = None,
               ch_block: int = kernel.CH_BLOCK):
    """a, b: (B, S, D) → h with h_t = a_t h_{t−1} + b_t."""
    bsz, s, d = a.shape
    sb = seq_block or min(kernel.SEQ_BLOCK, s)
    pad_s = (-s) % sb
    pad_d = (-d) % ch_block
    if pad_s or pad_d:
        # a=1 on padded channels keeps the carry intact; padded rows are
        # sliced off afterwards so any a value works — use 0 for safety.
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d)))
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_d)))
    h = kernel.rglru_scan_blocked(a, b, seq_block=sb, ch_block=ch_block,
                                  interpret=interpret_mode())
    return h[:, :s, :d]
