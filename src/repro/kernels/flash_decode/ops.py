"""jit wrapper for the flash-decode kernel (pads S, picks interpret)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import interpret_mode
from . import kernel


@functools.partial(jax.jit, static_argnames=("window", "seq_block"))
def flash_decode(q, k, v, length, *, window: int | None = None,
                 seq_block: int = kernel.SEQ_BLOCK):
    """q: (B,H,hd); k/v: (B,S,K,hd); length: (B,). Returns (B,H,hd)."""
    s = k.shape[1]
    sb = min(seq_block, max(128, 1 << (s - 1).bit_length())) \
        if s < seq_block else seq_block
    pad = (-s) % sb
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return kernel.flash_decode_gqa(
        q, k, v, jnp.asarray(length, jnp.int32), window=window,
        seq_block=sb, interpret=interpret_mode(),
    )
