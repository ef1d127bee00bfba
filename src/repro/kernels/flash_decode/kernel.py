"""Pallas TPU kernel: single-token GQA flash decode.

The decode_32k / long_500k bottleneck is streaming the KV cache once per
token: it is purely memory-bound (arithmetic intensity ~2 flops/byte). The
kernel tiles the cache sequence dimension into VMEM blocks and keeps the
online-softmax running state (m, l, acc) in VMEM scratch across the
sequence grid dimension (sequential on TPU), so HBM traffic is exactly one
pass over K and V. Grid: (B, S/block). One program holds every KV head of
its sequence block — a ``(block, K, hd)`` tile whose minor two dims are
the cache's own, which Mosaic accepts for any K and hd — and loops over
the heads; the G = H/K query heads of one KV head ride along in a
(G, hd) tile. The per-row valid lengths are scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SEQ_BLOCK = 512


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, window, seq_block, n_blocks,
            kv_heads):
    bi = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]
    pos = sb * seq_block + jax.lax.broadcasted_iota(
        jnp.int32, (1, seq_block), 1)
    valid = pos < length
    if window is not None:
        valid &= pos >= length - window

    for h in range(kv_heads):                  # static unroll over KV heads
        q = q_ref[0, h].astype(jnp.float32)        # (G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)  # (Sb, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)  # (Sb, hd)

        s = jax.lax.dot_general(                   # q · kᵀ: (G, Sb)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[h]                          # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (G, Sb)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = alpha * acc_scr[h] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(sb == n_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_gqa(q, k, v, length, *, window: int | None = None,
                     seq_block: int = SEQ_BLOCK, interpret: bool = True):
    """q: (B, H, hd); k/v: (B, S, K, hd); length: (B,) int32.

    Returns (B, H, hd). S must be a multiple of seq_block (ops.py pads).
    """
    b, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    assert s % seq_block == 0, (s, seq_block)
    n_blocks = s // seq_block
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(b, kvh, g, hd)
    qspec = pl.BlockSpec((1, kvh, g, hd), lambda bi, si, lens: (bi, 0, 0, 0))
    kvspec = pl.BlockSpec((1, seq_block, kvh, hd),
                          lambda bi, si, lens: (bi, si, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          seq_block=seq_block, n_blocks=n_blocks,
                          kv_heads=kvh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                 # length → SMEM
            grid=(b, n_blocks),
            in_specs=[qspec, kvspec, kvspec],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((kvh, g, 1), jnp.float32),    # running max m
                pltpu.VMEM((kvh, g, 1), jnp.float32),    # running sum l
                pltpu.VMEM((kvh, g, hd), jnp.float32),   # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        interpret=interpret,
    )(length, qg, k, v)
    return out.reshape(b, h, hd)
