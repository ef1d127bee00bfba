"""Pallas TPU kernel: blocked RG-LRU linear recurrence.

h_t = a_t ⊙ h_{t−1} + b_t over (B, S, D). The channel dim is tiled into
128-lane blocks; the sequence dim into VMEM-resident chunks, with the
carry h kept in VMEM scratch across the (sequential) seq grid dimension.
Within a chunk, the recurrence runs as a Blelloch-free sequential
fori_loop over rows — each step is a (1, Db) VPU FMA; HBM traffic is one
pass over a and b (the memory-bound roofline floor for this op).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SEQ_BLOCK = 512
CH_BLOCK = 128


def _kernel(a_ref, b_ref, h_ref, carry_scr, *, seq_block):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    # Rows stay 2-D (1, Db) slices: Mosaic keeps its (sublane, lane)
    # layout for them, while a rank-1 row aborts the TPU compiler.
    def body(t, carry):
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * carry + b_ref[0, row, :]
        h_ref[0, row, :] = h
        return h

    carry = jax.lax.fori_loop(0, seq_block, body, carry_scr[...])
    carry_scr[...] = carry


def rglru_scan_blocked(a, b, *, seq_block: int = SEQ_BLOCK,
                       ch_block: int = CH_BLOCK, interpret: bool = True):
    """a, b: (B, S, D) with S % seq_block == 0 and D % ch_block == 0
    (ops.py pads). Returns h (B, S, D)."""
    bsz, s, d = a.shape
    assert s % seq_block == 0 and d % ch_block == 0
    grid = (bsz, d // ch_block, s // seq_block)
    spec = pl.BlockSpec((1, seq_block, ch_block),
                        lambda bi, ci, si: (bi, si, ci))
    return pl.pallas_call(
        functools.partial(_kernel, seq_block=seq_block),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, ch_block), a.dtype)],
        interpret=interpret,
    )(a, b)
