"""Pallas TPU kernel: fused RWSADMM triple update (x, z, y).

Why a kernel: the zone round's update is ~10 elementwise HLO ops over four
model-sized tensors (x, z, y, g). Unfused, XLA streams each intermediate
through HBM; fused, it is a single HBM pass: read 4·P, write 3·P — the
roofline floor for this memory-bound op. VMEM tiling: flat vectors in
(8, 1024)-shaped blocks (8×128-lane aligned), all operands resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 8 * 1024  # elements per program: 7 arrays × 32 KB fp32 in VMEM


def _kernel(x_ref, z_ref, y_ref, g_ref, kappa_ref,
            x_out, z_out, y_out, *, beta, eps_half, n_total):
    x = x_ref[...]
    z = z_ref[...]
    y = y_ref[...]
    g = g_ref[...]
    kappa = kappa_ref[0]

    s_prev = jnp.sign(y - x)
    x_new = y - g / beta + s_prev * (z - beta * eps_half) / beta
    z_new = z + kappa * beta * (x_new - y - eps_half)
    c_old = x - (z / beta + eps_half) * s_prev
    c_new = x_new - (z_new / beta + eps_half) * jnp.sign(y - x_new)
    y_new = y + (c_new - c_old) / n_total

    x_out[...] = x_new
    z_out[...] = z_new
    y_out[...] = y_new


def fused_update_flat(x, z, y, g, kappa, *, beta: float, eps_half: float,
                      n_total: float, interpret: bool = True,
                      block: int = BLOCK):
    """x/z/y/g: flat (N,) arrays, N a multiple of ``block`` (ops.py pads).
    kappa: (1,) array (decayed per round, so not compile-time)."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    vspec = pl.BlockSpec((block,), lambda i: (i,))
    kspec = pl.BlockSpec((1,), lambda i: (0,))
    out_shape = [jax.ShapeDtypeStruct((n,), x.dtype)] * 3
    return pl.pallas_call(
        functools.partial(_kernel, beta=beta, eps_half=eps_half,
                          n_total=n_total),
        grid=grid,
        in_specs=[vspec, vspec, vspec, vspec, kspec],
        out_specs=[vspec, vspec, vspec],
        out_shape=out_shape,
        interpret=interpret,
    )(x, z, y, g, kappa)


# ---------------------------------------------------------------------------
# Masked multi-client zone variant (paper Eq. 31): all Z active clients'
# x/z updates plus the server's folded y update in ONE HBM pass. Per block
# of P parameters the pass reads (3Z+1)·P (x, z, g per client + y) and
# writes (2Z+1)·P (x⁺, z⁺ per client + y⁺) — the roofline floor; the
# unfused zone round streams every per-client intermediate (s', c, c⁺, Δ)
# through HBM. The Z loop is unrolled at trace time (Z ≤ ~16), all
# operands VMEM-resident.
# ---------------------------------------------------------------------------

ZONE_BLOCK = 2 * 1024  # elements/program: (5Z+2) arrays ≈ 42 × 8 KB @ Z=8


def _zone_kernel(x_ref, z_ref, y_ref, g_ref, mask_ref, kappa_ref,
                 x_out, z_out, y_out, *, beta, eps_half, n_total, zone):
    y = y_ref[...]
    kappa = kappa_ref[0]
    acc = jnp.zeros_like(y)
    for j in range(zone):          # static unroll over the padded zone
        m = mask_ref[j]
        x = x_ref[j]
        z = z_ref[j]
        g = g_ref[j]
        s_prev = jnp.sign(y - x)
        x_new = y - g / beta + s_prev * (z - beta * eps_half) / beta
        z_new = z + kappa * beta * (x_new - y - eps_half)
        c_old = x - (z / beta + eps_half) * s_prev
        c_new = x_new - (z_new / beta + eps_half) * jnp.sign(y - x_new)
        # Padded slots (m=0) pass through untouched and fold zero into y.
        x_out[j] = m * x_new + (1.0 - m) * x
        z_out[j] = m * z_new + (1.0 - m) * z
        acc = acc + m * (c_new - c_old)
    y_out[...] = y + acc / n_total


def _multizone_kernel(x_ref, z_ref, y_ref, g_ref, mask_ref, kappa_ref,
                      x_out, z_out, y_out, *, beta, eps_half, n_total,
                      zone):
    """One walker's zone block (leading size-1 walker axis carved out by
    the grid) — same math as :func:`_zone_kernel` against that walker's
    own token slice. Rows stay 2-D ``(1, block)`` tiles; the walker's
    mask row and κ are SMEM scalars."""
    k = pl.program_id(0)
    y = y_ref[0]                   # (1, block)
    kappa = kappa_ref[0]
    acc = jnp.zeros_like(y)
    for j in range(zone):          # static unroll over the padded zone
        m = mask_ref[k, j]
        x = x_ref[0, j:j + 1]
        z = z_ref[0, j:j + 1]
        g = g_ref[0, j:j + 1]
        s_prev = jnp.sign(y - x)
        x_new = y - g / beta + s_prev * (z - beta * eps_half) / beta
        z_new = z + kappa * beta * (x_new - y - eps_half)
        c_old = x - (z / beta + eps_half) * s_prev
        c_new = x_new - (z_new / beta + eps_half) * jnp.sign(y - x_new)
        x_out[0, j:j + 1] = m * x_new + (1.0 - m) * x
        z_out[0, j:j + 1] = m * z_new + (1.0 - m) * z
        acc = acc + m * (c_new - c_old)
    y_out[0] = y + acc / n_total


def multizone_fused_update_flat(x, z, y, g, mask, kappa, *, beta: float,
                                eps_half: float, n_total: float,
                                interpret: bool = True,
                                block: int = ZONE_BLOCK):
    """K simultaneous zones in ONE kernel launch (fleet mode).

    x/z/g: (K, Z, N) stacked walker zones; y: (K, N) stacked tokens;
    mask: (K, Z); kappa: (1,). N a multiple of ``block`` (ops.py pads).
    Grid (K, N/block): each program serves one walker's parameter block,
    so the whole fleet wall step is a single HBM pass — K independent
    :func:`zone_fused_update_flat` launches would re-dispatch per
    walker. Returns (x⁺ (K, Z, N), z⁺ (K, Z, N), y⁺ (K, N)).
    """
    k_walkers, zone, n = x.shape
    assert n % block == 0, (n, block)
    grid = (k_walkers, n // block)
    mspec = pl.BlockSpec((1, zone, block), lambda k, i: (k, 0, i))
    # y rides as (K, 1, N): its (1, 1, block) tile keeps the second-minor
    # block dim equal to the array's, which Mosaic accepts for every K
    # (a (1, block) tile over (K, N) is refused unless 8 | K or K = 1).
    yspec = pl.BlockSpec((1, 1, block), lambda k, i: (k, 0, i))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole mask / κ
    out_shape = [
        jax.ShapeDtypeStruct((k_walkers, zone, n), x.dtype),
        jax.ShapeDtypeStruct((k_walkers, zone, n), x.dtype),
        jax.ShapeDtypeStruct((k_walkers, 1, n), x.dtype),
    ]
    x_new, z_new, y_new = pl.pallas_call(
        functools.partial(_multizone_kernel, beta=beta, eps_half=eps_half,
                          n_total=n_total, zone=zone),
        grid=grid,
        in_specs=[mspec, mspec, yspec, mspec, smem, smem],
        out_specs=[mspec, mspec, yspec],
        out_shape=out_shape,
        interpret=interpret,
    )(x, z, y[:, None], g, mask, kappa)
    return x_new, z_new, y_new[:, 0]


def zone_fused_update_flat(x, z, y, g, mask, kappa, *, beta: float,
                           eps_half: float, n_total: float,
                           interpret: bool = True, block: int = ZONE_BLOCK):
    """x/z/g: (Z, N) stacked active clients; y: (N,); mask: (Z,);
    kappa: (1,). N a multiple of ``block`` (ops.py pads). Returns
    (x⁺ (Z, N), z⁺ (Z, N), y⁺ (N,))."""
    zone, n = x.shape
    assert n % block == 0, (n, block)
    grid = (n // block,)
    mspec = pl.BlockSpec((zone, block), lambda i: (0, i))
    vspec = pl.BlockSpec((block,), lambda i: (i,))
    maskspec = pl.BlockSpec((zone,), lambda i: (0,))
    kspec = pl.BlockSpec((1,), lambda i: (0,))
    out_shape = [
        jax.ShapeDtypeStruct((zone, n), x.dtype),
        jax.ShapeDtypeStruct((zone, n), x.dtype),
        jax.ShapeDtypeStruct((n,), x.dtype),
    ]
    return pl.pallas_call(
        functools.partial(_zone_kernel, beta=beta, eps_half=eps_half,
                          n_total=n_total, zone=zone),
        grid=grid,
        in_specs=[mspec, mspec, vspec, mspec, maskspec, kspec],
        out_specs=[mspec, mspec, vspec],
        out_shape=out_shape,
        interpret=interpret,
    )(x, z, y, g, mask, kappa)
