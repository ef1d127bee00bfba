"""jit wrapper: pytree-level fused RWSADMM update via the Pallas kernel.

Flattens the parameter pytree once, pads to the block size, runs the
fused kernel, and unflattens. On TPU the kernel compiles through Mosaic;
on CPU it runs in interpret mode for the tests (``kernels.backend``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import tree as tree_util
from ..backend import interpret_mode
from . import kernel


@functools.partial(jax.jit, static_argnames=("beta", "eps_half", "n_total",
                                             "block"))
def rwsadmm_fused_update(x, z, y, g, kappa, *, beta: float, eps_half: float,
                         n_total: float, block: int = kernel.BLOCK):
    """Pytree version of the fused triple update. Returns (x⁺, z⁺, y⁺)."""
    xf = tree_util.flatten(x)
    zf = tree_util.flatten(z)
    yf = tree_util.flatten(y)
    gf = tree_util.flatten(g)
    n = xf.shape[0]
    pad = (-n) % block
    if pad:
        xf, zf, yf, gf = (jnp.pad(a, (0, pad)) for a in (xf, zf, yf, gf))
    kappa_arr = jnp.reshape(jnp.asarray(kappa, xf.dtype), (1,))
    x_new, z_new, y_new = kernel.fused_update_flat(
        xf, zf, yf, gf, kappa_arr, beta=beta, eps_half=eps_half,
        n_total=n_total, interpret=interpret_mode(), block=block,
    )
    if pad:
        x_new, z_new, y_new = (a[:n] for a in (x_new, z_new, y_new))
    return (tree_util.unflatten(x, x_new),
            tree_util.unflatten(z, z_new),
            tree_util.unflatten(y, y_new))


@functools.partial(jax.jit, static_argnames=("beta", "eps_half", "n_total",
                                             "block"))
def rwsadmm_zone_fused_update(x, z, y, g, mask, kappa, *, beta: float,
                              eps_half: float, n_total: float,
                              block: int = kernel.ZONE_BLOCK):
    """Masked multi-client zone update (Eq. 31) via the fused kernel.

    x/z/g: pytrees with a padded leading ``Z`` axis (stacked active
    clients); y: the server token pytree; mask: (Z,) float (0 = padding).
    Returns (x⁺, z⁺, y⁺) with the same layouts — one HBM pass for the
    whole zone round. Oracle: ``core.rwsadmm.zone_round_masked``.
    """
    xf = jax.vmap(tree_util.flatten)(x)   # (Z, N)
    zf = jax.vmap(tree_util.flatten)(z)
    gf = jax.vmap(tree_util.flatten)(g)
    yf = tree_util.flatten(y)             # (N,)
    n = yf.shape[0]
    pad = (-n) % block
    if pad:
        xf, zf, gf = (jnp.pad(a, ((0, 0), (0, pad))) for a in (xf, zf, gf))
        yf = jnp.pad(yf, (0, pad))
    kappa_arr = jnp.reshape(jnp.asarray(kappa, yf.dtype), (1,))
    mask_arr = jnp.asarray(mask, yf.dtype)
    x_new, z_new, y_new = kernel.zone_fused_update_flat(
        xf, zf, yf, gf, mask_arr, kappa_arr, beta=beta, eps_half=eps_half,
        n_total=n_total, interpret=interpret_mode(), block=block,
    )
    if pad:
        x_new, z_new = (a[:, :n] for a in (x_new, z_new))
        y_new = y_new[:n]
    template = jax.tree_util.tree_map(lambda l: l[0], x)
    unstack = jax.vmap(lambda f: tree_util.unflatten(template, f))
    return (unstack(x_new), unstack(z_new), tree_util.unflatten(y, y_new))


@functools.partial(jax.jit, static_argnames=("beta", "eps_half", "n_total",
                                             "block"))
def rwsadmm_multizone_fused_update(x, z, y, g, mask, kappa, *, beta: float,
                                   eps_half: float, n_total: float,
                                   block: int = kernel.ZONE_BLOCK):
    """K simultaneous masked zone rounds via one fused-kernel launch.

    x/z/g: pytrees with padded leading ``(K, Z)`` axes (K walkers, each
    with a stacked active zone); y: stacked ``(K, ...)`` token pytree
    (one token per walker); mask: (K, Z) float (0 = padding). Returns
    (x⁺, z⁺, y⁺) with the same layouts — the whole fleet wall step in
    one HBM pass. Oracle: ``core.rwsadmm.multizone_round_masked``.
    """
    flat2 = jax.vmap(jax.vmap(tree_util.flatten))
    xf = flat2(x)                         # (K, Z, N)
    zf = flat2(z)
    gf = flat2(g)
    yf = jax.vmap(tree_util.flatten)(y)   # (K, N)
    n = yf.shape[-1]
    pad = (-n) % block
    if pad:
        xf, zf, gf = (jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
                      for a in (xf, zf, gf))
        yf = jnp.pad(yf, ((0, 0), (0, pad)))
    kappa_arr = jnp.reshape(jnp.asarray(kappa, yf.dtype), (1,))
    mask_arr = jnp.asarray(mask, yf.dtype)
    x_new, z_new, y_new = kernel.multizone_fused_update_flat(
        xf, zf, yf, gf, mask_arr, kappa_arr, beta=beta, eps_half=eps_half,
        n_total=n_total, interpret=interpret_mode(), block=block,
    )
    if pad:
        x_new, z_new = (a[..., :n] for a in (x_new, z_new))
        y_new = y_new[..., :n]
    template = jax.tree_util.tree_map(lambda l: l[0, 0], x)
    unstack2 = jax.vmap(jax.vmap(
        lambda f: tree_util.unflatten(template, f)))
    y_template = jax.tree_util.tree_map(lambda l: l[0], y)
    unstack_y = jax.vmap(lambda f: tree_util.unflatten(y_template, f))
    return (unstack2(x_new), unstack2(z_new), unstack_y(y_new))
