"""Compile the Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` and
compiled by the TPU compiler for a ``v5e:2x2`` topology described (not
attached) here, and the compiled text must hold the Mosaic kernel
(``tpu_custom_call``). This catches what interpret mode cannot — block
shapes the TPU tiling refuses, layouts Mosaic cannot lower — at no chip
time. The topology is described inside a fixture, so importing this
file never loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen2_7b import QWEN2_7B
from repro.configs.recurrentgemma_9b import RECURRENTGEMMA_9B
from repro.kernels.flash_decode import kernel as flash_kernel
from repro.kernels.rglru_scan import kernel as rglru_kernel
from repro.kernels.rwsadmm_update import kernel as rw_kernel

#: the paper CNN at (32, 32, 3): 1 068 266 parameters, padded per block
CNN_PARAMS = 1_068_266
HP = dict(beta=10.0, eps_half=5e-6, n_total=100.0, interpret=False)


def _padded(n: int, block: int) -> int:
    return -(-n // block) * block


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_fused_update_compiles(one_chip):
    n = _padded(CNN_PARAMS, rw_kernel.BLOCK)
    vec = _spec(one_chip, (n,))
    text = _compiled_text(
        lambda x, z, y, g, k: rw_kernel.fused_update_flat(
            x, z, y, g, k, **HP),
        vec, vec, vec, vec, _spec(one_chip, (1,)))
    assert "tpu_custom_call" in text


def test_zone_fused_update_compiles(one_chip):
    n, zone = _padded(CNN_PARAMS, rw_kernel.ZONE_BLOCK), 8
    rows = _spec(one_chip, (zone, n))
    text = _compiled_text(
        lambda x, z, y, g, m, k: rw_kernel.zone_fused_update_flat(
            x, z, y, g, m, k, **HP),
        rows, rows, _spec(one_chip, (n,)), rows, _spec(one_chip, (zone,)),
        _spec(one_chip, (1,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("walkers", [3, 5])
def test_multizone_fused_update_compiles(one_chip, walkers):
    n, zone = _padded(CNN_PARAMS, rw_kernel.ZONE_BLOCK), 8
    rows = _spec(one_chip, (walkers, zone, n))
    text = _compiled_text(
        lambda x, z, y, g, m, k: rw_kernel.multizone_fused_update_flat(
            x, z, y, g, m, k, **HP),
        rows, rows, _spec(one_chip, (walkers, n)), rows,
        _spec(one_chip, (walkers, zone)), _spec(one_chip, (1,)))
    assert "tpu_custom_call" in text


def test_flash_decode_compiles(one_chip):
    """Qwen2-7B decode at 32k context: 28 query heads over 4 KV heads,
    head dim 128, a bf16 cache, 8 sequences per chip."""
    cfg, b, s = QWEN2_7B, 8, 32_768
    cache = _spec(one_chip, (b, s, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v, length: flash_kernel.flash_decode_gqa(
            q, k, v, length, interpret=False),
        _spec(one_chip, (b, cfg.n_heads, cfg.hd), jnp.bfloat16), cache,
        cache, _spec(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in text


def test_rglru_scan_compiles(one_chip):
    """RecurrentGemma-9B's recurrence width over a 4k sequence, f32 (the
    dtype the Griffin block feeds it)."""
    shape = (1, 4096, RECURRENTGEMMA_9B.d_model)
    text = _compiled_text(
        lambda a, b: rglru_kernel.rglru_scan_blocked(a, b, interpret=False),
        _spec(one_chip, shape), _spec(one_chip, shape))
    assert "tpu_custom_call" in text
