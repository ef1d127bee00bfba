"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required because the dry-run process
overrides the device count via XLA_FLAGS before first jax init, while
tests/benches must keep seeing the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    JAX ≥ 0.9 defaults to Explicit axes, under which a gather from a
    row-sharded operand must name its output sharding. The trainers and
    the dry-run steps place their inputs and rely on sharding
    propagation inside jit instead (fl/sharding.py), which is what Auto
    axes mean."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_data_mesh(n_devices: int | None = None):
    """1-D "data" mesh over all (or the first ``n_devices``) local
    devices — the FL client plane's shard unit is the leading
    client/capacity axis, so a single data axis is the whole story."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devs)} "
                "(set --xla_force_host_platform_device_count before "
                "backend init for CPU hosts)")
        devs = devs[:n_devices]
    return make_mesh((len(devs),), ("data",), devices=devs)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CI-grade dry-run tests (8 host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
