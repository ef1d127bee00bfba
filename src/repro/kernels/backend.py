"""Where the Pallas kernels run: Mosaic on the TPU, the interpreter on CPU.

Interpret mode exists for the CPU test suite (``JAX_PLATFORMS=cpu``). On
any other backend the kernels would silently fall back to emulation and
hide that the device path never ran, so that is an error.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on CPU (interpret mode), False on TPU (Mosaic); raises
    elsewhere. Read at trace time by every kernel wrapper's jit."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (Mosaic) or 'cpu' (interpret mode, "
        f"for tests); the default backend is {backend!r}")
