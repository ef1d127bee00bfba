"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, the benchmark CLIs) call
:func:`enable_compile_cache` before their first compile, so a second
run on the same machine reads its executables back instead of compiling
them again. Importing ``repro`` never turns the cache on, and the tests
never call this.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this leaves it alone. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored): the directory is part of what a
later run must find, so it is never derived from a temporary name, a
process id or the time.

Executables are keyed by their HLO metadata too (op names, source
lines). JAX's default key leaves metadata out, so a cached executable
compiled from other code with the same HLO (say, before the round body
gained its ``jax.named_scope``s) would be loaded with that code's
metadata, and a profiler trace would show its op names, not this
code's. Source paths enter the key relative to the checkout, so a
checkout that moves still finds its executables.
"""
from __future__ import annotations

import os
import re

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CHECKOUT_CACHE = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Make sure the persistent compile cache is on; return its dir."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    path = os.environ.get(ENV_VAR)
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
