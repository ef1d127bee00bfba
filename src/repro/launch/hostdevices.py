"""Host-platform device bootstrap (shared by benches, dry-run, tests).

XLA can split one CPU host into N "host platform devices"
(``--xla_force_host_platform_device_count=N``), which is how the
dry-run mesh, the multi-device CPU bench harness, and the sharded-plane
tests get a mesh without real accelerators. The flag only takes effect
if it is present in ``XLA_FLAGS`` *before* the JAX backend initializes
(first computation / first ``jax.devices()`` call — NOT import), so the
helpers here must run at the very top of an entrypoint.

This module deduplicates the copy-pasted env blocks that used to live
at the top of ``benchmarks/perf_iterations.py`` and
``repro/launch/dryrun.py``, and adds the olmax-style tcmalloc env for
multi-device CPU runs (SNIPPETS §1–2).
"""
from __future__ import annotations

import os

_FLAG = "xla_force_host_platform_device_count"

# Common Debian/Ubuntu locations, preferred order (olmax uses the
# first). Only used when the file actually exists — never forced.
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)


def ensure_host_platform_devices(count: int = 512) -> bool:
    """Prepend ``--xla_force_host_platform_device_count=count`` to
    XLA_FLAGS unless some value for the flag is already set.

    Idempotent; returns True when the env now requests the flag (either
    set here or pre-existing). Must run before the JAX backend
    initializes — callers that cannot guarantee that (e.g. a bench
    registry where earlier jobs already ran computations) should spawn
    a fresh subprocess with this env instead (see
    ``subprocess_env``)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        return True
    os.environ["XLA_FLAGS"] = (
        f"--{_FLAG}={int(count)} " + flags).strip()
    return True


def require_cpu_platform(env=None) -> None:
    """Raise unless ``env`` (default: this process) already selects the
    CPU backend with ``JAX_PLATFORMS=cpu``. Forced host devices are a
    CPU rehearsal; nothing here switches an accelerator host to the CPU
    on the caller's behalf. Reads only the environment, so it decides
    before the backend initializes."""
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "") != "cpu":
        raise RuntimeError(
            "forced host platform devices are a CPU rehearsal: set "
            "JAX_PLATFORMS=cpu to run on N virtual CPU devices "
            f"(JAX_PLATFORMS is {env.get('JAX_PLATFORMS')!r}); on an "
            "accelerator host, run on its real devices instead")


def host_device_env(count: int, base: dict | None = None,
                    *, tcmalloc: bool = True) -> dict:
    """Environment dict for a FRESH subprocess that should see ``count``
    host platform devices: XLA flag + (when available) the olmax
    tcmalloc LD_PRELOAD, which keeps many-device CPU allocation from
    serializing on glibc malloc. ``base`` must already ask for the CPU
    backend (:func:`require_cpu_platform`)."""
    env = dict(os.environ if base is None else base)
    require_cpu_platform(env)
    flags = env.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        env["XLA_FLAGS"] = (f"--{_FLAG}={int(count)} " + flags).strip()
    if tcmalloc and "LD_PRELOAD" not in env:
        for p in _TCMALLOC_PATHS:
            if os.path.exists(p):
                env["LD_PRELOAD"] = p
                env.setdefault(
                    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                    str(2 ** 37))
                break
    return env
