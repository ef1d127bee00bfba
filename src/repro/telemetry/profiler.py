"""Opt-in device-level profiling hooks (``jax.profiler``).

Phase timers (``TelemetryRun.phase``) give wall-clock spans; when that
is not enough, a run opened with ``profile=True`` (or with
``REPRO_PROFILE=1`` in the environment) additionally wraps its training
loop in ``jax.profiler.trace`` writing a TensorBoard-loadable trace to
``runs/<id>/profile/``. Every phase span is an :func:`annotate` region
(``jax.profiler.TraceAnnotation`` named ``repro.<phase>``), so the
trace's host plane carries the same phase names as the event stream,
on the device timeline's clock.

Both hooks cost nothing when profiling is off, so they are safe to leave
in library code. When a run asked for a trace and the profiler cannot
start, that is an error: a run that silently records no trace would be
read as one that was measured.
"""
from __future__ import annotations

import contextlib
import os


def profiling_enabled(run=None) -> bool:
    """True when this run (or the environment) opted into profiling."""
    if run is not None and getattr(run, "profile", False):
        return True
    return os.environ.get("REPRO_PROFILE", "") not in ("", "0")


@contextlib.contextmanager
def maybe_trace(run=None):
    """``jax.profiler.trace`` over the wrapped block, writing under the
    run's ``profile/`` directory — a no-op unless profiling is enabled
    and a run directory exists to hold the trace."""
    if run is None or not profiling_enabled(run):
        yield None
        return
    import jax.profiler as jp

    logdir = os.path.join(run.run_dir, "profile")
    os.makedirs(logdir, exist_ok=True)
    # No try/except around the yield: the body's exceptions propagate
    # unchanged, and so does a profiler that fails to start.
    with jp.trace(logdir):
        yield logdir
    run.update_manifest(profile_dir="profile")


def annotate(name: str):
    """Named region on the device trace (``TraceAnnotation``); costs
    next to nothing when no trace is being recorded."""
    import jax.profiler as jp

    return jp.TraceAnnotation(name)
