"""Run-scoped telemetry recorder: manifest + JSONL event stream.

A :class:`TelemetryRun` owns one run directory (``runs/<id>/`` by
default) holding:

* ``manifest.json`` — config, seed, git SHA, jax backend/device count,
  package versions, status; written atomically at open, on
  :meth:`update_manifest`, and at :meth:`close`.
* ``events.jsonl``  — the typed event stream (``telemetry.events``),
  one line per event. Events are kept in memory and appended in
  batches: at :meth:`TelemetryRun.flush` (``run_simulation`` calls it
  at the end of every call), whenever ``FLUSH_LINES`` are waiting, and
  at :meth:`TelemetryRun.close`, so a timed loop pays no write per
  event.
* ``profile/``      — optional ``jax.profiler`` traces
  (``telemetry.profiler``, opt-in).

Every layer of the stack emits into the same run: ``run_simulation``
(rounds, snapshots, phase spans), the trainers' scan drivers (schedule
precompute / chunk execution spans), ``Scenario.schedule`` (rollout
spans), and the walk/zone trace stream (``telemetry.trace``). Each
phase span is also a ``jax.profiler.TraceAnnotation`` named
``repro.<phase>``, so it lands on a profiler trace's host plane, on the
device planes' clock, and records its ``parent``: the phase open around
it on the same thread. The recorder never touches an RNG and never
forces a device sync the caller
didn't ask for (phase fencing is explicit via :meth:`PhaseSpan.fence`),
so telemetry-on trajectories are bit-identical to telemetry-off — pinned
in ``tests/test_telemetry.py``.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import subprocess
import sys
import threading
import time
from typing import Any

from . import events as ev
from .artifacts import atomic_write_json
from .profiler import annotate

log = logging.getLogger("repro.telemetry")

#: manifest keys that must be identical across runs of the same seeded
#: workload on the same checkout/toolchain (the determinism contract
#: asserted by manifest_fingerprint and its test).
DETERMINISTIC_MANIFEST_KEYS = (
    "schema_version", "seed", "config", "git_sha", "jax", "packages",
)
#: buffered event lines that trigger a write to ``events.jsonl``
FLUSH_LINES = 4096


def _git_sha() -> str | None:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def _environment() -> tuple[dict, dict]:
    """(jax runtime info, package versions) — best-effort, import-gated
    so the recorder also works in jax-free tooling contexts."""
    jx: dict[str, Any] = {}
    pkgs: dict[str, str] = {
        "python": ".".join(map(str, sys.version_info[:3])),
    }
    try:
        import jax

        jx = {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "devices": [d.device_kind for d in jax.devices()],
        }
        pkgs["jax"] = jax.__version__
        import jaxlib

        pkgs["jaxlib"] = getattr(jaxlib, "__version__", "?")
    except Exception:  # pragma: no cover - jax always present in CI
        pass
    try:
        import numpy

        pkgs["numpy"] = numpy.__version__
    except Exception:  # pragma: no cover
        pass
    return jx, pkgs


def manifest_fingerprint(manifest: dict) -> str:
    """sha256 over the deterministic manifest subset — two runs of the
    same seeded workload on the same checkout must agree on this even
    though run ids and timestamps differ."""
    sub = {k: manifest.get(k) for k in DETERMINISTIC_MANIFEST_KEYS}
    blob = json.dumps(sub, sort_keys=True, separators=(",", ":"),
                      default=ev._json_default)
    return hashlib.sha256(blob.encode()).hexdigest()


class PhaseSpan:
    """One fenced phase-timer span (context manager).

    The span opens at ``__enter__`` and records at ``__exit__``; call
    :meth:`fence` on device values before the context closes so async
    dispatch doesn't end the span early — the span then measures
    completed device work, not enqueue time. The fence is explicit
    (never implicit) so a span can also time pure host work without
    forcing a sync.

    While open, the span is a ``repro.<name>`` profiler annotation and
    the top of its thread's stack of open spans; its event records the
    span below it on that stack as ``parent`` (``None`` at the top), so
    a reader can work out self time.
    """

    def __init__(self, run: "TelemetryRun", name: str, meta: dict):
        self._run = run
        self.name = name
        self.meta = meta
        self.seconds: float | None = None

    def fence(self, value):
        """``jax.block_until_ready`` on ``value`` (pass-through), so the
        span covers the device work that produced it."""
        import jax

        return jax.block_until_ready(value)

    def __enter__(self) -> "PhaseSpan":
        stack = self._run._open_spans()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._note = annotate("repro." + self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._note.__exit__(exc_type, exc, tb)
        self._run._open_spans().pop()
        if exc_type is None:
            # ``t0`` (seconds since the run opened) lets the report CLI
            # reconstruct the span timeline — e.g. show the prefetch
            # staging span overlapping the scan_chunk span it hides
            # behind. Wall-clock, so (like ``seconds``) excluded from
            # the byte-identical-events determinism contract.
            self._run.emit("phase", name=self.name,
                           seconds=self.seconds,
                           t0=round(self._t0 - self._run._t_open, 6),
                           parent=self.parent, **self.meta)


class _NullSpan(PhaseSpan):
    """Phase span with no recorder attached (telemetry disabled): no
    annotation, no stack, no event."""

    def __init__(self):  # noqa: D401 - trivial
        super().__init__(None, "", {})  # type: ignore[arg-type]

    def __enter__(self) -> "PhaseSpan":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._t0


def null_phase() -> PhaseSpan:
    """A fence-capable span that records nowhere — what phase-timer call
    sites use when no telemetry run is attached, keeping the disabled
    path allocation-trivial and sync-free (fence is never called on it
    by the built-in call sites)."""
    return _NullSpan()


class TelemetryRun:
    """One recorded run: manifest + event stream under ``run_dir``.

    Parameters
    ----------
    run_dir:  explicit directory for this run's artifacts; or
    root/run_id: ``<root>/<run_id>`` (``run_id`` defaults to a
              wall-clock + pid tag — pass one for reproducible paths).
    config:   free-form JSON-serializable run configuration, captured
              verbatim in the manifest (and in its fingerprint).
    seed:     the run's base RNG seed (manifest + fingerprint).
    profile:  opt-in ``jax.profiler`` tracing (``telemetry.profiler``).
    """

    def __init__(self, run_dir: str | None = None, *, root: str = "runs",
                 run_id: str | None = None, config: dict | None = None,
                 seed: int | None = None, profile: bool = False):
        if run_dir is None:
            if run_id is None:
                run_id = time.strftime("%Y%m%d-%H%M%S") + f"-{os.getpid()}"
            run_dir = os.path.join(root, run_id)
        self.run_dir = run_dir
        self.run_id = run_id or os.path.basename(os.path.normpath(run_dir))
        self.profile = bool(profile)
        self.events_path = os.path.join(run_dir, "events.jsonl")
        self.manifest_path = os.path.join(run_dir, "manifest.json")
        os.makedirs(run_dir, exist_ok=True)
        self._fh = open(self.events_path, "a")
        self._lines: list[str] = []
        # Serializes appends: the lazy plane's prefetch worker emits its
        # staging phase span from a background thread while the main
        # thread streams round events.
        self._emit_lock = threading.Lock()
        # Each thread's stack of open phase names (PhaseSpan.parent).
        self._local = threading.local()
        self._counts: dict[str, int] = {}
        self._t_open = time.perf_counter()
        jx, pkgs = _environment()
        self.manifest: dict[str, Any] = {
            "schema_version": ev.SCHEMA_VERSION,
            "run_id": self.run_id,
            "created_unix": time.time(),
            "seed": seed,
            "config": config or {},
            "git_sha": _git_sha(),
            "jax": jx,
            "packages": pkgs,
            "events": "events.jsonl",
            "status": "open",
        }
        self.manifest["fingerprint"] = manifest_fingerprint(self.manifest)
        self._write_manifest()

    # -- manifest ---------------------------------------------------------
    def _write_manifest(self) -> None:
        atomic_write_json(self.manifest_path, self.manifest)

    def update_manifest(self, **fields) -> None:
        """Merge fields into the manifest and rewrite it atomically.
        ``config`` merges key-wise (late writers — e.g. run_simulation
        adding engine/rounds — extend rather than clobber), and the
        fingerprint is recomputed since config is part of it."""
        cfg = fields.pop("config", None)
        if cfg:
            self.manifest["config"] = {**self.manifest["config"], **cfg}
        self.manifest.update(fields)
        self.manifest["fingerprint"] = manifest_fingerprint(self.manifest)
        self._write_manifest()

    # -- event stream -----------------------------------------------------
    def emit(self, etype: str, **fields) -> None:
        """Queue one typed event for ``events.jsonl`` (written at the
        next :meth:`flush`, or now if ``FLUSH_LINES`` are waiting)."""
        if self._fh.closed:
            raise ev.TelemetryError(
                f"telemetry run {self.run_id!r} is closed")
        line = ev.encode_event({"t": etype, **fields})
        with self._emit_lock:
            self._lines.append(line)
            self._counts[etype] = self._counts.get(etype, 0) + 1
            if len(self._lines) >= FLUSH_LINES:
                self._write_lines()

    def _write_lines(self) -> None:
        """Append the queued lines (caller holds ``_emit_lock``)."""
        if self._lines:
            self._fh.write("\n".join(self._lines) + "\n")
            self._lines.clear()
        self._fh.flush()

    def flush(self) -> None:
        """Write every queued event to ``events.jsonl``."""
        with self._emit_lock:
            if not self._fh.closed:
                self._write_lines()

    def _open_spans(self) -> list[str]:
        """This thread's stack of open phase names."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def round(self, metrics: dict) -> None:
        """One training round's ``round_metrics`` entry."""
        self.emit("round", **metrics)

    def visit(self, **fields) -> None:
        self.emit("visit", **fields)

    def snapshot(self, snap: dict) -> None:
        self.emit("snapshot", **snap)

    def counter(self, name: str, value) -> None:
        self.emit("counter", name=name, value=value)

    def phase(self, name: str, **meta) -> PhaseSpan:
        """A fenced phase-timer span (see :class:`PhaseSpan`):

        >>> with run.phase("scan_chunk", engine="scan") as sp:
        ...     state, stacked = trainer.run_chunk(state, sched)
        ...     sp.fence(stacked)
        """
        return PhaseSpan(self, name, meta)

    # -- console ----------------------------------------------------------
    def log(self, msg: str) -> None:
        """Route human-facing progress lines through the telemetry
        logger (stderr handler installed lazily so library users who
        configure logging themselves are not double-printed)."""
        telemetry_print(msg)

    # -- lifecycle --------------------------------------------------------
    def close(self, **fields) -> None:
        """Finalize: flush events, stamp status/wall time/event counts."""
        with self._emit_lock:
            if not self._fh.closed:
                self._write_lines()
                self._fh.close()
        self.update_manifest(**{
            "status": "finalized",
            "wall_time_s": round(time.perf_counter() - self._t_open, 6),
            "event_counts": dict(sorted(self._counts.items())),
            **fields})

    def __enter__(self) -> "TelemetryRun":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(**({"status": "failed"} if exc_type else {}))


def telemetry_print(msg: str) -> None:
    """Print via the ``repro.telemetry`` logger, installing a bare
    stderr handler on first use when the app configured none — the
    replacement for ad-hoc ``print()`` progress lines."""
    if not log.handlers and not logging.getLogger().handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter("%(message)s"))
        log.addHandler(h)
        log.setLevel(logging.INFO)
    log.info(msg)
