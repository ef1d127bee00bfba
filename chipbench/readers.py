"""What the per-layer readers in ``metrics/`` share: sums of the
program's telemetry phase spans, and device time inside jitted modules.

Every reader takes the traced run's ``ctx`` (see ``harness``) and
returns a number or None when the run holds nothing to read.
"""
from __future__ import annotations

from . import trace


def phase_ms_per_round(ctx, name: str):
    """Milliseconds of the program's ``name`` phase spans per round."""
    spans = [e["seconds"] for e in ctx["telemetry"]
             if e["t"] == "phase" and e["name"] == name]
    if not spans:
        return None
    return 1e3 * sum(spans) / ctx["rounds"]


def device_ns(ctx, module: str | None = None, op=None) -> float | None:
    """Busy device ns in the window, averaged over the cell's devices:
    the union of the ``XLA Ops`` events, restricted to runs of the
    modules matching ``module`` and to ops matching ``op``."""
    if not ctx["devices"]:
        return None
    lo, hi = ctx["window"]
    total = 0.0
    for d in ctx["devices"]:
        ops = trace.select(ctx["events"], plane=d, line=trace.OPS_LINE,
                           name=op)
        if module is not None:
            runs = trace.select(ctx["events"], plane=d,
                                line=trace.MODULES_LINE, name=module)
            ops = trace.inside(ops, [(e.start, e.end) for e in runs])
        total += trace.total(trace.clip([(e.start, e.end) for e in ops],
                                        lo, hi))
    return total / len(ctx["devices"])


CHUNK_MODULE = r"jit_chunk\b"


def chunk_s_per_round(ctx):
    ns = device_ns(ctx, module=CHUNK_MODULE)
    if not ns:
        return None
    return ns / 1e9 / ctx["rounds"]
