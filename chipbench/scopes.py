"""Readers of the program's own spans and scopes in a profiler trace.

Host: every telemetry phase span of the program is a
``jax.profiler.TraceAnnotation`` named ``repro.<phase>``, so it is an
event on a ``/host:`` plane, one line per thread, on the device planes'
clock. Device: the compiled round names its parts with
``jax.named_scope`` (``rwsadmm.grad``, ``rwsadmm.zone_update``,
``rwsadmm.scatter``); XLA keeps the scope in each instruction's
``op_name``, and the TPU profiler writes it as the ``tf_op`` stat of the
op's event metadata in the ``.xplane.pb`` file. ``ProfileData`` does not
read event metadata, so :func:`read_op_scopes` decodes the file's
protobuf wire format for it. A fusion counts under the scope of its own
``op_name``.

Every reader takes the traced run's ``ctx`` (see ``harness``) and
returns None when the run holds nothing to read: a program without the
spans or scopes, or a run without a device.
"""
from __future__ import annotations

import glob
import os
import re

from . import trace
from .readers import CHUNK_MODULE

#: the stat of a device op's event metadata that holds its op_name
SCOPE_STAT = "tf_op"


def host_ms_per_round(ctx, *phases: str):
    """Milliseconds per round of the host spans ``repro.<phase>`` inside
    the window: the union of each thread's spans, summed over threads."""
    want = {"repro." + p for p in phases}
    lo, hi = ctx["window"]
    per_line: dict = {}
    for e in ctx["events"]:
        if (e.name in want and e.plane.startswith("/host:")
                and e.end > lo and e.start < hi):
            per_line.setdefault((e.plane, e.line), []).append(
                (e.start, e.end))
    if not per_line:
        return None
    ns = sum(trace.total(trace.clip(iv, lo, hi))
             for iv in per_line.values())
    return ns / 1e6 / ctx["rounds"]


def scope_ms_per_round(ctx, scope: str):
    """Busy device milliseconds per round (averaged over the cell's
    devices) of the ops inside runs of the compiled chunk whose op_name
    holds ``scope``, alone or under a transform (``vmap(<scope>)``)."""
    if not ctx["devices"]:
        return None
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/):])")
    mine = {k for k, v in op_scopes(ctx).items() if pat.search(v)}
    if not mine:
        return None
    lo, hi = ctx["window"]
    total = 0.0
    for d in ctx["devices"]:
        ops = [e for e in trace.select(ctx["events"], plane=d,
                                       line=trace.OPS_LINE)
               if (e.plane, e.name) in mine]
        runs = trace.select(ctx["events"], plane=d,
                            line=trace.MODULES_LINE, name=CHUNK_MODULE)
        ops = trace.inside(ops, [(e.start, e.end) for e in runs])
        total += trace.total(trace.clip([(e.start, e.end) for e in ops],
                                        lo, hi))
    return total / len(ctx["devices"]) / 1e6 / ctx["rounds"]


def op_scopes(ctx) -> dict:
    """{(device plane, op event name): op_name} of the run's trace, kept
    in ``ctx["op_scopes"]``. The harness rebuilds its cell's trace
    directory in each traced run, so the newest trace under it is this
    run's."""
    if "op_scopes" not in ctx:
        from .harness import OUT

        files = glob.glob(os.path.join(OUT, "trace", "*", "plugins",
                                       "profile", "*", "*.xplane.pb"))
        ctx["op_scopes"] = (read_op_scopes(max(files, key=os.path.getmtime))
                            if files else {})
    return ctx["op_scopes"]


def read_op_scopes(path: str) -> dict:
    """{(device plane, event metadata name): ``tf_op`` stat} of an
    ``.xplane.pb`` file; {} when the file cannot be decoded."""
    with open(path, "rb") as f:
        buf = f.read()
    try:
        return _space_scopes(buf)
    except (IndexError, ValueError):
        return {}


# ------------------------------------------------- protobuf wire format --
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
# key 1, value 2), .stat_metadata = 5 (map entry, value XStatMetadata:
# id 1, name 2); XEventMetadata.name = 2, .stats = 5; XStat.metadata_id
# = 1, .str_value = 5, .ref_value = 7 (a stat_metadata id whose name is
# the string). Field numbers from tsl/profiler/protobuf/xplane.proto.

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    return next((v for f, v in _fields(buf, *span) if f == 2), None)


def _space_scopes(buf) -> dict:
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                metas.append(_map_value(buf, v))
            elif g == 5:
                sm = _map_value(buf, v)
                if sm is not None:
                    got = dict(_fields(buf, *sm))
                    if 1 in got and 2 in got:
                        stat_names[got[1]] = _text(buf, got[2])
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        scope_ids = {k for k, v in stat_names.items() if v == SCOPE_STAT}
        for meta in metas:
            if meta is None:
                continue
            ev_name, scope = None, None
            for g, v in _fields(buf, *meta):
                if g == 2:
                    ev_name = _text(buf, v)
                elif g == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in scope_ids:
                        if 5 in stat:
                            scope = _text(buf, stat[5])
                        elif 7 in stat:
                            scope = stat_names.get(stat[7])
            if ev_name is not None and scope:
                out[(name, ev_name)] = scope
    return out
