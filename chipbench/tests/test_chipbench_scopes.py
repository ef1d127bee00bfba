"""The readers of the program's own spans and scopes: host spans
``repro.<phase>`` on hand-made host events (nested, crossing the
window's end, on a second thread), device ops under the compiled
round's named scopes (inside and outside runs of the compiled chunk,
with and without a scope), and the decoder that reads each op's scope
from the ``tf_op`` stat of a hand-encoded ``.xplane.pb``."""
import os

import pytest

from chipbench import harness, scopes, trace
from chipbench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _host(name, s, e, line="python"):
    return Event(HOST, line, name, float(s), float(e))


def _dev(line, name, s, e):
    return Event(DEV, line, name, float(s), float(e))


HOST_EVENTS = [
    _host("chipbench.schedule", 90, 510),
    _host("repro.schedule", 100, 500),
    _host("repro.scenario_rollout", 110, 400),
    _host("repro.mobility", 120, 300),
    _host("repro.links", 300, 390),
    _host("repro.walk", 400, 420),
    _host("repro.zones", 420, 470),
    _host("repro.price", 470, 490),
    _host("repro.readback", 600, 650),
    _host("repro.mobility", 900, 1200),                  # crosses the end
    _host("repro.mobility", 150, 250, line="worker"),    # second thread
    _host("repro.links", 1100, 1300),                    # after the end
]


def _ctx(events, devices=(), rounds=2, op_scopes=None):
    return {"events": list(events), "devices": list(devices),
            "window": (0.0, 1000.0), "rounds": rounds, "chips": 1,
            "telemetry": [], "op_scopes": dict(op_scopes or {})}


@pytest.mark.parametrize("metric,ns", [
    # 180 on the main thread + 100 clipped at the end + 100 on the worker
    ("mobility_ms_per_round", 180 + 100 + 100),
    ("links_ms_per_round", 90),
    ("plan_ms_per_round", 20 + 50 + 20),
    ("readback_ms_per_round", 50),
])
def test_host_span_metrics(metric, ns):
    got = harness.metric_reader(metric)(_ctx(HOST_EVENTS))
    assert got == pytest.approx(ns / 1e6 / 2)


def test_nested_spans_each_count_their_own_interval():
    ctx = _ctx(HOST_EVENTS)
    assert scopes.host_ms_per_round(ctx, "schedule") == pytest.approx(
        400 / 1e6 / 2)
    assert scopes.host_ms_per_round(ctx, "scenario_rollout") == \
        pytest.approx(290 / 1e6 / 2)
    # one thread's overlapping spans of the asked names count once
    assert scopes.host_ms_per_round(ctx, "schedule", "mobility") == \
        pytest.approx((400 + 100 + 100) / 1e6 / 2)


def test_host_metrics_none_without_spans():
    bare = [e for e in HOST_EVENTS if not e.name.startswith("repro.")]
    only_late = [_host("repro.links", 1100, 1300)]
    for metric in ("mobility_ms_per_round", "links_ms_per_round",
                   "plan_ms_per_round", "readback_ms_per_round"):
        assert harness.metric_reader(metric)(_ctx(bare)) is None
        assert harness.metric_reader(metric)(_ctx(only_late)) is None


DEVICE_EVENTS = [
    _dev(trace.MODULES_LINE, "jit_chunk(11)", 100, 400),
    _dev(trace.OPS_LINE, "%while.1 = while(...)", 100, 400),
    _dev(trace.OPS_LINE, "%fusion.1 = fusion(...)", 110, 200),
    _dev(trace.OPS_LINE, "%convolution.2 = convolution(...)", 150, 220),
    _dev(trace.OPS_LINE, "%fusion.3 = fusion(...)", 230, 260),
    _dev(trace.OPS_LINE, "%fusion.4 = fusion(...)", 270, 330),
    _dev(trace.OPS_LINE, "%copy.5 = copy(...)", 330, 380),     # no scope
    _dev(trace.MODULES_LINE, "jit_eval_rows(12)", 600, 700),
    _dev(trace.OPS_LINE, "%fusion.1 = fusion(...)", 600, 700),  # not chunk
    _dev(trace.MODULES_LINE, "jit_chunk(11)", 950, 1100),
    _dev(trace.OPS_LINE, "%fusion.4 = fusion(...)", 960, 1050),  # crosses
]
OP_SCOPES = {
    (DEV, "%while.1 = while(...)"): "jit(chunk)/while:",
    (DEV, "%fusion.1 = fusion(...)"):
        "jit(chunk)/while/body/closed_call/rwsadmm.grad/dot_general:",
    (DEV, "%convolution.2 = convolution(...)"):
        "jit(chunk)/while/body/closed_call/vmap(rwsadmm.grad)/conv:",
    (DEV, "%fusion.3 = fusion(...)"):
        "jit(chunk)/while/body/closed_call/rwsadmm.zone_update/mul:",
    (DEV, "%fusion.4 = fusion(...)"):
        "jit(chunk)/while/body/closed_call/rwsadmm.scatter/scatter-add:",
    # a longer scope name that must not count as rwsadmm.grad
    ("/device:TPU:1", "%fusion.1 = fusion(...)"):
        "jit(chunk)/rwsadmm.grad_norm/add:",
}


@pytest.mark.parametrize("metric,ns", [
    # fusion.1 ∪ convolution.2 = [110, 220]; eval's fusion.1 not counted
    ("grad_device_ms_per_round", 110),
    ("zone_update_device_ms_per_round", 30),
    # [270, 330] + the second chunk's run clipped to [960, 1000]
    ("scatter_device_ms_per_round", 60 + 40),
])
def test_device_scope_metrics(metric, ns):
    ctx = _ctx(DEVICE_EVENTS, devices=[DEV], op_scopes=OP_SCOPES)
    assert harness.metric_reader(metric)(ctx) == pytest.approx(
        ns / 1e6 / 2)


def test_device_metrics_none_without_device_or_scopes():
    for metric in ("grad_device_ms_per_round",
                   "zone_update_device_ms_per_round",
                   "scatter_device_ms_per_round"):
        read = harness.metric_reader(metric)
        assert read(_ctx(DEVICE_EVENTS, op_scopes=OP_SCOPES)) is None
        unscoped = {k: "jit(chunk)/while/body/add:" for k in OP_SCOPES}
        assert read(_ctx(DEVICE_EVENTS, devices=[DEV],
                         op_scopes=unscoped)) is None


def test_scope_name_must_match_a_whole_component():
    only_norm = {k: v for k, v in OP_SCOPES.items() if "norm" in v}
    ctx = _ctx(DEVICE_EVENTS, devices=[DEV], op_scopes=only_norm)
    assert scopes.scope_ms_per_round(ctx, "rwsadmm.grad") is None


# ------------------------------------------------------------- xplane --
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, v):
    return _varint(field << 3) + _varint(v)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _double(field):
    return _varint(field << 3 | 1) + b"\x00" * 8


def _stat_md(sid, name):          # XPlane.stat_metadata map entry
    return _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, name)))


def _event_md(eid, name, *stats):  # XPlane.event_metadata map entry
    return _msg(4, _int(1, eid) + _msg(2, _int(1, eid) + _msg(2, name)
                                       + b"".join(stats)))


def _plane(name, *parts):
    return _msg(1, _int(1, 7) + _msg(2, name) + b"".join(parts))


def _space():
    line = _msg(3, _int(1, 1) + _msg(2, "XLA Ops") + _msg(
        4, _int(1, 10) + _int(2, 5) + _int(3, 9)))
    device = _plane(
        DEV, line, _stat_md(1, "tf_op"), _stat_md(2, "flops"),
        _stat_md(3, "jit(chunk)/rwsadmm.scatter/add:"),
        _event_md(10, "%fusion.1 = fusion(...)",
                  _msg(5, _int(1, 1) + _msg(5, "jit(chunk)/rwsadmm.grad/dot:"))),
        _event_md(11, "%copy.2 = copy(...)",
                  _msg(5, _int(1, 2) + _int(4, 99))),
        _event_md(12, "%fusion.3 = fusion(...)",
                  _msg(5, _int(1, 2) + _double(2)),
                  _msg(5, _int(1, 1) + _int(7, 3))))
    host = _plane(HOST, _stat_md(1, "tf_op"),
                  _event_md(10, "ignored", _msg(5, _int(1, 1) + _msg(5, "x"))))
    return host + device + _msg(4, "a-host-name")


def test_read_op_scopes_decodes_tf_op(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space())
    assert scopes.read_op_scopes(str(path)) == {
        (DEV, "%fusion.1 = fusion(...)"): "jit(chunk)/rwsadmm.grad/dot:",
        (DEV, "%fusion.3 = fusion(...)"): "jit(chunk)/rwsadmm.scatter/add:",
    }
    path.write_bytes(_space()[:-30])              # cut short
    assert scopes.read_op_scopes(str(path)) == {}


def test_op_scopes_reads_the_newest_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT", str(tmp_path))
    assert scopes.op_scopes({}) == {}
    d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    old = d / "old.xplane.pb"
    old.write_bytes(b"")
    new = d / "new.xplane.pb"
    new.write_bytes(_space())
    os.utime(old, (1, 1))
    ctx = {}
    got = scopes.op_scopes(ctx)
    assert len(got) == 2 and ctx["op_scopes"] is got
    new.unlink()                                  # kept in the ctx
    assert scopes.op_scopes(ctx) is got
