"""The reduction from a profiler trace to device metrics: on a trace
recorded on the CPU in the test, and on hand-made device events whose
busy union, idle share, module and collective filters are known."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, readers, trace
from chipbench.trace import Event

DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def cpu_events(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return trace.load(d)


def test_cpu_trace_loads_with_window(cpu_events):
    (win,) = trace.select(cpu_events, name=r"^chipbench\.window$")
    assert win.plane.startswith("/host:") and win.end > win.start
    inside = [e for e in cpu_events if e.plane == win.plane
              and e.start >= win.start and e.end <= win.end]
    assert inside
    busy = trace.total([(e.start, e.end) for e in inside])
    assert 0 < busy <= (win.end - win.start) * len(
        {e.line for e in inside})
    assert trace.planes(cpu_events) == []     # no device plane on a CPU


def _ev(line, name, s, e, plane=DEV):
    return Event(plane, line, name, float(s), float(e))


EVENTS = [
    _ev(trace.MODULES_LINE, "jit_chunk(11)", 100, 400),
    _ev(trace.OPS_LINE, "fusion.1", 100, 200),
    _ev(trace.OPS_LINE, "fusion.2", 150, 250),          # overlaps fusion.1
    _ev(trace.OPS_LINE, "all-reduce.3", 300, 350),
    _ev(trace.MODULES_LINE, "jit_eval_rows(12)", 600, 700),
    _ev(trace.OPS_LINE, "convolution.4", 600, 700),
    _ev(trace.OPS_LINE, "fusion.9", 950, 1100),         # crosses the end
    _ev("python", "chipbench.schedule", 420, 590, plane="/host:CPU"),
]


def _ctx(rounds=2):
    return {"events": EVENTS, "devices": [DEV], "window": (0.0, 1000.0),
            "rounds": rounds, "chips": 1, "telemetry": [],
            "round_flops": 197e3, "round_bytes": 819.0,
            "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_busy_union_and_idle_share():
    ctx = _ctx()
    # fusion.1 ∪ fusion.2 = [100, 250]; + all-reduce 50 + conv 100 +
    # fusion.9 clipped to [950, 1000]
    assert readers.device_ns(ctx) == 150 + 50 + 100 + 50
    assert harness.metric_reader("device_idle_share")(ctx) == 65.0


def test_module_and_collective_filters():
    ctx = _ctx()
    assert readers.device_ns(ctx, module=readers.CHUNK_MODULE) == 200
    assert readers.device_ns(ctx, op=trace.COLLECTIVE) == 50
    assert readers.chunk_s_per_round(ctx) == 200 / 1e9 / 2
    assert harness.metric_reader("chunk_device_ms_per_round")(
        ctx) == pytest.approx(200 / 1e6 / 2)
    # least time 1 ns (both bounds) over 100 ns of chunk per round
    assert harness.metric_reader("round_roofline")(ctx) == pytest.approx(1.0)
    assert harness.metric_reader("round_mfu")(ctx) == pytest.approx(
        100 * 197e3 * 2 / 1e-6 / 197e12)


def test_gaps_are_labelled_by_host_work():
    busy = [(e.start, e.end) for e in trace.select(
        EVENTS, plane=DEV, line=trace.OPS_LINE)]
    gaps = trace.gaps(busy, 0, 1000)
    assert gaps == [(0, 100), (250, 300), (350, 600), (700, 950)]
    host = trace.select(EVENTS, plane="/host:")
    assert trace.label((350, 600), host) == "chipbench.schedule"
    assert trace.label((0, 100), host) == "unlabelled"


def test_readers_return_nothing_without_a_device():
    ctx = dict(_ctx(), devices=[])
    for name in ("device_idle_share", "chunk_device_ms_per_round",
                 "round_roofline", "schedule_ms_per_round"):
        assert harness.metric_reader(name)(ctx) is None
