"""The benchmark's trace-to-metric reduction, on events built here.

Two chips, a window of 100 ms, overlapping operations, and host spans:
the busy union, the idle share, device ms per chunk, the top operations
and the idle gaps named by the host span they fall in.
"""

import pytest

from bench import harness, trace_reduce
from bench.metrics import device_idle_pct, device_ms_per_chunk, host_ms_per_sim

MS = 1e6  # ns


@pytest.fixture
def trace():
    return trace_reduce.Trace(
        ops={
            # chip 0: a loop (10-40) holding an op of its body (15-30), one
            # op at 60-70, and one that starts before the window (clipped)
            0: [(-5 * MS, 5 * MS, "fusion.1"), (10 * MS, 40 * MS, "while.3"),
                (15 * MS, 30 * MS, "fusion.2"), (60 * MS, 70 * MS, "fusion.2")],
            # chip 1: busy 0-50
            1: [(0, 50 * MS, "while.3")],
        },
        spans=[
            (0, 100 * MS, "bench.window"),
            (0, 45 * MS, "bench.dispatch"),
            (45 * MS, 80 * MS, "bench.wait"),
            (80 * MS, 100 * MS, "bench.summarize"),
        ],
    )


def test_busy_union_and_idle(trace):
    busy = trace_reduce.busy_s(trace)
    assert busy[0] == pytest.approx((5 + 30 + 10) / 1000)  # 0-5, 10-40, 60-70
    assert busy[1] == pytest.approx(50 / 1000)
    assert trace_reduce.window_s(trace) == pytest.approx(0.1)
    # mean busy 47.5 ms of 100
    assert trace_reduce.idle_pct(trace) == pytest.approx(52.5)
    assert device_idle_pct.read(harness.Window(trace=trace)) == pytest.approx(52.5)


def test_device_ms_per_chunk(trace):
    w = harness.Window(trace=trace, chunks=19, sims=2)
    # 95 ms busy over both chips, 19 chunks simulated
    assert device_ms_per_chunk.read(w) == pytest.approx(5.0)
    assert device_ms_per_chunk.read(harness.Window(trace=trace_reduce.Trace(
        spans=trace.spans), chunks=19)) is None


def test_host_ms_per_sim(trace):
    w = harness.Window(trace=trace, sims=2)
    assert host_ms_per_sim.read(w) == pytest.approx((45 + 20) / 2)
    assert host_ms_per_sim.read(harness.Window(
        trace=trace_reduce.Trace(ops=trace.ops, spans=trace.spans[:1]), sims=2)) is None


def test_top_ops_by_self_time(trace):
    top = trace_reduce.top_ops(trace)
    # seconds of their own (the loop less its body's op), summed over runs,
    # averaged over the two chips
    assert top[0] == ["while.3", pytest.approx((15 + 50) / 1000 / 2)]
    assert top[1] == ["fusion.2", pytest.approx((15 + 10) / 1000 / 2)]
    assert top[2] == ["fusion.1", pytest.approx(5 / 1000 / 2)]


def test_op_name():
    assert trace_reduce.op_name(
        "%fusion.716 = f32[1024]{0:T(1024)S(1)} fusion(f32[1048576]{0} %x), kind=kCustom"
    ) == "fusion.716"
    assert trace_reduce.op_name("copy-start.3") == "copy-start.3"


def test_idle_gaps_named_by_host_span(trace):
    gaps = trace_reduce.idle_gaps(trace)
    assert gaps[0] == ["bench.wait", pytest.approx(0.05)]  # chip 1, 50-100
    assert gaps[1] == ["bench.summarize", pytest.approx(0.03)]  # chip 0, 70-100
    assert gaps[2] == ["bench.wait", pytest.approx(0.02)]  # chip 0, 40-60
    assert gaps[3] == ["bench.dispatch", pytest.approx(0.005)]  # chip 0, 5-10
    assert len(gaps) == 4


def test_window_span_required():
    with pytest.raises(ValueError):
        trace_reduce.Trace(ops={0: []}, spans=[]).window
