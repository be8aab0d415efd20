"""The per-phase and per-span reduction of ``bench/phases.py``: on events
built here, on a CPU profiler trace of the program's host spans, and on a
small trace recorded on a TPU v5e."""

import gzip
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, phases, trace_reduce
from bench.metrics import device_idle_pct, device_ms_per_chunk

MS = 1e6  # ns
DATA = Path(__file__).parent / "data"


@pytest.fixture
def trace():
    """Two chips in a window of 100 ms, the ops of test_bench_trace.py with
    a phase each, benchmark spans and the program's spans inside them."""
    return phases.PhaseTrace(
        ops={
            0: [(-5 * MS, 5 * MS, "fusion.1"), (10 * MS, 40 * MS, "while.3"),
                (15 * MS, 30 * MS, "fusion.2"), (60 * MS, 70 * MS, "fusion.2")],
            1: [(0, 50 * MS, "while.3")],
        },
        spans=[
            (0, 100 * MS, "bench.window"),
            (0, 45 * MS, "bench.dispatch"),
            (45 * MS, 80 * MS, "bench.wait"),
            (80 * MS, 100 * MS, "bench.summarize"),
        ],
        program_spans=[
            (0, 4 * MS, "repro.run.put"),
            (4 * MS, 8 * MS, "repro.run.dispatch"),
            (82 * MS, 98 * MS, "repro.summarize"),
        ],
        scopes={"fusion.1": "step_read", "fusion.2": "step_reclaim", "while.3": "scan"},
    )


def test_phase_of():
    assert phases.phase_of("jit(_run_jit)/while/body/closed_call/step_read/gather") == "step_read"
    assert phases.phase_of(
        "jit(_sweep_jit)/vmap(while)/body/closed_call/step_gc/cond/branch_1_fun/step_x"
    ) == "step_gc"
    assert phases.phase_of("jit(_run_jit)/while/body/dynamic_update_slice") == "scan"
    assert phases.phase_of("") == "scan"


def test_phase_seconds(trace):
    got = phases.phase_s(trace)
    assert set(got) == {*phases.PHASES, phases.SCAN}
    assert got["step_read"] == pytest.approx(5 / 1000)  # clipped at the window
    assert got["step_reclaim"] == pytest.approx((15 + 10) / 1000)
    assert got["scan"] == pytest.approx((15 + 50) / 1000)  # the loop less its body
    assert got["step_write"] == 0.0


def test_phases_add_up_to_device_ms_per_chunk(trace):
    red = phases.reduce(trace, chunks=19, sims=2)
    whole = device_ms_per_chunk.read(harness.Window(trace=trace, chunks=19))
    assert red["device_ms_per_chunk"] == pytest.approx(whole)
    assert sum(red["ms_per_chunk"].values()) == pytest.approx(whole)
    assert red["ms_per_chunk"]["reclaim"] == pytest.approx(25 / 19)
    assert red["top_ops"][0] == ["while.3", pytest.approx(0.0325), "scan"]


def test_program_spans_per_sim(trace):
    red = phases.reduce(trace, chunks=19, sims=2)
    assert red["dispatch_ms_per_sim"] == pytest.approx(8 / 2)
    assert red["summarize_ms_per_sim"] == pytest.approx(16 / 2)


def test_idle_by_innermost_span(trace):
    idle = phases.idle_by_span(trace)
    # chip 0 idles 5-10, 40-60 and 70-100, chip 1 50-100; program spans
    # own their stretch before the benchmark spans around them
    want = {
        "repro.run.dispatch": 3,  # chip 0, 5-8
        "bench.dispatch": 2 + 5,  # chip 0, 8-10 and 40-45
        "bench.wait": 15 + 10 + 30,  # chip 0, 45-60 and 70-80; chip 1, 50-80
        "bench.summarize": 4 * 2,  # 80-82 and 98-100 on both chips
        "repro.summarize": 2 * 16,  # 82-98 on both chips
    }
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert idle[name] == pytest.approx(ms / 1000 / 2), name  # mean of 2 chips
    window = trace_reduce.window_s(trace)
    assert sum(idle.values()) / window * 100 == pytest.approx(
        device_idle_pct.read(harness.Window(trace=trace)))


def test_readers_find_nothing_without_device_ops(trace):
    empty = phases.PhaseTrace(spans=trace.spans)
    assert phases.idle_by_span(empty) == {}
    assert set(phases.phase_s(empty).values()) == {0.0}


def test_program_spans_on_cpu_trace(tmp_path):
    """The program's own spans reach a CPU profiler trace, nested as the
    reduction reads them."""
    from repro.experiments import sweep
    from repro.ssdsim import engine, geometry, workload

    cfg = geometry.tiny_config()
    tr = workload.mixed_trace(cfg, 2 * cfg.chunk, 1.2, seed=3)
    spec = sweep.SweepSpec(scenario="zipf", n_requests=2 * cfg.chunk,
                           policies=(cfg.policy,), initial_pe=(100, 900), seeds=(0,),
                           base=cfg)
    s, _ = engine.run(cfg, tr)  # compile outside the trace
    engine.summarize(jax.block_until_ready(s), cfg)
    sweep.run_sweep(spec)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        s, _ = engine.run(cfg, tr)
        engine.summarize(jax.block_until_ready(s), cfg)
        results = sweep.run_sweep(spec)
    jax.profiler.stop_trace()
    got = phases.load(trace_reduce.find_xplane(tmp_path))
    assert [s[2] for s in got.spans] == [trace_reduce.WINDOW_SPAN]
    names = [s[2] for s in sorted(got.program_spans)]
    assert names == ["repro.run.put", "repro.run.dispatch", "repro.summarize",
                     "repro.sweep.build", "repro.sweep.stack", "repro.sweep.dispatch",
                     "repro.sweep.fetch", "repro.sweep.summarize",
                     *["repro.summarize"] * len(results)]
    span = {n: (a, b) for a, b, n in got.program_spans}
    lo, hi = got.window
    assert all(lo <= a <= b <= hi for a, b, _ in got.program_spans)
    outer = span["repro.sweep.summarize"]
    lanes = [(a, b) for a, b, n in got.program_spans if n == "repro.summarize"][1:]
    assert len(lanes) == 2 and all(outer[0] <= a <= b <= outer[1] for a, b in lanes)
    assert span["repro.run.put"][1] <= span["repro.run.dispatch"][0]
    assert np.diff([span[f"repro.sweep.{k}"][0] for k in
                    ("build", "stack", "dispatch", "fetch", "summarize")]).min() > 0


def test_recorded_tpu_trace(tmp_path):
    """One tiny mixed drive run and its summary in a measured window,
    recorded on a TPU v5e (``python3 bench/phases.py --root <tiny root>
    --workload table3_drive.mixed_rw --seed 7 --seconds 0.001 --keep <dir>``,
    the tiny root of ``bench_tiny.tiny_root``; gzipped): the phase scopes
    and the program's spans reach the profiler's file where the reduction
    reads them, and the phases add up to the busy time."""
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "tiny_v5e.xplane.pb.gz").read_bytes()))
    tr = phases.load(path)
    assert set(tr.scopes.values()) == {*phases.PHASES, phases.SCAN}
    assert [s[2] for s in sorted(tr.program_spans)] == [
        "repro.run.put", "repro.run.dispatch", "repro.summarize"]
    red = phases.reduce(tr, chunks=12, sims=1)
    assert min(red["ms_per_chunk"].values()) > 0
    assert sum(red["ms_per_chunk"].values()) == pytest.approx(red["device_ms_per_chunk"])
    assert red["top_ops"][0][2] == phases.SCAN  # the scan loop's own while
    idle = phases.idle_by_span(tr)
    assert sum(idle.values()) / red["window_s"] * 100 == pytest.approx(red["idle_pct"])
    assert max(idle, key=idle.get) == "repro.summarize"
