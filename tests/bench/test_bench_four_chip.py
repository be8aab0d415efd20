"""The four-chip wear-stage grid at unit-test size, on four CPU devices that
XLA fakes in a child process, and the two readers that only a multi-chip
trace gives meaning to, on a trace built here.

- ``bench/run.py``'s run of the cell on four devices is ``correct`` and
  names four devices;
- ``run_sweep`` shards the 48 lanes 12 to each device;
- every lane equals the same spec's one-device vmapped sweep bit for bit;
- sampled lanes, some from each device, agree with the plain reference
  within the cell's limits.
"""

import json
import os
import subprocess
import sys

import pytest
from bench_tiny import REPO

from bench import harness, trace_reduce
from bench.metrics import slowest_chip_ms_per_lane_chunk, sweep_serial_ms_per_lane

CELL = "table3_age_grid_4chip.fio_zipf_read"
CHIPS = 4

CHILD = r'''
import json, sys, time
from pathlib import Path

sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/tests/bench"]
import jax
from bench_tiny import tiny_root

from bench import harness, reference, run, verify
from repro.experiments import sweep

out = {}
root = tiny_root(Path(sys.argv[2]))
cell = harness.find_cell(sys.argv[3], root)
devs = jax.devices()[:cell.chips]
line = run.execute(cell, devs, 2**31 + 77, 0.3, 0, time.perf_counter(),
                   trace_dir=Path(sys.argv[2]) / "trace")
out["line"] = {k: line[k] for k in ("correct", "attempted", "failed", "device")}

runner = harness.make(cell, 2**31 + 78)
spec = runner.specs[0]
outputs = []
sharded_jit = sweep._sweep_sharded_jit


def spy(*a):
    outputs.append(sharded_jit(*a))
    return outputs[-1]


sweep._sweep_sharded_jit = spy
sharded = sweep.run_sweep(spec, devices=cell.chips)
sweep._sweep_sharded_jit = sharded_jit
# the zero-size leaves of the observability left off carry no lanes
out["shards"] = sorted({
    tuple(sorted((s.device.id, s.data.shape[0]) for s in leaf.addressable_shards))
    for leaf in jax.tree_util.tree_leaves(outputs[0]) if leaf.size})
out["lanes"] = len(sharded)

try:
    sweep.assert_results_identical(sharded, sweep.run_sweep(spec))
    out["identical"] = True
except AssertionError as e:
    out["identical"] = str(e)

per_chip = len(sharded) // cell.chips
checked = []
for lane in range(0, len(sharded), per_chip // 2):
    r = sharded[lane]
    tr = runner.trace_of(harness.Sim(r["run"]["seed"], r["run"]["initial_pe"],
                                     0, 0, 0, r))
    ref = reference.simulate(cell.config["sim"], tr["lpn"], tr["op"],
                             initial_pe=r["run"]["initial_pe"])
    g = verify.gaps(r, ref)
    checked.append({"lane": lane, "chip": lane // per_chip, "gaps": g,
                    "passes": verify.passes(g, cell.limits)})
out["reference"] = checked
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    """One child process on four faked CPU devices does the cell's checks."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + f" --xla_force_host_platform_device_count={CHIPS}").strip())
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(REPO), str(tmp_path_factory.mktemp("four")), CELL],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    found = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert found, p.stdout[-4000:]
    return json.loads(found[-1].removeprefix("RESULT "))


def test_cell_runs_correct_on_four_devices(four):
    line = four["line"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == CHIPS


def test_each_device_holds_twelve_lanes(four):
    assert four["lanes"] == 48
    # every leaf of the sweep's final state: one shard of 12 lanes per device
    assert four["shards"] == [[[d, 12] for d in range(CHIPS)]]


def test_sharded_lanes_equal_one_device_sweep(four):
    assert four["identical"] is True


def test_sampled_lanes_agree_with_reference(four):
    checked = four["reference"]
    assert sorted({c["chip"] for c in checked}) == list(range(CHIPS))
    for c in checked:
        assert c["gaps"]["count_gap"] == 0, c
        assert c["passes"], c


MS = 1e6  # ns


@pytest.fixture
def trace():
    """Four chips in a 100 ms window; two sweeps (10-50 and 60-100 ms)."""
    return trace_reduce.Trace(
        ops={
            0: [(20 * MS, 40 * MS, "while.1"), (25 * MS, 30 * MS, "fusion.2"),
                (70 * MS, 90 * MS, "while.1")],
            1: [(22 * MS, 45 * MS, "while.1"), (72 * MS, 92 * MS, "while.1")],
            # the busiest chip, 45 ms: one op starts before the window
            2: [(-10 * MS, 5 * MS, "fusion.3"), (20 * MS, 40 * MS, "while.1"),
                (70 * MS, 90 * MS, "while.1")],
            3: [(21 * MS, 41 * MS, "while.1"), (71 * MS, 91 * MS, "while.1")],
        },
        spans=[
            (0, 100 * MS, "bench.window"),
            (10 * MS, 50 * MS, "bench.sweep"),
            (60 * MS, 100 * MS, "bench.sweep"),
            (95 * MS, 99 * MS, "bench.summarize"),
        ],
    )


def test_slowest_chip_ms_per_lane_chunk(trace):
    # chip 2 is busy 5 + 20 + 20 ms; 64 lane-chunks over 4 chips, 16 a chip
    w = harness.Window(trace=trace, chunks=64, sims=8)
    assert slowest_chip_ms_per_lane_chunk.read(w) == pytest.approx(45 / 16)


def test_sweep_serial_ms_per_lane(trace):
    # no chip runs: 10-20 and 45-50 of the first sweep, 60-70 and 92-100 of
    # the second; 33 ms over 8 lanes
    w = harness.Window(trace=trace, chunks=64, sims=8)
    assert sweep_serial_ms_per_lane.read(w) == pytest.approx(33 / 8)


@pytest.mark.parametrize("reader", [slowest_chip_ms_per_lane_chunk, sweep_serial_ms_per_lane])
def test_readers_find_nothing_without_device_plane(trace, reader):
    """On the CPU the trace holds no device plane: the reader leaves its
    metric out."""
    w = harness.Window(trace=trace_reduce.Trace(spans=trace.spans), chunks=64, sims=8)
    assert reader.read(w) is None


def test_sweep_serial_needs_sweep_spans(trace):
    spans = [s for s in trace.spans if s[2] != "bench.sweep"]
    w = harness.Window(trace=trace_reduce.Trace(ops=trace.ops, spans=spans),
                       chunks=64, sims=8)
    assert sweep_serial_ms_per_lane.read(w) is None
