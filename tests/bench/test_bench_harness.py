"""The benchmark harness at unit-test size on the CPU: every cell's run
gives a well-formed result line, files are found by name, and the command
refuses to run without a TPU."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest
from bench_tiny import REPO, cell_names, tiny_root

from bench import harness, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_root"))


def _execute(root, name, trace, tmp_path, seed=20260417):
    cell = harness.find_cell(name, root)
    return run.execute(cell, jax.devices()[:1], seed, 0.3, trace, time.perf_counter(),
                       trace_dir=tmp_path / "trace")


@pytest.mark.parametrize("name", cell_names())
def test_cell_line_well_formed(root, name, tmp_path):
    line = _execute(root, name, 0, tmp_path)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert line["device"]["count"] == 1
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(line)


def test_traced_line_reads_spans(root, tmp_path):
    """On the CPU there is no device plane: the device readers find nothing
    and leave their metrics out; the host spans are still read."""
    line = _execute(root, "table3_drive.fio_zipf_read", 1, tmp_path)
    assert set(line["metrics"]) == {"host_ms_per_sim"}
    assert line["metrics"]["host_ms_per_sim"]["value"] > 0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"
    assert not (tmp_path / "trace").exists()


HOT_SET = '''
import numpy as np


def build(params, n_logical, chunk, n_requests, seed):
    rng = np.random.default_rng(seed)
    hot = rng.permutation(n_logical)[: params["hot_pages"]]
    lpn = hot[rng.integers(0, len(hot), size=n_requests)].astype(np.int32)
    n_chunks = -(-n_requests // chunk)
    lpn = np.concatenate([lpn, np.full(n_chunks * chunk - n_requests, -1, np.int32)])
    return {"lpn": lpn.reshape(n_chunks, chunk), "op": np.zeros_like(lpn).reshape(n_chunks, chunk)}
'''

# a mix of the general generator given as data alone, and a mix whose
# generator is a module of its own
ADDED = {
    "data": ({"generator": "synthetic", "theta": 0.99, "read_frac": 0.5,
              "write_theta": 0.99, "why": "YCSB A: Zipfian reads and updates"}, None),
    "code": ({"generator": "hot_set", "hot_pages": 64, "why": "reads of a hot set"},
             HOT_SET),
}


@pytest.mark.parametrize("kind", sorted(ADDED))
def test_added_config_and_traffic_found_by_name(root, tmp_path, kind):
    """A configuration, a traffic mix (with its generator where it needs
    one of its own) and a cell added as files and entries run without an
    edit to any file that is there."""
    mix, code = ADDED[kind]
    new = tiny_root(tmp_path / "new")
    conf = json.loads((new / "bench/configs/table3_drive.json").read_text())
    conf["sim"]["initial_pe"] = 833
    (new / "bench/configs/old_drive.json").write_text(json.dumps(conf))
    (new / "bench/traffic/added.json").write_text(json.dumps(mix))
    if code:
        (new / f"bench/traffic/{mix['generator']}.py").write_text(code)
    (new / "bench/limits/old_drive.added.json").write_text(
        (new / "bench/limits/table3_drive.mixed_rw.json").read_text())
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "old_drive", "source": "test", "reduced": [],
                             "file": "bench/configs/old_drive.json", "why": "test"})
    bench["workloads"].append({"name": "old_drive.added", "config": "old_drive",
                               "traffic": "added", "chips": 1, "why": "test"})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("old_drive.added", new)
    assert cell.config["sim"]["initial_pe"] == 833 and cell.traffic == mix
    assert [m["name"] for m in cell.per_layer] == []
    tr = cell.trace(1536, 128, 256, 7)
    if code:
        assert len(set(tr["lpn"].ravel().tolist())) <= 64
    else:
        assert 0 < int((tr["op"] == 1).sum()) < 256
    line = _execute(new, "old_drive.added", 0, tmp_path)
    assert line["correct"] is True and line["attempted"] > 0


def test_missing_module_named():
    with pytest.raises(FileNotFoundError, match="no_such"):
        harness.find(REPO, "traffic", "no_such")


def test_cli_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell_names()[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


class _FakeRunner:
    times = {"bench.dispatch": 0.0}

    def one(self):
        return [harness.Sim(seed=0, initial_pe=0, requests=1, reads=1, chunks=1,
                            summary={}, state=object())]


@pytest.mark.parametrize("sample", [1, 3])
def test_window_keeps_states_of_its_sample_only(sample):
    w = harness.measure(_FakeRunner(), 0.05, sample, seed=9)
    assert w.sims == len(w.done) > sample
    assert len(set(w.picks)) == sample
    kept = [i for i, s in enumerate(w.done) if s.state is not None]
    assert sorted(kept) == sorted(w.picks)
    assert w.calls == w.sims and 0 <= w.slowest["call"] < w.calls
    assert w.slowest["s"] >= w.median_call_s and "bench.dispatch" in w.slowest
