"""What decides the benchmark's ``correct``, at unit-test size on the CPU.

- The plain reference agrees with the program on the cells' traffic, also
  where capacity recovery and GC run.
- The control (the reference in bfloat16, one precision below the float32
  the simulator states, put in the program's place) fails the limits.
- A whole run with the timed path broken underneath reports ``correct``
  false: a step that returns its state unchanged, half of each chunk's
  requests left out, and an answer altered where it is produced.
"""

import json
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from bench_tiny import REPO, TINY_SIM, cell_names, tiny_root

from bench import harness, reference, run, verify
from bench.traffic import synthetic

TRAFFIC = ("fio_zipf_read", "mixed_rw")
# the tiny geometry as it is, and with GC and capacity recovery under
# pressure (a fuller working set, a higher GC watermark)
PRESSURE = {"plain": {}, "pressure": dict(n_logical=2200, gc_free_threshold=12,
                                          gc_victims_per_pass=1)}


def _sim(extra):
    conf = json.loads((REPO / "bench/configs/table3_drive.json").read_text())
    return {**conf["sim"], **TINY_SIM, **extra}


def _limits(traffic):
    return json.loads(
        (REPO / f"bench/limits/table3_drive.{traffic}.json").read_text())["limits"]


def _trace(sim, traffic, seed, chunks=60):
    mix = json.loads((REPO / "bench/traffic" / f"{traffic}.json").read_text())
    return synthetic.build(mix, sim["n_logical"], sim["chunk"], chunks * sim["chunk"], seed)


@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("pressure", sorted(PRESSURE))
def test_reference_agrees_with_program(traffic, pressure):
    from repro.ssdsim import engine

    sim = _sim(PRESSURE[pressure])
    cfg = harness.sim_config({"sim": sim})
    for seed in (1, 2):
        tr = _trace(sim, traffic, seed)
        s, _ = engine.run(cfg, tr)
        got = engine.summarize(s, cfg)
        ref = reference.simulate(sim, tr["lpn"], tr["op"])
        g = verify.gaps(got, ref)
        assert g["count_gap"] == 0, g
        assert verify.passes(g, _limits(traffic)), g
        assert verify.invariant_faults(verify.host_state(s), sim) == []


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_control_fails(traffic):
    sim = _sim({})
    for seed in (3, 4, 5):
        tr = _trace(sim, traffic, seed)
        ref = reference.simulate(sim, tr["lpn"], tr["op"])
        ctl = reference.simulate(sim, tr["lpn"], tr["op"], dtype=ml_dtypes.bfloat16)
        assert not verify.passes(verify.gaps(ctl, ref), _limits(traffic))


def test_invariants_catch_a_broken_state():
    from repro.ssdsim import state as st

    sim = _sim({})
    s = verify.host_state(st.init_state(harness.sim_config({"sim": sim})))
    assert verify.invariant_faults(s, sim) == []
    s["l2p"] = s["l2p"].copy()
    s["l2p"][5] = s["l2p"][6]
    assert "l2p -> p2l" in verify.invariant_faults(s, sim)


def _unchanged(step):
    def broken(s, req, *a, **kw):
        return s, step(s, req, *a, **kw)[1]
    return broken


def _half(step):
    def broken(s, req, *a, **kw):
        lpn = jnp.where(jnp.arange(req[0].shape[0]) % 2 == 1, -1, req[0])
        return step(s, (lpn,) + tuple(req[1:]), *a, **kw)
    return broken


def _altered(summarize):
    def broken(*a, **kw):
        out = summarize(*a, **kw)
        return dict(out, reads=out["reads"] + 1)
    return broken


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("name", cell_names())
def test_broken_timed_path_is_not_correct(root, name, fault, monkeypatch, tmp_path):
    from repro.ssdsim import engine

    if fault == "altered":
        monkeypatch.setattr(engine, "summarize", _altered(engine.summarize))
    else:
        wrap = _unchanged if fault == "unchanged" else _half
        monkeypatch.setattr(engine, "step_chunk", wrap(engine.step_chunk))
    jax.clear_caches()
    try:
        cell = harness.find_cell(name, root)
        line = run.execute(cell, jax.devices()[:1], 11, 0.2, 0, time.perf_counter(),
                           trace_dir=tmp_path / "trace")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert line["correct"] is False and line["failed"] > 0


def test_drive_reference_uses_timed_trace(root):
    """The reference re-runs the very trace a sampled window run served."""
    cell = harness.find_cell("table3_drive.mixed_rw", root)
    runner = harness.make(cell, 5)
    sims = runner.one()
    tr = runner.trace_of(sims[0])
    assert int(((tr["op"] == 0) & (tr["lpn"] >= 0)).sum()) == sims[0].reads
    grid = harness.make(harness.find_cell("table3_age_grid.fio_zipf_read", root), 5)
    lane = grid.one()[3]
    tr = grid.trace_of(lane)
    ref = reference.simulate(grid.cell.config["sim"], tr["lpn"], tr["op"],
                             initial_pe=lane.initial_pe)
    assert verify.gaps(lane.summary, ref)["count_gap"] == 0


def test_seed_gives_same_inputs(root):
    cell = harness.find_cell("table3_drive.fio_zipf_read", root)
    a, b = harness.make(cell, 2**31 + 5), harness.make(cell, 2**31 + 5)
    for (sa, ta, _), (sb, tb, _) in zip(a.pool, b.pool):
        assert sa == sb and np.array_equal(ta["lpn"], tb["lpn"])


@pytest.mark.parametrize("name", cell_names())
def test_control_script_reads_served_runs(root, name):
    """``bench/control.py`` runs the reference over the runs a window of the
    cell serves first, in float32 and in bfloat16. (That the control fails
    the limits over runs of some length is ``test_control_fails``'s.)"""
    from bench import control

    cell = harness.find_cell(name, root)
    got, seed, runs = control._one((root, name, 3))
    assert (got, seed) == (name, 3)
    served = harness.make(cell, 3).served(cell.sample)
    assert [(r["trace_seed"], r["initial_pe"]) for r in runs] == [
        (s.seed, s.initial_pe) for s in served]
    assert all(r["control"]["stats_gap"] > 0 for r in runs)
