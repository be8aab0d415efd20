"""Sharded-vs-vmapped sweep equivalence (DESIGN.md §7.3).

The sharded executor must reproduce the single-device vmapped results
*exactly* — same ``engine.summarize`` dicts, bit for bit — for every grid
shape: even splits, uneven grids that force padding, and grids smaller than
the device count. The multi-device cases need more than one visible device,
so the tier-1 run (1 CPU device) skips them; CI exercises them in a
dedicated step under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.experiments import registry, sweep
from repro.ssdsim import engine, ftl, geometry, policies
from repro.ssdsim import state as st

TINY = geometry.tiny_config()
N_DEV = len(jax.devices())

_needs_devices = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >=2 devices; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


def multi_device(fn):
    """Skips on one device, and carries the ``multi_device`` marker so CI's
    dedicated faked-device step selects exactly the tests the tier-1 run
    skipped (``-m multi_device``) instead of re-running the whole file."""
    return pytest.mark.multi_device(_needs_devices(fn))


def _spec(**kw):
    d = dict(
        scenario="read_disturb_hammer",
        n_requests=2_048,
        policies=(geometry.BASELINE, geometry.RARO),
        initial_pe=(166, 833),
        seeds=(0,),
        base=TINY,
    )
    d.update(kw)
    return sweep.SweepSpec(**d)


# same runs, same order, every summarize value exactly equal — the shared
# checker the scaling benchmark also runs after its timing passes
_assert_identical = sweep.assert_results_identical


class TestShardedEquivalence:
    def test_one_device_mesh_matches_vmap(self):
        """devices=1 runs the full shard_map machinery on a 1-device mesh;
        must be indistinguishable from the plain vmap path (runs in the
        tier-1 suite, no faked devices needed)."""
        spec = _spec()
        _assert_identical(sweep.run_sweep(spec), sweep.run_sweep(spec, devices=1))

    @multi_device
    def test_even_grid(self):
        """Grid divides the device count: no padding."""
        spec = _spec(seeds=(0, 1))  # 4 runs per policy group
        _assert_identical(sweep.run_sweep(spec), sweep.run_sweep(spec, devices=2))

    @multi_device
    def test_uneven_grid_forces_padding(self):
        """3 runs per group on 2 devices: one dummy pad, dropped on host."""
        spec = _spec(initial_pe=(166,), seeds=(0, 1, 2))
        _assert_identical(sweep.run_sweep(spec), sweep.run_sweep(spec, devices=2))

    @multi_device
    def test_grid_smaller_than_device_count(self):
        """1 run per group on every visible device: all but one lane is pad."""
        spec = _spec(initial_pe=(500,), seeds=(0,))
        _assert_identical(
            sweep.run_sweep(spec), sweep.run_sweep(spec, devices="all")
        )

    @multi_device
    def test_open_loop_arrival_scale_axis(self):
        """The open-loop engine (arrival_ms + RunKnobs.arrival_scale) shards
        identically: queueing telemetry is per-run state, no cross-lane."""
        spec = _spec(
            scenario="hammer_openloop",
            policies=(geometry.RARO,),
            initial_pe=(500,),
            arrival_scale=(0.5, 1.0, 4.0),
            scenario_kw=(("rate_iops", 20_000.0),),
        )
        res = sweep.run_sweep(spec)
        _assert_identical(res, sweep.run_sweep(spec, devices=2))
        assert any(r["read_queue_delay_us"] > 0 for r in res)

    def test_too_many_devices_clamps_with_warning(self):
        # over-asking devices clamps to the visible count (with a warning)
        # instead of aborting the sweep — results are unchanged
        with pytest.warns(UserWarning, match="clamping"):
            res = sweep.run_sweep(_spec(), devices=N_DEV + 1)
        _assert_identical(res, sweep.run_sweep(_spec(), devices=N_DEV))

    def test_zero_devices_raises(self):
        with pytest.raises(ValueError, match="devices"):
            sweep.run_sweep(_spec(), devices=0)

    @pytest.mark.parametrize("devices", [None, 1], ids=["vmap", "shard_map"])
    def test_place_span_only_on_mesh_path(self, devices, monkeypatch):
        """The sharded placement of the stacked inputs runs in its own
        ``repro.sweep.place`` span inside ``repro.sweep.dispatch``; the vmap
        path, which lets jit move its inputs, opens none."""
        opened, stack = [], []

        class Span:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                opened.append((self.name, tuple(stack)))
                stack.append(self.name)

            def __exit__(self, *exc):
                stack.pop()

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
        sweep.run_sweep(_spec(), devices=devices)
        place = [outer for name, outer in opened if name == "repro.sweep.place"]
        dispatches = sum(name == "repro.sweep.dispatch" for name, _ in opened)
        assert dispatches == len(_spec().policies)
        if devices is None:
            assert place == []
        else:
            assert place == [("repro.sweep.dispatch",)] * dispatches


# --------------- watermark-gated passes under vmap (ftl.gated) ---------------

# a hot-spot trace on a tightly filled drive: lanes at high wear with the
# paper's R1 convert enough to push the free pool under reclaim's and GC's
# watermarks; lanes whose R1 no read reaches never do
GATE_CHUNKS = 16
GATE_BASE = geometry.tiny_config(policy=geometry.RARO, n_logical=2560,
                                 gc_free_threshold=10)


def _gate_spec(obs_level="off"):
    return sweep.SweepSpec(
        scenario="hotspot_shift", n_requests=GATE_CHUNKS * GATE_BASE.chunk,
        policies=(geometry.RARO,), initial_pe=(833, 2500), seeds=(0,),
        r1=(1, 64), base=dataclasses.replace(GATE_BASE, obs_level=obs_level),
    )


def _lane_cfg(spec, run):
    return dataclasses.replace(spec.base, initial_pe=run.initial_pe, r1=run.r1,
                               r2_override=run.r2_override)


def _own_run(spec, run):
    """One lane alone, through the single-drive path (``engine.run``)."""
    tr = registry.build(spec.scenario, spec.base, spec.n_requests, seed=run.seed)
    s, _ = engine.run(_lane_cfg(spec, run), tr)
    return jax.device_get(s)


class TestGatedPassesUnderVmap:
    def test_batch_gate_opens_only_where_some_lane_needs_it(self):
        """Lanes that disagree: one lane's reclaim and GC gates open, another
        lane's never do. The batch runs a pass in a chunk when any lane needs
        it, and every lane's final state still equals its own run's."""
        spec = _gate_spec("counters")
        runs = sweep.expand(spec)
        tr = registry.build(spec.scenario, spec.base, spec.n_requests, seed=0)
        lpns = np.stack([tr["lpn"]] * len(runs)).astype(np.int32)
        ops = np.stack([tr["op"]] * len(runs)).astype(np.int32)

        def knob(name):
            return np.asarray([getattr(r, name) for r in runs], np.int32)

        knobs = policies.RunKnobs(r1=knob("r1"), r2_override=knob("r2_override"),
                                  initial_pe=knob("initial_pe"))
        states = jax.device_get(
            sweep._sweep_jit(spec.base, lpns, ops, True, knobs))
        own = [_own_run(spec, r) for r in runs]
        own_gates = np.stack([np.asarray(s.obs_gated_chunks) for s in own])
        batch_gates = np.asarray(states.obs_gated_chunks)
        # some lane ran both passes, some lane neither
        assert (own_gates > 0).all(axis=1).any(), own_gates
        assert (own_gates == 0).all(axis=1).any(), own_gates
        # one count for the whole batch: open in some chunks, shut in others
        assert (batch_gates == batch_gates[0]).all(), batch_gates
        assert (batch_gates[0] >= own_gates.max(axis=0)).all()
        assert (batch_gates[0] < GATE_CHUNKS).all(), batch_gates
        for i, s in enumerate(own):
            for name, a, b in zip(s._fields, s, sweep._take_run(states, i)):
                if name != "obs_gated_chunks":
                    np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b), err_msg=f"lane {i}/{name}")

    @pytest.mark.parametrize("devices", [None, 1], ids=["vmap", "shard_map"])
    def test_lanes_match_their_own_runs(self, devices):
        """Every lane's summary equals its own ``engine.run`` bit for bit, on
        the vmapped path and on the one-device ``shard_map`` path."""
        spec = _gate_spec()
        res = sweep.run_sweep(spec, devices=devices)
        want = []
        for run, r in zip(sweep.expand(spec), res):
            m = engine.summarize(_own_run(spec, run), _lane_cfg(spec, run))
            m["run"] = r["run"]
            want.append(m)
        _assert_identical(res, want)


# ------------- conversions under vmap (ftl.maybe_migrate_pages) -------------

# a drive whose per-slot and per-logical leaves fold 128 wide (4,096 slots,
# 3,072 logical pages) and whose conversions need four free blocks. In some
# chunk the lanes disagree: the worn lane with the paper's R1 and R2 sits
# below the free-block guard, the lane with the paper's R1 and an R2 no read
# reaches converts, and the lane whose R1 and R2 no read reaches has no page
# to convert
MIG_CHUNKS = 24
MIG_BASE = geometry.tiny_config(policy=geometry.RARO, n_logical=3072)
MIG_VARIANTS = {
    "plain": {},
    # uncorrectable migration reads, rebuilds and the obs event ring
    "faults_obs": dict(read_fail_rate=0.02, max_read_retries=3,
                       parity_rebuild=True, obs_level="full"),
}


def _mig_spec(variant):
    return sweep.SweepSpec(
        scenario="hotspot_shift", n_requests=MIG_CHUNKS * MIG_BASE.chunk,
        policies=(geometry.RARO,), initial_pe=(2500,), seeds=(0,),
        r1=(1, 64), r2_override=(-1, 64),
        base=dataclasses.replace(MIG_BASE, **MIG_VARIANTS[variant]),
    )


class TestMigrationUnderVmap:
    @pytest.mark.parametrize("variant", sorted(MIG_VARIANTS))
    @pytest.mark.parametrize("devices", [None, 1], ids=["vmap", "shard_map"])
    def test_lanes_that_disagree_match_their_own_runs(self, devices, variant):
        """Within one chunk some lanes convert pages, one has none to
        convert and one sits below the free-block guard; every lane's
        summary still equals its own ``engine.run`` bit for bit."""
        spec = _mig_spec(variant)
        runs = sweep.expand(spec)
        guard = ftl._dest_unroll(spec.base, spec.base.migrate_pages_per_chunk) + 2
        below, moved, want = {}, {}, []
        for run in runs:
            tr = registry.build(spec.scenario, spec.base, spec.n_requests,
                                seed=run.seed)
            s, m = engine.run(_lane_cfg(spec, run), tr)
            knobs = (run.r1, run.r2_override)
            # chunk k starts from chunk k - 1's free pool
            below[knobs] = np.asarray(m.free_blocks)[:-1] < guard
            moved[knobs] = np.diff(np.asarray(m.migrated)) > 0
            want.append(engine.summarize(jax.device_get(s), _lane_cfg(spec, run)))
        assert not moved[64, 64].any()
        assert (below[1, -1] & ~moved[1, -1] & moved[1, 64]).any(), (below, moved)
        res = sweep.run_sweep(spec, devices=devices)
        for m, r in zip(want, res):
            m["run"] = r["run"]
            # a gated pass counts its chunks once for the whole batch
            for d in (m, r):
                d.pop("gated_pass_chunks", None)
        _assert_identical(res, want)

    def test_sweep_states_hold_the_ftl_invariants(self):
        """Each lane's final state of that grid, unfolded, keeps the FTL's
        mapping and accounting invariants."""
        spec = _mig_spec("plain")
        runs = sweep.expand(spec)
        tr = registry.build(spec.scenario, spec.base, spec.n_requests, seed=0)
        lpns = np.stack([tr["lpn"]] * len(runs)).astype(np.int32)
        ops = np.stack([tr["op"]] * len(runs)).astype(np.int32)

        def knob(name):
            return np.asarray([getattr(r, name) for r in runs], np.int32)

        knobs = policies.RunKnobs(r1=knob("r1"), r2_override=knob("r2_override"),
                                  initial_pe=knob("initial_pe"))
        states = jax.device_get(
            sweep._sweep_jit(spec.base, lpns, ops, True, knobs))
        for i, run in enumerate(runs):
            st.check_invariants(sweep._take_run(states, i), _lane_cfg(spec, run),
                                where=run.tag())


@pytest.mark.parametrize("cfg", [MIG_BASE, geometry.SimConfig()],
                         ids=["tiny", "table3"])
def test_fold_lanes_round_trip(cfg):
    """``fold_lanes`` folds exactly the per-slot and per-logical leaves 128
    wide, and ``unfold_lanes`` gives every leaf back its shape, dtype and
    values."""
    s = jax.eval_shape(lambda: st.init_state(cfg))
    folded = jax.eval_shape(st.fold_lanes, s)
    wide = {n for n, a, b in zip(s._fields, s, folded) if a.shape != b.shape}
    assert wide == {"l2p", "p2l", "page_write_ms", "heat"}
    for n in wide:
        assert getattr(folded, n).shape == (getattr(s, n).shape[0] // 128, 128)
    back = jax.eval_shape(lambda f: st.unfold_lanes(f, s), folded)
    for n, a, b in zip(s._fields, s, back):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), n
    if cfg is MIG_BASE:
        s0 = st.init_state(cfg)
        s1 = st.unfold_lanes(st.fold_lanes(s0), s0)
        for n, a, b in zip(s0._fields, s0, s1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=n)


def _eqns(jaxpr, conds=()):
    """Every equation of a jaxpr and its sub-jaxprs, with the predicate
    avals of the ``cond``s around it."""
    for e in jaxpr.eqns:
        yield e, conds
        inner = conds + ((e.invars[0].aval,) if e.primitive.name == "cond" else ())
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


LANE_REDUCTIONS = {"psum", "pmax"}


class TestGatedPassStructure:
    def test_sweep_reclaim_gather_sits_under_a_scalar_cond(self):
        """The reclaim pass's per-slot heat gather over every lane runs inside
        a ``cond`` on a scalar predicate (the batch gate), not as one side of
        a per-lane select."""
        cfg = GATE_BASE
        r = 3
        x = jax.ShapeDtypeStruct((r, 2, cfg.chunk), jnp.int32)
        k = jax.ShapeDtypeStruct((r,), jnp.int32)
        knobs = policies.RunKnobs(r1=k, r2_override=k, initial_pe=k)
        jaxpr = jax.make_jaxpr(
            lambda l, o, kn: sweep._sweep_jit(cfg, l, o, True, kn))(x, x, knobs)
        gathers = [conds for e, conds in _eqns(jaxpr.jaxpr)
                   if e.primitive.name == "gather"
                   and e.outvars[0].aval.shape == (r, cfg.n_slots)
                   and e.outvars[0].aval.dtype == jnp.float32]
        assert gathers
        for conds in gathers:
            assert any(a.shape == () for a in conds), conds
        assert any(e.primitive.name in LANE_REDUCTIONS
                   for e, _ in _eqns(jaxpr.jaxpr))

    @pytest.mark.parametrize("has_writes", [False, True])
    def test_single_run_traces_no_lane_reduction(self, has_writes):
        cfg = GATE_BASE
        x = jax.ShapeDtypeStruct((2, cfg.chunk), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda l, o: engine._run_jit(cfg, l, o, has_writes))(x, x)
        assert not [e for e, _ in _eqns(jaxpr.jaxpr)
                    if e.primitive.name in LANE_REDUCTIONS]
