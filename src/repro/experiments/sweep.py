"""Device-sharded sweep orchestration (DESIGN.md §7.3).

Runs a (policy x wear x seed x knob x scenario) grid through the simulator
with one compiled program per *static* group. The split:

  batched along a stacked run axis (one jit per group):
      seeds / scenario draws (different traces, same shape),
      ``r1``, ``r2_override``, ``initial_pe``  (RunKnobs — traced scalars)
  looped in Python (change trace shapes or compiled branches):
      policy, geometry/SimConfig, scenario name, request count

The stacked run axis executes either on a single device through ``jax.vmap``
(``devices=None``, the original path) or sharded across a 1-D device mesh
via ``shard_map`` (``devices=N`` / a device list): each device runs the
identical vmapped program on its slice of the runs, so the results match the
single-device path bit for bit. Grids that don't divide the device count are
padded with dummy replicas of the last run; the pads are dropped on the host
and never summarized.

Dispatch is asynchronous: every policy group is traced/compiled and enqueued
before any result is awaited, so group k+1's compile overlaps group k's
execution. Summarization happens afterwards, off the dispatch critical path
— one batched ``jax.device_get`` of the stacked final states per group, then
a host-side ``engine.summarize`` loop over numpy leaves.

Results are per-run dicts (engine.summarize + run metadata) and optional
``BENCH_*.json`` artifacts in the harness's ``name,value,unit`` row format.
"""

from __future__ import annotations

import itertools
import json
import time
import warnings
from dataclasses import dataclass, field, replace
from functools import partial
from pathlib import Path

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import reclaim
from repro.experiments import registry
from repro.ssdsim import engine, geometry, metrics_schema, policies
from repro.ssdsim import state as st


@dataclass(frozen=True)
class SweepSpec:
    """A full experiment grid (cross product of every axis)."""

    scenario: str = "zipf"
    n_requests: int = 20_000
    policies: tuple[int, ...] = (geometry.BASELINE, geometry.RARO)
    initial_pe: tuple[int, ...] = (166, 833)
    seeds: tuple[int, ...] = (0, 1)
    r1: tuple[int, ...] = (1,)
    r2_override: tuple[int, ...] = (-1,)
    # offered-load multipliers for open-loop scenarios (traces carrying
    # arrival_ms): effective arrival time = trace arrival / scale, so the
    # whole latency-vs-load curve batches through one compiled program.
    # Ignored (with a warning) for closed-loop scenarios.
    arrival_scale: tuple[float, ...] = (1.0,)
    # fault-injection axes (DESIGN.md §2D), batched through RunKnobs like
    # the policy knobs: while every axis sits at its fault-free default the
    # knob fields stay None and no fault ops are traced; any non-default
    # value activates them for the whole grid (a traced rate of exactly 0.0
    # stays bit-identical to the fault-free program, so mixed grids are
    # safe).
    prog_fail_rate: tuple[float, ...] = (0.0,)
    erase_fail_rate: tuple[float, ...] = (0.0,)
    max_read_retries: tuple[int, ...] = (-1,)
    fault_seed: tuple[int, ...] = (0,)
    # wear-coupled reliability axes (DESIGN.md §2D, wear-correlated): ride
    # the fault-knob activation above; their fault-free defaults (rate 0.0,
    # slope 0.0, rebuild off, unbounded spares) trace bit-identically to the
    # flat-rate program, so mixed grids stay safe
    read_fail_rate: tuple[float, ...] = (0.0,)
    fault_wear_slope: tuple[float, ...] = (0.0,)
    parity_rebuild: tuple[bool, ...] = (False,)
    spare_blocks: tuple[int, ...] = (-1,)
    # GC victim-objective axis (DESIGN.md §2E), batched through
    # RunKnobs.gc_objective as integer codes: while the axis sits at its
    # default the knob stays None (no formula-select traced); a mixed axis
    # runs both objectives in one compiled program, with code 0 (min_valid)
    # pinned bit-identical to the knob-free trace.
    gc_objective: tuple[str, ...] = ("min_valid",)
    # forwarded to the scenario builder (e.g. {"theta": 1.2}); tuple-of-items
    # so the spec stays hashable
    scenario_kw: tuple[tuple[str, object], ...] = ()
    base: geometry.SimConfig = field(default_factory=geometry.SimConfig)

    def n_runs(self) -> int:
        return (len(self.policies) * len(self.initial_pe) * len(self.seeds)
                * len(self.r1) * len(self.r2_override)
                * len(self.arrival_scale) * len(self.prog_fail_rate)
                * len(self.erase_fail_rate) * len(self.max_read_retries)
                * len(self.fault_seed) * len(self.read_fail_rate)
                * len(self.fault_wear_slope) * len(self.parity_rebuild)
                * len(self.spare_blocks) * len(self.gc_objective))

    def faults_on(self) -> bool:
        """Any fault axis off its fault-free default -> the grid batches
        fault knobs through RunKnobs (see ``faults.params_for``)."""
        return (self.prog_fail_rate != (0.0,)
                or self.erase_fail_rate != (0.0,)
                or self.max_read_retries != (-1,)
                or self.fault_seed != (0,)
                or self.read_fail_rate != (0.0,)
                or self.fault_wear_slope != (0.0,)
                or self.parity_rebuild != (False,)
                or self.spare_blocks != (-1,))


@dataclass(frozen=True)
class RunSpec:
    """One point of the grid."""

    scenario: str
    policy: int
    initial_pe: int
    seed: int
    r1: int
    r2_override: int
    arrival_scale: float = 1.0
    prog_fail_rate: float = 0.0
    erase_fail_rate: float = 0.0
    max_read_retries: int = -1
    fault_seed: int = 0
    read_fail_rate: float = 0.0
    fault_wear_slope: float = 0.0
    parity_rebuild: bool = False
    spare_blocks: int = -1
    gc_objective: str = "min_valid"

    def tag(self) -> str:
        parts = [
            self.scenario,
            geometry.POLICY_NAMES[self.policy],
            f"pe{self.initial_pe}",
            f"seed{self.seed}",
        ]
        if self.r1 != 1:
            parts.append(f"r1_{self.r1}")
        if self.r2_override >= 0:
            parts.append(f"r2_{self.r2_override}")
        if self.arrival_scale != 1.0:
            parts.append(f"load{self.arrival_scale:g}")
        if self.prog_fail_rate != 0.0:
            parts.append(f"pfail{self.prog_fail_rate:g}")
        if self.erase_fail_rate != 0.0:
            parts.append(f"efail{self.erase_fail_rate:g}")
        if self.max_read_retries >= 0:
            parts.append(f"mrr{self.max_read_retries}")
        if self.fault_seed != 0:
            parts.append(f"fseed{self.fault_seed}")
        if self.read_fail_rate != 0.0:
            parts.append(f"rfail{self.read_fail_rate:g}")
        if self.fault_wear_slope != 0.0:
            parts.append(f"wear{self.fault_wear_slope:g}")
        if self.parity_rebuild:
            parts.append("parity")
        if self.spare_blocks >= 0:
            parts.append(f"spares{self.spare_blocks}")
        if self.gc_objective != "min_valid":
            parts.append(f"gc_{self.gc_objective}")
        return "_".join(parts)


def expand(spec: SweepSpec) -> list[RunSpec]:
    return [
        RunSpec(spec.scenario, pol, pe, seed, r1, r2, scale, pf, ef, mrr, fs,
                rf, ws, pr, sb, gco)
        for pol, pe, seed, r1, r2, scale, pf, ef, mrr, fs, rf, ws, pr, sb, gco
        in itertools.product(
            spec.policies, spec.initial_pe, spec.seeds, spec.r1,
            spec.r2_override, spec.arrival_scale, spec.prog_fail_rate,
            spec.erase_fail_rate, spec.max_read_retries, spec.fault_seed,
            spec.read_fail_rate, spec.fault_wear_slope, spec.parity_rebuild,
            spec.spare_blocks, spec.gc_objective
        )
    ]


def _run_batch(cfg: geometry.SimConfig, has_writes: bool, lpns, ops,
               knobs: policies.RunKnobs, arrival_ms=None):
    """Vmapped body shared by both executors; everything dynamic rides the
    stacked run axis.

    ``lpns``/``ops``: (R, n_chunks, chunk); ``knobs``: (R,) fields;
    ``arrival_ms``: (R, n_chunks, chunk) f32 or None (closed loop). Returns
    the stacked final state pytree (leading run axis on every leaf). The
    vmap axis is named ``_LANE_AXIS`` so the reclaim and GC passes run in a
    chunk only when some run of the batch needs them (``ftl.gated``).
    """

    def one(lpns_i, ops_i, knobs_i, arr_i=None):
        s0 = st.init_state(cfg, initial_pe=knobs_i.initial_pe,
                           spare_blocks=knobs_i.spare_blocks)

        def body(s, x):
            return engine.step_chunk(s, x, cfg, has_writes, knobs_i,
                                     lane_axis=_LANE_AXIS)

        xs = (lpns_i, ops_i) if arr_i is None else (lpns_i, ops_i, arr_i)
        s, _ = lax.scan(body, s0, xs)
        return s

    batched = jax.vmap(one, axis_name=_LANE_AXIS)
    if arrival_ms is None:
        return batched(lpns, ops, knobs)
    return batched(lpns, ops, knobs, arrival_ms)


@partial(jax.jit, static_argnums=(0, 3))
def _sweep_jit(cfg: geometry.SimConfig, lpns, ops, has_writes: bool,
               knobs: policies.RunKnobs, arrival_ms=None):
    """Single-device executor: the whole run axis on one ``jax.vmap``."""
    return _run_batch(cfg, has_writes, lpns, ops, knobs, arrival_ms)


@partial(jax.jit, static_argnums=(0, 3, 6))
def _sweep_sharded_jit(cfg: geometry.SimConfig, lpns, ops, has_writes: bool,
                       knobs: policies.RunKnobs, arrival_ms, mesh: Mesh):
    """Sharded executor: the run axis (a multiple of the mesh size — the
    caller pads) is split across ``mesh``'s devices via ``shard_map``; each
    device runs the identical vmapped program on its local runs, so results
    are bitwise identical to the single-device path. No collectives across
    devices — runs are independent, making the shard axis embarrassingly
    parallel; the gated passes reduce over each device's own lanes only."""
    spec = P(_MESH_AXIS)
    # check_vma=False: every value is per-device (no collectives, nothing
    # replicated), so there is nothing for the varying-axes check to prove
    if arrival_ms is None:
        fn = jax.shard_map(
            lambda l, o, k: _run_batch(cfg, has_writes, l, o, k),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(lpns, ops, knobs)
    fn = jax.shard_map(
        lambda l, o, k, a: _run_batch(cfg, has_writes, l, o, k, a),
        mesh=mesh, in_specs=(spec, spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(lpns, ops, knobs, arrival_ms)


_MESH_AXIS = "runs"
# the vmap axis over one device's runs: the watermark-gated passes reduce
# their predicates over it (``ftl.gated``), never over the mesh
_LANE_AXIS = "lanes"


def resolve_devices(devices):
    """Normalize the ``devices`` argument to a tuple of jax devices (or None
    for the single-device vmap path). Accepts an int count, ``"all"``, an
    explicit device sequence, or a numeric string — so CLI entry points can
    forward their ``--devices`` argument verbatim (and validate it early via
    this function without paying for trace building first)."""
    if devices is None:
        return None
    if devices == "all":
        return tuple(jax.devices())
    if isinstance(devices, str):
        devices = int(devices)
    if isinstance(devices, int):
        avail = jax.devices()
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if devices > len(avail):
            # clamp-and-warn rather than abort: an over-asked sweep on a
            # smaller host still runs (bit-identical results, just less
            # parallel), which is what a batch harness wants
            warnings.warn(
                f"requested {devices} devices but only {len(avail)} visible; "
                f"clamping to {len(avail)} "
                f"(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"fakes N host devices)",
                stacklevel=2,
            )
            devices = len(avail)
        return tuple(avail[:devices])
    return tuple(devices)


def _take_run(stacked, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def assert_results_identical(a, b):
    """Assert two ``run_sweep`` result lists are the same runs in the same
    order with every summarize value exactly equal — the sharded-executor
    guarantee. One checker shared by the equivalence tests and the scaling
    benchmark's self-check; raises explicitly (not bare ``assert``) so the
    benchmark keeps its guarantee under ``python -O``."""
    if len(a) != len(b):
        raise AssertionError(f"{len(a)} runs vs {len(b)}")
    for ra, rb in zip(a, b):
        if ra["run"] != rb["run"]:
            raise AssertionError(f"run order diverged: {ra['run']} vs {rb['run']}")
        if ra.keys() != rb.keys():
            raise AssertionError(f"metric keys diverged for {ra['run']['tag']}")
        for k in ra:
            if k != "run":
                np.testing.assert_array_equal(
                    np.asarray(ra[k]), np.asarray(rb[k]),
                    err_msg=f"{ra['run']['tag']}/{k}",
                )


def _group_ckpt_path(resume_dir, spec: SweepSpec, pol: int) -> Path:
    return (Path(resume_dir)
            / f"ckpt_{spec.scenario}_{geometry.POLICY_NAMES[pol]}.json")


def _load_group_checkpoint(path: Path, expect_tags, spec: SweepSpec,
                           threads: int):
    """Completed-group results from a prior run, or None when absent/stale.

    A checkpoint is only honored when its run tags (which encode every knob
    of every run in order), request count and thread model match — anything
    else is a different experiment and must re-run."""
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        return None
    if (doc.get("tags") != expect_tags
            or doc.get("n_requests") != spec.n_requests
            or doc.get("threads") != threads):
        return None
    return doc["results"]


def _write_group_checkpoint(path: Path, expect_tags, spec: SweepSpec,
                            threads: int, group_results) -> None:
    """Persist one completed policy group. Write-then-rename so a kill
    mid-write never leaves a truncated checkpoint; JSON float round-trips
    are exact in Python 3, so resumed results satisfy
    :func:`assert_results_identical` against an uninterrupted run."""
    path.parent.mkdir(parents=True, exist_ok=True)
    doc = dict(tags=expect_tags, n_requests=spec.n_requests, threads=threads,
               results=group_results)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(doc))
    tmp.replace(path)


def _retry_delays(max_retries: int, backoff_s: float):
    return [backoff_s * (2 ** i) for i in range(max_retries)]


def run_sweep(spec: SweepSpec, threads: int = 4, verbose: bool = False,
              devices=None, resume_dir=None, max_retries: int = 2,
              retry_backoff_s: float = 0.5):
    """Execute the grid. Returns one result dict per run: everything from
    ``engine.summarize`` (mean + p50/p95/p99/p999 read latency, IOPS,
    capacity, ...) plus the run's metadata under ``"run"``.

    ``devices`` selects the executor: ``None`` keeps the whole run axis on
    one device (``jax.vmap``); an int N / ``"all"`` / a device sequence
    shards the run axis across those devices (identical results — see
    :func:`_sweep_sharded_jit`). Every policy group is dispatched before any
    result is fetched, so compile and execution overlap across groups.

    Robustness (DESIGN.md §2D): ``resume_dir`` checkpoints each completed
    policy group to disk and deterministically resumes from matching
    checkpoints on a rerun — a killed sweep repeats only the unfinished
    groups and the merged results are identical to an uninterrupted run.
    Device dispatch/fetch failures are retried ``max_retries`` times with
    exponential backoff (``retry_backoff_s`` doubling per attempt); a group
    still failing after that does not lose the rest of the grid — every
    other group completes (and checkpoints) before a ``RuntimeError`` names
    the poisoned groups.
    """
    devs = resolve_devices(devices)  # validate before the trace-build cost
    runs = expand(spec)
    kw = dict(spec.scenario_kw)
    faults_on = spec.faults_on()
    if len(spec.seeds) > 1 and registry.is_seed_invariant(spec.scenario):
        warnings.warn(
            f"scenario {spec.scenario!r} is deterministic w.r.t. seed; "
            f"{len(spec.seeds)} seeds will produce identical runs",
            stacklevel=2,
        )

    # traces depend only on (scenario, seed): build each once, share across
    # policies/knobs
    traces: dict[int, dict] = {}
    with jax.profiler.TraceAnnotation("repro.sweep.build"):
        for seed in spec.seeds:
            traces[seed] = registry.build(
                spec.scenario, spec.base, spec.n_requests, seed=seed, **kw
            )
    has_writes = bool(any((t["op"] == engine.OP_WRITE).any() for t in traces.values()))
    open_loop = all("arrival_ms" in t for t in traces.values())
    if spec.arrival_scale != (1.0,) and not open_loop:
        warnings.warn(
            f"scenario {spec.scenario!r} has no arrival timestamps; the "
            f"arrival_scale axis {spec.arrival_scale} has no effect on "
            "closed-loop runs",
            stacklevel=2,
        )

    mesh = Mesh(np.asarray(devs), (_MESH_AXIS,)) if devs is not None else None
    run_sharding = (
        NamedSharding(mesh, P(_MESH_AXIS)) if mesh is not None else None
    )

    # ---- phase 1: dispatch every policy group (nothing blocks on results;
    # group k+1's trace/compile overlaps group k's execution) ----
    pending = []
    for pol in spec.policies:  # static axis -> one compile each
        group = [r for r in runs if r.policy == pol]
        cfg = replace(spec.base, policy=pol)
        expect_tags = [r.tag() for r in group]
        if resume_dir is not None:
            cached = _load_group_checkpoint(
                _group_ckpt_path(resume_dir, spec, pol), expect_tags, spec,
                threads,
            )
            if cached is not None:
                if verbose:
                    print(f"# sweep group policy={geometry.POLICY_NAMES[pol]}"
                          f": {len(group)} runs resumed from checkpoint",
                          flush=True)
                pending.append((group, cfg, None, None, cached))
                continue

        def _dispatch(group=group, cfg=cfg, pol=pol):
            # pad uneven grids (and grids smaller than the device count)
            # with dummy replicas of the last run so the run axis divides
            # the mesh; the pads are dropped on the host below, never
            # summarized
            with jax.profiler.TraceAnnotation("repro.sweep.stack"):
                n_pad = (-len(group)) % len(devs) if devs is not None else 0
                padded = group + [group[-1]] * n_pad
                # stacked on the host (numpy): the vmap path lets jit move them
                # to the default device as before, the sharded path transfers
                # each array exactly once, straight to its run-sharded layout
                lpns = np.stack([np.asarray(traces[r.seed]["lpn"], np.int32) for r in padded])
                ops = np.stack([np.asarray(traces[r.seed]["op"], np.int32) for r in padded])
                arr = (
                    np.stack([np.asarray(traces[r.seed]["arrival_ms"], np.float32)
                              for r in padded])
                    if open_loop else None
                )
                knobs = policies.RunKnobs(
                    r1=np.asarray([r.r1 for r in padded], np.int32),
                    r2_override=np.asarray([r.r2_override for r in padded], np.int32),
                    initial_pe=np.asarray([r.initial_pe for r in padded], np.int32),
                    arrival_scale=(
                        np.asarray([r.arrival_scale for r in padded], np.float32)
                        if open_loop else None
                    ),
                    prog_fail_rate=(
                        np.asarray([r.prog_fail_rate for r in padded], np.float32)
                        if faults_on else None
                    ),
                    erase_fail_rate=(
                        np.asarray([r.erase_fail_rate for r in padded], np.float32)
                        if faults_on else None
                    ),
                    max_read_retries=(
                        np.asarray([r.max_read_retries for r in padded], np.int32)
                        if faults_on else None
                    ),
                    fault_seed=(
                        np.asarray([r.fault_seed for r in padded], np.int32)
                        if faults_on else None
                    ),
                    read_fail_rate=(
                        np.asarray([r.read_fail_rate for r in padded], np.float32)
                        if faults_on else None
                    ),
                    fault_wear_slope=(
                        np.asarray([r.fault_wear_slope for r in padded],
                                   np.float32)
                        if faults_on else None
                    ),
                    parity_rebuild=(
                        np.asarray([int(r.parity_rebuild) for r in padded],
                                   np.int32)
                        if faults_on else None
                    ),
                    spare_blocks=(
                        np.asarray([r.spare_blocks for r in padded], np.int32)
                        if faults_on else None
                    ),
                    gc_objective=(
                        np.asarray(
                            [reclaim.GC_OBJECTIVE_CODES[r.gc_objective]
                             for r in padded], np.int32)
                        if spec.gc_objective != ("min_valid",) else None
                    ),
                )
            if verbose:
                where = (f"sharded over {len(devs)} devices"
                         f" (+{n_pad} pad)" if devs is not None else "one device")
                print(f"# sweep group policy={geometry.POLICY_NAMES[pol]}: "
                      f"{len(group)} runs in one jit, {where}", flush=True)
            with jax.profiler.TraceAnnotation("repro.sweep.dispatch"):
                if mesh is None:
                    return _sweep_jit(cfg, lpns, ops, has_writes, knobs, arr)
                with jax.profiler.TraceAnnotation("repro.sweep.place"):
                    place = lambda x: jax.device_put(x, run_sharding)  # noqa: E731
                    lpns, ops = place(lpns), place(ops)
                    arr = place(arr) if arr is not None else None
                    knobs = jax.tree_util.tree_map(place, knobs)
                return _sweep_sharded_jit(cfg, lpns, ops, has_writes, knobs,
                                          arr, mesh)

        try:
            states = _dispatch()
        except Exception as e:  # retried with backoff in phase 2
            warnings.warn(
                f"dispatch of sweep group {geometry.POLICY_NAMES[pol]!r} "
                f"failed ({e!r}); will retry",
                stacklevel=2,
            )
            states = None
        pending.append((group, cfg, states, _dispatch, None))

    # ---- phase 2: one batched device->host transfer per group, then
    # summarize on numpy leaves off the dispatch critical path ----
    results = []
    failed = []
    for group, cfg, states, redispatch, cached in pending:
        if cached is not None:
            results.extend(cached)
            continue
        name = geometry.POLICY_NAMES[group[0].policy]
        host = None
        last_err = None
        delays = _retry_delays(max_retries, retry_backoff_s)
        for attempt in range(max_retries + 1):
            try:
                if states is None:  # prior dispatch/fetch failed -> redo
                    states = redispatch()
                with jax.profiler.TraceAnnotation("repro.sweep.fetch"):
                    host = jax.device_get(states)  # blocks on this group only
                break
            except Exception as e:  # one poisoned group must not lose the grid
                last_err = e
                states = None
                if attempt < max_retries:
                    warnings.warn(
                        f"sweep group {name!r} failed ({e!r}); retry "
                        f"{attempt + 1}/{max_retries} in "
                        f"{delays[attempt]:.1f}s",
                        stacklevel=2,
                    )
                    time.sleep(delays[attempt])
        if host is None:
            failed.append((name, last_err))
            continue
        with jax.profiler.TraceAnnotation("repro.sweep.summarize"):
            group_results = []
            for i, r in enumerate(group):  # pads (indices >= len(group)) dropped
                m = engine.summarize(_take_run(host, i), cfg, threads=threads)
                m["run"] = dict(
                    scenario=r.scenario,
                    policy=geometry.POLICY_NAMES[r.policy],
                    initial_pe=r.initial_pe,
                    seed=r.seed,
                    r1=r.r1,
                    r2_override=r.r2_override,
                    arrival_scale=r.arrival_scale,
                    prog_fail_rate=r.prog_fail_rate,
                    erase_fail_rate=r.erase_fail_rate,
                    max_read_retries=r.max_read_retries,
                    fault_seed=r.fault_seed,
                    read_fail_rate=r.read_fail_rate,
                    fault_wear_slope=r.fault_wear_slope,
                    parity_rebuild=r.parity_rebuild,
                    spare_blocks=r.spare_blocks,
                    gc_objective=r.gc_objective,
                    n_requests=spec.n_requests,
                    tag=r.tag(),
                )
                group_results.append(m)
        if resume_dir is not None:
            _write_group_checkpoint(
                _group_ckpt_path(resume_dir, spec, group[0].policy),
                [r.tag() for r in group], spec, threads, group_results,
            )
        results.extend(group_results)
    if failed:
        names = ", ".join(n for n, _ in failed)
        hint = (
            "completed groups were checkpointed to resume_dir and are "
            "reused on rerun" if resume_dir is not None else
            "pass resume_dir= to checkpoint completed groups across reruns"
        )
        raise RuntimeError(
            f"sweep group(s) failed after {max_retries} retries: {names} "
            f"({hint})"
        ) from failed[0][1]
    return results


# --------------------------- result artifacts ------------------------------

# Scalar metric names + units come from the single schema registry
# (ssdsim/metrics_schema.py); the name is kept for backward compatibility.
_ROW_UNITS = metrics_schema.row_units()


def result_rows(res: dict, prefix: str = "sweep"):
    """Flatten one run result into harness-style (name, value, unit) rows."""
    tag = res["run"]["tag"]
    rows = [
        (f"{prefix}/{tag}/{k}", float(res[k]), u)
        for k, u in _ROW_UNITS.items()
        if k in res
    ]
    # per-mode observability readout (present at obs_level="full"):
    # retry share of each mode's p99 tail mass (DESIGN.md §7.4)
    if "tail_retry_share" in res:
        from repro.core import modes
        rows += [
            (f"{prefix}/{tag}/tail_retry_share_{name.lower()}",
             float(v), "fraction")
            for name, v in zip(modes.MODE_NAMES, res["tail_retry_share"])
        ]
    return rows


def _json_safe(v):
    """Summarize values are floats, nested lists, or ndarrays — normalize
    all three to JSON-native types."""
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    return float(v)


def write_artifacts(results, out_dir, prefix: str = "sweep") -> list[Path]:
    """One ``BENCH_<tag>.json`` per run, mirroring the harness CSV rows so
    artifacts and stdout stay diffable against each other."""
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    paths = []
    for res in results:
        doc = {
            "name": f"{prefix}/{res['run']['tag']}",
            "run": res["run"],
            "metrics": {
                k: _json_safe(v) for k, v in res.items() if k != "run"
            },
            "rows": [list(r) for r in result_rows(res, prefix)],
        }
        p = out / f"BENCH_{prefix}_{res['run']['tag']}.json"
        p.write_text(json.dumps(doc, indent=1, sort_keys=True))
        paths.append(p)
    return paths
