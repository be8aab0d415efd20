"""Device time per ``step_chunk`` phase and idle time per named span, from
a profiler trace of a measured window.

The program names its own work. Each section of ``engine.step_chunk`` runs
under a ``jax.named_scope`` (``PHASES``); XLA keeps the scope in each
operation's ``op_name`` metadata, and the profiler's trace holds each
program's compiled HLO (``op_scopes``). A fusion takes the ``op_name`` of
its root, so a fusion that merges two phases counts for one of them. What
runs outside every scope (the scan loop, its carry and output copies,
``init_state``, copies XLA inserts) is the ``scan`` phase. The host work of
``engine.run``, ``engine.summarize`` and ``sweep.run_sweep`` runs inside
``repro.*`` spans, on the device trace's clock.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

builds, warms and measures one cell's window under the profiler as
``bench/run.py --trace 1`` does, and prints one JSON object: device ms per
simulated chunk of each phase (the phases add up to the busy time per
chunk), host ms per simulated run in the program's spans, the idle seconds
of the window by the innermost span open, and the slowest operations with
their phases. It needs a TPU.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402

PHASES = ("step_read", "step_heat", "step_write", "step_convert", "step_reclaim",
          "step_gc", "step_account")
SCAN = "scan"  # operations outside every phase scope
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PROGRAM_PREFIX = "repro."
# the program's host spans read as dispatch (drive runs, grid sweeps) and as
# summary; each lane's repro.summarize nests in repro.sweep.summarize
DISPATCH_SPANS = ("repro.run.put", "repro.run.dispatch", "repro.sweep.build",
                  "repro.sweep.stack", "repro.sweep.dispatch")
SUMMARIZE_SPAN = "repro.summarize"


@dataclass
class PhaseTrace(trace_reduce.Trace):
    """A ``trace_reduce.Trace`` that also holds the program's host spans
    (``repro.*``) and the phase of each device operation name."""

    program_spans: list[tuple[float, float, str]] = field(default_factory=list)
    scopes: dict[str, str] = field(default_factory=dict)


def phase_of(op_path: str) -> str:
    """The phase of an ``op_name`` path such as
    ``jit(_run_jit)/while/body/closed_call/step_read/gather``: its first
    component that names a phase, else ``SCAN``."""
    return next((p for p in op_path.split("/") if p in PHASES), SCAN)


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a protobuf message held
    in ``buf`` (a memoryview): an int, or a view of the bytes of a
    length-delimited field, which is not copied."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:  # fixed 64 or 32 bits
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        yield key >> 3, value


def _map_values(fields, number: int):
    """The values of a protobuf map field (entries: key 1, value 2)."""
    for n, entry in fields:
        if n == number:
            yield dict(_fields(entry)).get(2, memoryview(b""))


def _str(fields, number: int) -> str:
    return next((bytes(v).decode() for n, v in fields if n == number), "")


def op_scopes(path: Path) -> dict[str, str]:
    """The phase of each operation name of the programs an ``.xplane.pb``
    holds.

    The trace carries each program's compiled HLO (the ``HLO_STAT`` stat of
    its entry in the ``METADATA_PLANE``), with the ``op_name`` of every
    instruction. (The ``tf_op`` stat of the device events' metadata holds
    the same path, but not for a ``conditional`` or a ``while``, and
    ``jax.profiler.ProfileData`` shows only the events' own stats.) So this
    reads the protobuf fields straight from the file: ``XSpace.planes`` 1;
    ``XPlane`` name 2, ``event_metadata`` 4, ``stat_metadata`` 5;
    ``XEventMetadata`` stats 5; ``XStat`` metadata id 1, bytes 6;
    ``HloProto.hlo_module`` 1; ``HloModuleProto.computations`` 3;
    ``HloComputationProto.instructions`` 2; ``HloInstructionProto`` name 1,
    metadata 7; ``OpMetadata.op_name`` 2. A name that two programs share
    keeps the phase it has in one of them."""
    scopes: dict[str, str] = {}
    for num, plane in _fields(memoryview(Path(path).read_bytes())):
        fields = list(_fields(plane)) if num == 1 else []
        if _str(fields, 2) != METADATA_PLANE:
            continue
        stat_names = {}
        for sm in _map_values(fields, 5):
            sm = list(_fields(sm))
            stat_names[next((v for n, v in sm if n == 1), 0)] = _str(sm, 2)
        for em in _map_values(fields, 4):
            for n, stat in _fields(em):
                stat = dict(_fields(stat)) if n == 5 else {}
                if stat_names.get(stat.get(1)) != HLO_STAT or 6 not in stat:
                    continue
                module = dict(_fields(stat[6])).get(1, memoryview(b""))
                for comp in (v for n, v in _fields(module) if n == 3):
                    for ins in (v for n, v in _fields(comp) if n == 2):
                        ins = list(_fields(ins))
                        meta = next((list(_fields(v)) for n, v in ins if n == 7), [])
                        name, phase = _str(ins, 1), phase_of(_str(meta, 2))
                        if scopes.get(name, SCAN) == SCAN:
                            scopes[name] = phase
    return scopes


def load(path: Path) -> PhaseTrace:
    """Read an ``.xplane.pb`` as ``trace_reduce.load`` does, keeping the
    program's spans too and the phase of each operation name."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    tr = PhaseTrace(scopes=op_scopes(path))
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace_reduce.OPS_LINE:
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, trace_reduce.op_name(e.name)) for e in line.events)
            elif plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith(trace_reduce.SPAN_PREFIX):
                        tr.spans.append((e.start_ns, e.end_ns, e.name))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        tr.program_spans.append((e.start_ns, e.end_ns, e.name))
    return tr


def phase_s(tr: PhaseTrace) -> dict[str, float]:
    """Device seconds of each phase's operations inside the window, less
    the time of operations nested in them, summed over chips. The phases
    add up to the chips' busy time where the operations nest."""
    lo, hi = tr.window
    out = dict.fromkeys((*PHASES, SCAN), 0.0)
    for ops in tr.ops.values():
        for name, t in trace_reduce.self_times(ops, lo, hi).items():
            out[tr.scopes.get(name, SCAN)] += t / 1e9
    return out


def _innermost(spans, t: float) -> str | None:
    open_ = [s for s in spans if s[0] <= t < s[1]]
    return min(open_, key=lambda s: s[1] - s[0])[2] if open_ else None


def _owners(tr: PhaseTrace, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """The window cut at every span edge, each piece owned by the innermost
    program span open over it, else the innermost benchmark span."""
    edges = {t for s, e, _ in tr.spans + tr.program_spans for t in (s, e) if lo < t < hi}
    cuts = sorted({lo, hi, *edges})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        owner = (_innermost(tr.program_spans, mid) or _innermost(tr.spans, mid)
                 or "no span")
        if pieces and pieces[-1][2] == owner:
            pieces[-1] = (pieces[-1][0], b, owner)
        else:
            pieces.append((a, b, owner))
    return pieces


def idle_by_span(tr: PhaseTrace) -> dict[str, float]:
    """Seconds of the window in which a chip ran no operation, mean over
    chips, each given to the innermost span open then (program spans
    first). They add up to the idle share times the window."""
    lo, hi = tr.window
    pieces = _owners(tr, lo, hi)
    out: dict[str, float] = {}
    for ops in tr.ops.values():
        idle, t = [], lo
        for s, e in trace_reduce.busy_intervals(ops, lo, hi):
            if s > t:
                idle.append((t, s))
            t = e
        if hi > t:
            idle.append((t, hi))
        j = 0
        for a, b in idle:
            while pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                pa, pb, owner = pieces[k]
                out[owner] = out.get(owner, 0.0) + min(b, pb) - max(a, pa)
                k += 1
    chips = max(len(tr.ops), 1)
    return {name: ns / chips / 1e9 for name, ns in sorted(out.items(), key=lambda kv: -kv[1])}


def program_span_s(tr: PhaseTrace, names) -> float:
    """Total seconds of the program's spans with one of ``names``."""
    return sum(e - s for s, e, name in tr.program_spans if name in names) / 1e9


def reduce(tr: PhaseTrace, chunks: int, sims: int) -> dict:
    """The per-phase and per-span numbers of one window that simulated
    ``chunks`` chunks in ``sims`` runs (lanes of a grid counted)."""
    busy = trace_reduce.busy_s(tr)
    per_phase = phase_s(tr)
    lo, hi = tr.window
    top = trace_reduce.top_ops(tr)
    return {
        "device_ms_per_chunk": 1000.0 * sum(busy.values()) / chunks,
        "ms_per_chunk": {p.removeprefix("step_"): 1000.0 * s / chunks
                         for p, s in per_phase.items()},
        "dispatch_ms_per_sim": 1000.0 * program_span_s(tr, DISPATCH_SPANS) / sims,
        "summarize_ms_per_sim": 1000.0 * program_span_s(tr, (SUMMARIZE_SPAN,)) / sims,
        "idle_pct": trace_reduce.idle_pct(tr),
        "idle_by_span": idle_by_span(tr),
        "window_s": (hi - lo) / 1e9,
        "top_ops": [[name, s, tr.scopes.get(name, SCAN)] for name, s in top],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--root", type=Path, default=ROOT,
                    help="checkout whose BENCHMARK.json and bench/ files build the cell")
    ap.add_argument("--keep", type=Path, default=None,
                    help="copy the window's .xplane.pb here")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.find_cell(args.workload, args.root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the persistent cache's key leaves op metadata out by default, so a
    # program loaded from it keeps the op_name paths (and phases) of the
    # build that wrote the entry; keyed on them, a changed scope compiles anew
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"phases: needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    runner = harness.make(cell, args.seed)
    runner.warm()
    setup_s = time.perf_counter() - T0
    trace_dir = ROOT / "bench" / ".trace" / f"phases-{os.getpid()}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    window = harness.measure(runner, args.seconds, cell.sample, args.seed)
    t = time.perf_counter()
    jax.profiler.stop_trace()
    stop_s = time.perf_counter() - t
    path = trace_reduce.find_xplane(trace_dir)
    if args.keep:
        args.keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, args.keep / path.name)
    t = time.perf_counter()
    trace_reduce.load(path)
    plain_load_s = time.perf_counter() - t
    t = time.perf_counter()
    tr = load(path)
    load_s = time.perf_counter() - t
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = {"workload": cell.name, "seed": args.seed, "device": devices[0].device_kind,
           "setup_s": setup_s, "stop_s": stop_s, "plain_load_s": plain_load_s,
           "load_s": load_s, "ops": sum(len(o) for o in tr.ops.values()),
           "sims": window.sims, "chunks": window.chunks,
           **reduce(tr, window.chunks, window.sims)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
