"""The benchmark's harness: finds a cell's files by name, builds the cell,
warms it up, measures a window, checks what the window produced against
the plain reference, and assembles the result line.

Everything a cell is made of is found by name under a root directory (the
checkout): ``BENCHMARK.json``, the configuration file it names, the
traffic mix ``bench/traffic/<traffic>.json`` and its generator module
``bench/traffic/<generator>.py``, the entry the configuration drives
``bench/entries/<entry>.py``, the limits ``bench/limits/<cell>.json``, and
each per-layer metric's reader ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import reference, trace_reduce, verify

POOL = 4  # distinct traces (or grids) a cell cycles through in its window


def _read(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find(root: Path, kind: str, name: str):
    """The module ``<root>/bench/<kind>/<name>.py``, loaded once."""
    path = (Path(root) / "bench" / kind / f"{name}.py").resolve()
    key = f"bench._found.{kind}.{name}.{abs(hash(str(path)))}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None or not path.is_file():
            raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    sample: int
    per_layer: list
    root: Path

    def trace(self, n_logical: int, chunk: int, n_requests: int, seed: int) -> dict:
        """One simulated run's packed trace of the cell's traffic mix."""
        gen = find(self.root, "traffic", self.traffic["generator"])
        return gen.build(self.traffic, n_logical, chunk, n_requests, seed)


def find_cell(name: str, root: Path) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    root = Path(root)
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    lim = _read(root / "bench" / "limits" / f"{name}.json")
    return Cell(
        name=name,
        chips=w["chips"],
        config=_read(root / conf["file"]),
        traffic=_read(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=lim["limits"],
        sample=lim["sample"],
        per_layer=[m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])],
        root=root,
    )


def sim_config(config: dict):
    """The program's ``SimConfig`` for a configuration file's ``sim`` group."""
    from repro.core.hotness import HeatConfig
    from repro.ssdsim.geometry import SimConfig

    kw = dict(config["sim"])
    kw["heat"] = HeatConfig(**kw["heat"])
    return SimConfig(**kw)


@dataclass
class Sim:
    """One simulated drive run: its trace seed and wear, its summary, and
    (drive cells, while sampled) its final state on the device."""

    seed: int
    initial_pe: int
    requests: int
    reads: int
    chunks: int
    summary: dict
    state: object = None


def reads_of(trace: dict) -> int:
    """Active reads a packed trace holds."""
    return int(((trace["op"] == 0) & (trace["lpn"] >= 0)).sum())


@dataclass
class Window:
    """What one measured window did. ``slowest`` is the window's slowest
    call of the entry with the host spans inside it, ``gc_s`` the Python
    garbage collector's seconds in the window: where a run's time went when
    it reads far off."""

    seconds: float = 0.0
    sims: int = 0
    chunks: int = 0
    requests: int = 0
    done: list = field(default_factory=list)
    picks: list = field(default_factory=list)
    trace: trace_reduce.Trace | None = None
    calls: int = 0
    median_call_s: float = 0.0
    slowest: dict = field(default_factory=dict)
    gc_s: float = 0.0


@contextlib.contextmanager
def span(name, times: dict | None = None):
    """A host span the profiler records; with ``times``, also adds its
    seconds to ``times[name]``."""
    import jax

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    if times is not None:
        times[name] = times.get(name, 0.0) + time.perf_counter() - t


def make(cell: Cell, seed: int):
    """The runner of the entry the cell's configuration names."""
    return find(cell.root, "entries", cell.config["entry"]).Runner(cell, seed)


def measure(runner, seconds: float, sample: int, seed: int) -> Window:
    """Start runs until ``seconds`` have passed; the last one finishes.

    ``picks`` is a uniform sample of ``sample`` finished runs drawn from
    the seed (reservoir sampling), the only runs whose final state stays on
    the device for the check."""
    rng = np.random.default_rng([seed, 1])
    w = Window()
    calls = []
    gc_t = []

    def on_gc(phase, info):
        if phase == "start":
            gc_t.append(time.perf_counter())
        elif gc_t:
            w.gc_s += time.perf_counter() - gc_t.pop()

    gc.callbacks.append(on_gc)
    try:
        with span(trace_reduce.WINDOW_SPAN):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                t = time.perf_counter()
                sims = runner.one()
                calls.append(time.perf_counter() - t)
                if calls[-1] >= max(calls):
                    w.slowest = {"call": len(calls) - 1, "s": calls[-1],
                                 **runner.times}
                for sim in sims:
                    i = len(w.done)
                    w.done.append(sim)
                    w.sims += 1
                    w.chunks += sim.chunks
                    w.requests += sim.requests
                    if i < sample:
                        w.picks.append(i)
                        continue
                    j = rng.integers(0, i + 1)
                    if j < sample:
                        w.done[w.picks[j]].state = None
                        w.picks[j] = i
                    else:
                        sim.state = None
            w.seconds = time.perf_counter() - t0
    finally:
        gc.callbacks.remove(on_gc)
    w.calls = len(calls)
    w.median_call_s = float(np.median(calls))
    return w


def check(cell: Cell, runner, window: Window):
    """Compare the window's runs with the plain reference.

    Every run's read count is checked against its trace. The window's
    sample of runs is simulated again by the reference and compared
    statistic by statistic; a drive's sampled final state is checked for
    FTL consistency. Returns ``(checks, failed_runs)``: each compared number
    beside its limit, and how many runs failed a check."""
    failed = {i for i, sim in enumerate(window.done)
              if sim.summary["reads"] != sim.reads}
    worst = {"count_gap": float(max(abs(s.summary["reads"] - s.reads)
                                    for s in window.done))}
    # the program's state leaves the device before the reference runs
    states = {i: verify.host_state(window.done[i].state) for i in window.picks
              if window.done[i].state is not None}
    for sim in window.done:
        sim.state = None
    for i in sorted(window.picks):
        sim = window.done[i]
        got = {}
        if i in states:
            got["invariant_faults"] = float(len(
                verify.invariant_faults(states.pop(i), cell.config["sim"])))
        tr = runner.trace_of(sim)
        ref = reference.simulate(cell.config["sim"], tr["lpn"], tr["op"],
                                 initial_pe=sim.initial_pe)
        got.update(verify.gaps(sim.summary, ref))
        print("# reference " + json.dumps({"run": int(i), "seed": sim.seed,
                                           "initial_pe": sim.initial_pe,
                                           **verify.detail(sim.summary, ref)}),
              flush=True)
        if not verify.passes(got, cell.limits):
            failed.add(i)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in worst.items()}
    return checks, len(failed)


def per_layer(cell: Cell, window: Window) -> dict:
    """The cell's per-layer metrics from their readers; a reader that finds
    nothing leaves its metric out."""
    out = {}
    for m in cell.per_layer:
        v = find(cell.root, "metrics", m["name"]).read(window)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(tr: trace_reduce.Trace) -> dict:
    return {"device_ops": trace_reduce.top_ops(tr), "idle_gaps": trace_reduce.idle_gaps(tr)}
