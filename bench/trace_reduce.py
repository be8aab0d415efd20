"""Reduction of a profiler trace of the measured window to device numbers.

The trace holds the device's operations (one plane per chip, line
``XLA Ops``) and the benchmark's own host spans (``bench.*``), on one
clock. From them: the busy time of each chip (the union of its operations'
intervals inside the window), the idle share, the operations that took most
time, and the longest idle gaps, each named by the host span it falls in.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    """Intervals in nanoseconds on the trace's clock: ``ops[chip]`` are
    ``(start, end, name)`` of device operations, ``spans`` the benchmark's
    host spans."""

    ops: dict[int, list[tuple[float, float, str]]] = field(default_factory=dict)
    spans: list[tuple[float, float, str]] = field(default_factory=list)

    @property
    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[2] == WINDOW_SPAN]
        if not w:
            raise ValueError(f"trace holds no {WINDOW_SPAN} span")
        return w[0][0], w[0][1]


def load(path: Path) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.end_ns, op_name(e.name)) for e in line.events)
            elif plane.name.startswith("/host"):
                tr.spans.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return tr


def op_name(hlo: str) -> str:
    """``fusion.716`` from the trace's ``%fusion.716 = f32[1024] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def busy_intervals(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the ``(start, end, ...)`` intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e, *_ in sorted(ops):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(tr: Trace) -> dict[int, float]:
    """Seconds in which an operation ran, per chip, inside the window."""
    lo, hi = tr.window
    return {chip: sum(e - s for s, e in busy_intervals(ops, lo, hi)) / 1e9
            for chip, ops in tr.ops.items()}


def window_s(tr: Trace) -> float:
    lo, hi = tr.window
    return (hi - lo) / 1e9


def idle_pct(tr: Trace) -> float | None:
    """Share of the window in which no operation ran, mean over chips."""
    busy = busy_s(tr)
    if not busy:
        return None
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / window_s(tr))


def self_times(ops, lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds each operation ran inside [lo, hi] less the time of the
    operations nested in it (a ``while`` or ``conditional`` holds its
    body's operations on the same line)."""
    total: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self time]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, t = stack.pop()
            total[name] = total.get(name, 0.0) + t

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return total


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` operations with the most device seconds of their own inside
    the window, summed over their runs and averaged over chips."""
    lo, hi = tr.window
    total: dict[str, float] = {}
    for ops in tr.ops.values():
        for name, t in self_times(ops, lo, hi).items():
            total[name] = total.get(name, 0.0) + t
    chips = max(len(tr.ops), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / chips / 1e9] for name, t in ranked]


def span_at(tr: Trace, t: float) -> str:
    """The innermost benchmark span open at time ``t``."""
    open_ = [s for s in tr.spans if s[0] <= t <= s[1]]
    if not open_:
        return "no span"
    return min(open_, key=lambda s: s[1] - s[0])[2]


def idle_gaps(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of the window in which a chip ran no
    operation, each named by the host span open at its middle."""
    lo, hi = tr.window
    gaps = []
    for ops in tr.ops.values():
        t = lo
        for s, e in busy_intervals(ops, lo, hi):
            if s > t:
                gaps.append((s - t, t, s))
            t = e
        if hi > t:
            gaps.append((hi - t, t, hi))
    gaps.sort(reverse=True)
    return [[span_at(tr, (a + b) / 2), d / 1e9] for d, a, b in gaps[:n]]


def span_s(tr: Trace, names) -> float:
    """Total seconds of the host spans with one of ``names``."""
    return sum(e - s for s, e, name in tr.spans if name in names) / 1e9
