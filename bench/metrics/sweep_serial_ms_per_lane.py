"""Host milliseconds per lane of the benchmark's ``bench.sweep`` spans in
which no chip ran any operation: the serial host work of a sweep (trace
build, stacking, placement, fetch, summary) that every chip waits on. The
sweep spans inside the window less the union of all chips' busy intervals,
over the lanes simulated in the window."""

from bench import trace_reduce

SPAN = "bench.sweep"


def read(window):
    tr = window.trace
    sweeps = [s for s in tr.spans if s[2] == SPAN]
    if not tr.ops or not sweeps or not window.sims:
        return None
    lo, hi = tr.window
    busy = trace_reduce.busy_intervals(
        [op for ops in tr.ops.values() for op in ops], lo, hi)
    serial = 0.0
    for s, e, _ in sweeps:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            serial += (e - s) - sum(max(0.0, min(e, b) - max(s, a)) for a, b in busy)
    return serial / 1e6 / window.sims
