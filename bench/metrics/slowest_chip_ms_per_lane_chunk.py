"""Device milliseconds per lane-chunk on the busiest chip: that chip's busy
time in the window (union of its operation intervals) over the chunks each
chip simulated (the window's chunks, every lane counted, shared evenly by
the chips of the trace). A sweep sharded over chips in lockstep ends when
its slowest chip does, so this is the pace of the device side."""

from bench import trace_reduce


def read(window):
    busy = trace_reduce.busy_s(window.trace)
    if not busy or not window.chunks or max(busy.values()) <= 0:
        return None
    return 1000.0 * max(busy.values()) / (window.chunks / len(busy))
