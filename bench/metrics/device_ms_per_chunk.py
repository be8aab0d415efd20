"""Device milliseconds per simulated chunk of requests: the chips' busy
time in the window (union of operation intervals, summed over chips) over
the chunks simulated in it, every lane of a grid counted."""

from bench import trace_reduce


def read(window):
    busy = trace_reduce.busy_s(window.trace)
    if not busy or not window.chunks or sum(busy.values()) <= 0:
        return None
    return 1000.0 * sum(busy.values()) / window.chunks
