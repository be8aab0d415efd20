"""Share of the window in which no operation ran on a chip, mean over the
cell's chips."""

from bench import trace_reduce


def read(window):
    return trace_reduce.idle_pct(window.trace)
