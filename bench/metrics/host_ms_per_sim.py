"""Host milliseconds per simulated drive run outside the device wait: the
benchmark's spans around ``engine.run`` (dispatch and host-to-device copy)
and ``engine.summarize``."""

from bench import trace_reduce

SPANS = ("bench.dispatch", "bench.summarize")


def read(window):
    spans = [s for s in window.trace.spans if s[2] in SPANS]
    if not spans or not window.sims:
        return None
    return 1000.0 * trace_reduce.span_s(window.trace, SPANS) / window.sims
