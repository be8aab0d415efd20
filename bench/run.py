"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` from its files, warms up its
shapes (set-up), then starts simulated runs until ``--seconds`` have passed
and lets the last one finish (the window). After the window the plain
reference checks a sample of the window's runs. The last line of standard
output is one JSON object: ``correct``, ``attempted`` and ``failed``
simulated runs, the metrics (end to end with ``--trace 0``, per layer from
a profiler trace of the window with ``--trace 1``, whose window is at most
``TRACE_WINDOW_S`` long), the device, and each compared number beside its
limit. Exits non-zero, printing no result,
unless JAX finds a TPU with as many chips as the cell asks for.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench" / ".trace"
# longest window a --trace 1 run measures: exporting and reading the
# profile costs about 20 s per traced second of a drive cell on one v5e host
TRACE_WINDOW_S = 5.0


class CompileCount:
    """Compile seconds and persistent-cache hits, from JAX's events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.find_cell(args.workload, ROOT)

    t = time.perf_counter()
    # libtpu would otherwise write its logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    log(f"# setup jax_init_s={time.perf_counter() - t:.3f}")
    print(json.dumps(execute(cell, devices[:cell.chips], args.seed, args.seconds,
                             args.trace, T0, cache_dir)), flush=True)
    return 0


def execute(cell, devices, seed: int, seconds: float, trace: int,
            t0: float, cache_dir: str = "", trace_dir: Path = TRACE_DIR) -> dict:
    """Set up, measure and check one run of ``cell`` on ``devices``; return
    the result line. Set-up counts from ``t0``; a ``trace`` run writes its
    profile under ``trace_dir`` and removes it once read."""
    import jax

    from bench import harness

    compiles = CompileCount()
    log(f"# platform={devices[0].platform} device_kind={devices[0].device_kind} "
        f"devices={len(devices)} compile_cache={cache_dir}")

    t = time.perf_counter()
    runner = harness.make(cell, seed)
    log(f"# setup trace_pool_s={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    runner.warm()
    warm_s = time.perf_counter() - t
    log(f"# setup compile_or_cache_load_s={compiles.seconds:.3f} "
        f"programs={compiles.count} cache_hits={compiles.cache_hits}")
    log(f"# setup warm_up_s={warm_s:.3f} (compile or cache load included)")
    setup_s = time.perf_counter() - t0
    log(f"# setup setup_s={setup_s:.3f}")

    before, hits = compiles.count, compiles.cache_hits
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    window = harness.measure(runner, seconds, cell.sample, seed)
    if trace:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"# trace stop_s={time.perf_counter() - t:.3f}")
    log(f"# window seconds={window.seconds:.3f} sims={window.sims} "
        f"requests={window.requests} compiles_in_window={compiles.count - before} "
        f"cache_hits_in_window={compiles.cache_hits - hits}")
    log(f"# window calls={window.calls} median_call_s={window.median_call_s:.4f} "
        f"gc_s={window.gc_s:.4f} slowest={json.dumps(window.slowest)}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {}
    if trace:
        from bench import trace_reduce

        t = time.perf_counter()
        window.trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"# trace load_s={time.perf_counter() - t:.3f} "
            f"ops={sum(len(o) for o in window.trace.ops.values())}")
        t = time.perf_counter()
        busy = trace_reduce.busy_s(window.trace)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = trace_reduce.window_s(window.trace)
        metrics = harness.per_layer(cell, window)
        result["breakdown"] = harness.breakdown(window.trace)
        log(f"# trace reduce_s={time.perf_counter() - t:.3f}")
    else:
        metrics = {"sim_req_per_s": {"value": window.requests / window.seconds,
                                     "unit": "req/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    t = time.perf_counter()
    checks, failed = harness.check(cell, runner, window)
    log(f"# reference_check_s={time.perf_counter() - t:.3f}")
    correct = failed == 0
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    line = {"correct": correct, "attempted": window.sims, "failed": failed,
            "metrics": metrics, "device": device, **result, "checks": checks}
    return line


if __name__ == "__main__":
    sys.exit(main())
