"""Engine-throughput benchmark (DESIGN.md §2A): chunks/sec for the simulator
hot path, measured for read-only, mixed read/write, GC-pressure,
fault-injection, and channel-contention traces.

The paper's headline figures (13-18) come from mixed traces, so this script
is the regression guard for the vectorized write path, the fused reclaim
pass, the fused multi-victim GC (the ``gc_pressure`` section runs a
write-heavy trace against a nearly-full device so GC fires on virtually
every chunk), the armed fault path (``mixed_faults``), the full wear-correlated
reliability model (``wearout``: wear-scaled draws, die-parity rebuild,
finite spare pool), and the lattice timing model's second Lindley pass
(``channel_contention``: open-loop zipf reads funneling 4 dies into 1
channel under ``chan_model="lattice"``): it
reports steady-state chunks/sec and wall-clock per chunk (compile excluded,
measured separately) and emits a ``BENCH_engine.json`` artifact in the same
``name,value,unit`` row format as the rest of the harness.

  PYTHONPATH=src python -m benchmarks.engine_bench [--tiny] [--repeats N]
      [--out DIR]

``--tiny`` runs the unit-test geometry (CI smoke); the default is a mid-size
geometry large enough that per-chunk work dominates dispatch overhead.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

# gc_pressure workload shape — single source of truth for the trace builder
# in _sections and the provenance dict emitted into BENCH_engine.json
GC_PRESSURE_READ_FRAC = 0.1
GC_PRESSURE_WRITE_THETA = 2.0

# fault rates for the mixed_faults section: high enough that every fault
# class fires (the section prices the injected draws + recovery scatters,
# not just the dormant branches), shared with the provenance dict
FAULT_MAX_READ_RETRIES = 6
FAULT_PROG_FAIL_RATE = 0.01
FAULT_ERASE_FAIL_RATE = 0.02
FAULT_SEED = 1

# wearout section knobs (DESIGN.md §2D, wear-correlated): mixed_faults plus
# the wear curve, probabilistic read faults, die-parity rebuild and a finite
# spare pool — prices the full reliability model (wear-multiplied draws,
# rebuild lattice charges, spare accounting, degraded-mode gating)
WEAROUT_READ_FAIL_RATE = 0.002
WEAROUT_WEAR_SLOPE = 8.0
WEAROUT_SPARE_BLOCKS = 12

# channel_contention workload shape (DESIGN.md §2C): read-heavy open-loop
# Zipf trace at an offered rate that keeps the one shared bus saturated, so
# the section prices the lattice model's second Lindley pass
CHAN_CONTENTION_RATE_IOPS = 30_000.0
CHAN_CONTENTION_READ_THETA = 1.2


def bench_config(tiny: bool):
    from repro.ssdsim import geometry

    if tiny:
        return geometry.tiny_config(policy=geometry.RARO, initial_pe=500)
    return geometry.SimConfig(
        blocks_per_plane=64,  # 256 blocks
        slots_per_block=256,
        n_logical=32_768,  # half the device, like the paper's 8 GiB / 16 GiB
        chunk=512,
        migrate_pages_per_chunk=64,
        max_conversions_per_chunk=4,
        gc_free_threshold=4,
        policy=geometry.RARO,
        initial_pe=500,
    )


def gc_pressure_config(tiny: bool):
    """Geometry for the ``gc_pressure`` section: the working set covers
    almost the whole device (a handful of free blocks) and the GC watermark
    sits above the free-pool guard, so the single-victim reference must fire
    on virtually every chunk just to keep up with the write rate (~1 block
    consumed per chunk), while the fused pass amortizes the same relocation
    work over one firing per ``gc_victims_per_pass`` chunks. BASELINE policy
    isolates GC: no conversion/reclaim churn competes for the free pool (a
    nearly-full device under RARO sits below the reclaim watermark by
    construction, which would drown the GC signal in demotion work)."""
    from repro.ssdsim import geometry

    if tiny:
        # 64 blocks: 46 used, 18 free == the watermark, so the 4-chunk CI
        # smoke reaches GC pressure immediately (guard floor for k=4 is 8+2)
        return geometry.tiny_config(
            policy=geometry.BASELINE, initial_pe=500,
            n_logical=2_944, gc_free_threshold=18, gc_victims_per_pass=4,
        )
    # 256 blocks: 224 used, 32 free; up to k=8 victims per firing (floor
    # 12+2). chunk=256 keeps the per-chunk base cost small relative to the
    # every-chunk single-victim GC dispatch the section is measuring.
    return geometry.SimConfig(
        blocks_per_plane=64,
        slots_per_block=256,
        n_logical=57_344,
        chunk=256,
        migrate_pages_per_chunk=64,
        max_conversions_per_chunk=4,
        gc_free_threshold=24,
        gc_victims_per_pass=8,
        policy=geometry.BASELINE,
        initial_pe=500,
    )


def channel_contention_config(tiny: bool):
    """Geometry for the ``channel_contention`` section: every die on one
    channel (1 x 4) under ``chan_model="lattice"``, so page transfers from
    four concurrently-sensing dies serialize on a single bus. BASELINE
    policy keeps the section a pure pricing of the two-resource tandem
    recursion (no conversion/GC work in the loop)."""
    from repro.ssdsim import geometry

    if tiny:
        return geometry.tiny_config(
            n_channels=1, luns_per_channel=4, policy=geometry.BASELINE,
            initial_pe=500, chan_model="lattice",
        )
    return geometry.SimConfig(
        n_channels=1,
        luns_per_channel=4,
        blocks_per_plane=64,
        slots_per_block=256,
        n_logical=32_768,
        chunk=512,
        migrate_pages_per_chunk=64,
        max_conversions_per_chunk=4,
        gc_free_threshold=4,
        policy=geometry.BASELINE,
        initial_pe=500,
        chan_model="lattice",
    )


def _sections(tiny: bool, n_requests: int):
    """name -> (cfg, trace, has_writes). ``gc_pressure`` runs a write-heavy
    mixed trace with Zipf-skewed overwrites (concentrated invalidation makes
    worthwhile GC victims) against the small-free-pool geometry."""
    import dataclasses

    from repro.ssdsim import workload

    cfg = bench_config(tiny)
    gc_cfg = gc_pressure_config(tiny)
    # same geometry + trace as ``gc_pressure`` under the lifespan-aware GC
    # victim objective (DESIGN.md §2E): the pair prices the pluggable
    # scorer + wear telemetry against the pinned min-valid default
    gcl_cfg = dataclasses.replace(gc_cfg, gc_objective="lifespan")
    cc_cfg = channel_contention_config(tiny)
    mixed_trace = workload.mixed_trace(cfg, n_requests, 1.2, read_frac=0.7,
                                       seed=1)
    # same geometry + trace as ``mixed`` with every instrument on: the pair
    # prices the observability layer (DESIGN.md §7.4) and the regression
    # gate's ``mixed`` row doubles as the obs_level="off" zero-cost guard
    obs_cfg = dataclasses.replace(cfg, obs_level="full")
    # same geometry + trace as ``mixed`` with the fault model armed: the pair
    # prices the fault-injection layer (DESIGN.md §2D) — counter-hash draws,
    # the collapsed-retry read path, and the re-placement/retirement scatters
    flt_cfg = dataclasses.replace(
        cfg,
        max_read_retries=FAULT_MAX_READ_RETRIES,
        prog_fail_rate=FAULT_PROG_FAIL_RATE,
        erase_fail_rate=FAULT_ERASE_FAIL_RATE,
        fault_seed=FAULT_SEED,
    )
    # same geometry + trace with the whole wear-correlated reliability model
    # armed on top of mixed_faults (wear curve, read faults, parity rebuild,
    # finite spares): the flt/wear pair prices the wear-model increment
    wear_cfg = dataclasses.replace(
        flt_cfg,
        read_fail_rate=WEAROUT_READ_FAIL_RATE,
        fault_wear_slope=WEAROUT_WEAR_SLOPE,
        parity_rebuild=True,
        spare_blocks=WEAROUT_SPARE_BLOCKS,
    )
    return {
        "read_only": (
            cfg, workload.zipf_read_trace(cfg, n_requests, 1.2, seed=1), False),
        "mixed": (cfg, mixed_trace, True),
        "mixed_obs_full": (obs_cfg, mixed_trace, True),
        "mixed_faults": (flt_cfg, mixed_trace, True),
        "wearout": (wear_cfg, mixed_trace, True),
        "gc_pressure": (
            gc_cfg,
            workload.mixed_trace(gc_cfg, n_requests, 1.2, seed=1,
                                 read_frac=GC_PRESSURE_READ_FRAC,
                                 write_theta=GC_PRESSURE_WRITE_THETA),
            True),
        "gc_lifespan": (
            gcl_cfg,
            workload.mixed_trace(gcl_cfg, n_requests, 1.2, seed=1,
                                 read_frac=GC_PRESSURE_READ_FRAC,
                                 write_theta=GC_PRESSURE_WRITE_THETA),
            True),
        "channel_contention": (
            cc_cfg,
            workload.zipf_read_trace(
                cc_cfg, n_requests, CHAN_CONTENTION_READ_THETA, seed=1,
                arrival_rate=CHAN_CONTENTION_RATE_IOPS),
            False),
    }


def bench_engine(tiny: bool, n_requests: int, repeats: int):
    """Yield (name, value, unit) rows; compile time via AOT lower/compile so
    the steady-state timing loop never pays tracing cost."""
    from repro.ssdsim import engine

    for wl, (cfg, trace, has_writes) in _sections(tiny, n_requests).items():
        lpns = jnp.asarray(trace["lpn"], jnp.int32)
        ops = jnp.asarray(trace["op"], jnp.int32)
        n_chunks = lpns.shape[0]

        t0 = time.perf_counter()
        if "arrival_ms" in trace:  # open-loop section (arrival model)
            arr = jnp.asarray(trace["arrival_ms"], jnp.float32)
            compiled = engine._run_open_jit.lower(
                cfg, lpns, ops, arr, has_writes).compile()
            run = lambda: compiled(lpns, ops, arr)  # noqa: E731
        else:
            compiled = engine._run_jit.lower(cfg, lpns, ops,
                                             has_writes).compile()
            run = lambda: compiled(lpns, ops)  # noqa: E731
        compile_s = time.perf_counter() - t0

        jax.block_until_ready(run())  # warm-up / page in
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(run())
        dt = (time.perf_counter() - t0) / repeats

        yield f"engine/{wl}/compile_s", compile_s, "s"
        yield f"engine/{wl}/ms_per_chunk", dt / n_chunks * 1e3, "ms"
        yield f"engine/{wl}/chunks_per_sec", n_chunks / dt, "chunks/s"
        yield f"engine/{wl}/requests_per_sec", n_chunks * cfg.chunk / dt, "req/s"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="unit-test geometry (CI smoke)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--out", default=".", metavar="DIR",
                    help="directory for the BENCH_engine.json artifact")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = bench_config(args.tiny)
    gc_cfg = gc_pressure_config(args.tiny)
    cc_cfg = channel_contention_config(args.tiny)
    n_requests = args.requests or (4 * cfg.chunk if args.tiny else 40 * cfg.chunk)

    rows = []
    print("name,value,unit")
    for row in bench_engine(args.tiny, n_requests, args.repeats):
        rows.append(list(row))
        n, v, u = row
        print(f"{n},{v:.4f},{u}", flush=True)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    doc = {
        "bench": "engine",
        "config": {
            "tiny": args.tiny,
            "n_blocks": cfg.n_blocks,
            "slots_per_block": cfg.slots_per_block,
            "n_logical": cfg.n_logical,
            "chunk": cfg.chunk,
            "policy": cfg.policy,
            "n_requests": n_requests,
            "repeats": args.repeats,
            "gc_pressure": {
                "n_logical": gc_cfg.n_logical,
                "gc_free_threshold": gc_cfg.gc_free_threshold,
                "gc_victims_per_pass": gc_cfg.gc_victims_per_pass,
                "read_frac": GC_PRESSURE_READ_FRAC,
                "write_theta": GC_PRESSURE_WRITE_THETA,
            },
            "gc_lifespan": {
                "gc_objective": "lifespan",
                "gc_alpha": gc_cfg.gc_alpha,
                "gc_beta": gc_cfg.gc_beta,
                "gc_gamma": gc_cfg.gc_gamma,
                "base": "gc_pressure geometry + trace",
            },
            "mixed_faults": {
                "max_read_retries": FAULT_MAX_READ_RETRIES,
                "prog_fail_rate": FAULT_PROG_FAIL_RATE,
                "erase_fail_rate": FAULT_ERASE_FAIL_RATE,
                "fault_seed": FAULT_SEED,
            },
            "wearout": {
                "read_fail_rate": WEAROUT_READ_FAIL_RATE,
                "fault_wear_slope": WEAROUT_WEAR_SLOPE,
                "parity_rebuild": True,
                "spare_blocks": WEAROUT_SPARE_BLOCKS,
                "base": "mixed_faults config + trace",
            },
            "channel_contention": {
                "n_channels": cc_cfg.n_channels,
                "luns_per_channel": cc_cfg.luns_per_channel,
                "chan_model": cc_cfg.chan_model,
                "rate_iops": CHAN_CONTENTION_RATE_IOPS,
                "theta": CHAN_CONTENTION_READ_THETA,
            },
        },
        "rows": rows,
    }
    p = out / "BENCH_engine.json"
    p.write_text(json.dumps(doc, indent=1, sort_keys=True))
    print(f"# wrote {p}")


if __name__ == "__main__":
    main()
