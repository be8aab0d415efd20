"""Benchmark harness — one section per paper table/figure plus the Layer-B
(TPU) tiered-KV benchmark. Prints ``name,value,unit`` CSV; with
``--artifacts DIR`` every section also writes a ``BENCH_<section>.json``
artifact carrying the same rows (the sweep section additionally writes its
per-run artifacts, as before).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only SUBSTR]
      [--artifacts DIR]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _write_section_artifact(out_dir: str, section: str, rows: list) -> Path:
    """One ``BENCH_<section>.json`` mirroring the section's CSV rows — the
    same name/value/unit format the sweep runner emits per run. Non-finite
    values become null so the artifact stays strict RFC-8259 JSON."""
    def _clean(v):
        if isinstance(v, float) and (v != v or v in (float("inf"), float("-inf"))):
            return None
        return v

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    p = out / f"BENCH_{section}.json"
    p.write_text(
        json.dumps({"section": section, "rows": [[_clean(v) for v in r] for r in rows]},
                   indent=1, sort_keys=True)
    )
    return p


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced request counts")
    ap.add_argument("--only", default=None)
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="write BENCH_*.json artifacts here (one per section, "
                         "plus the sweep section's per-run artifacts)")
    ap.add_argument("--devices", default=None,
                    help="shard the sweep section's run axis across N devices "
                         "('all' = every visible device; default: vmap on one)")
    args = ap.parse_args()

    from benchmarks import paper_figs, sweep_bench, tiered_kv
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    q = args.quick
    sections = [
        ("fig2", lambda: paper_figs.fig2_mode_read_perf(20_000 if q else 60_000)),
        ("fig3_4", paper_figs.fig3_4_retry_impact),
        ("fig5_6", paper_figs.fig5_6_retry_distribution),
        ("fig13_16", lambda: paper_figs.fig13_16_policy_comparison(
            60_000 if q else 200_000,
            thetas=(1.2,) if q else (1.2, 1.5),
            threads=(4,) if q else (4, 1))),
        ("fig17_18", lambda: paper_figs.fig17_18_sensitivity(40_000 if q else 120_000)),
        ("sweep", lambda: sweep_bench.sweep_tail_latency(
            24_000 if q else 80_000,
            msr_requests=8_000 if q else 24_000,
            out_dir=args.artifacts,
            devices=args.devices)),
        ("faults", lambda: sweep_bench.sweep_fault_storm(
            12_000 if q else 40_000,
            out_dir=args.artifacts,
            devices=args.devices)),
        # named so `--only sweep` also matches it: the endurance grid is
        # part of the paper's sweep story (read-p99 vs WAF vs lifetime)
        ("endurance_sweep", lambda: sweep_bench.sweep_endurance(
            8_192 if q else 24_576,
            out_dir=args.artifacts,
            devices=args.devices)),
        # named so `--only sweep` also matches it: the wear-correlated
        # failure dashboard (rebuilds / data loss / spare drain)
        ("wearout_sweep", lambda: sweep_bench.sweep_wearout(
            8_192 if q else 24_576,
            out_dir=args.artifacts,
            devices=args.devices)),
        ("tiered_kv", lambda: tiered_kv.kv_policy_comparison(24 if q else 48)),
    ]

    print("name,value,unit")
    ran, failed = 0, []
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        ran += 1
        t0 = time.time()
        try:
            rows = []
            for row in fn():
                rows.append(row)
                n, v, u = row
                v = f"{v:.4f}" if isinstance(v, float) else v
                print(f"{n},{v},{u}", flush=True)
            if args.artifacts:
                p = _write_section_artifact(args.artifacts, name, rows)
                print(f"# wrote {p}", flush=True)
            print(f"# section {name} took {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep the harness going
            failed.append(name)
            print(f"# section {name} FAILED: {type(e).__name__}: {e}", flush=True)
    if failed:
        print(f"# {len(failed)}/{ran} sections FAILED: {', '.join(failed)}",
              flush=True)
        sys.exit(1)
    print(f"# all {ran} sections passed", flush=True)


if __name__ == "__main__":
    main()
