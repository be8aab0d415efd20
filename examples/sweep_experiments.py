"""Experiments-subsystem tour: batched sweeps + tail latency in ~1 minute.

Runs a batched policy x wear x seed grid on any registered scenario
(synthetic generators or the bundled MSR-style trace replay) and prints a
tail-latency table — the metric read retries actually damage. Per-run
BENCH_*.json artifacts land in --out. --devices N shards the run axis
across devices (identical results); --fake-devices N demos it on CPU.

  PYTHONPATH=src python examples/sweep_experiments.py \\
      [--scenario read_disturb_hammer] [--requests 24000] [--out bench_out] \\
      [--devices N|all] [--fake-devices N]
  PYTHONPATH=src python examples/sweep_experiments.py --list
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="read_disturb_hammer")
    ap.add_argument("--requests", type=int, default=24_000)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--devices", default=None,
                    help="shard the run axis across N devices ('all' = every "
                         "visible device; default: single-device vmap)")
    ap.add_argument("--fake-devices", type=int, default=None, metavar="N",
                    help="fake N host devices via XLA_FLAGS (set before jax "
                         "loads) to try --devices on a CPU-only box")
    ap.add_argument("--out", default=None, help="artifact directory")
    ap.add_argument("--list", action="store_true", help="list scenarios and exit")
    args = ap.parse_args()

    from repro.hostdev import fake_host_devices  # jax-free import

    fake_host_devices(args.fake_devices)

    from repro.compile_cache import enable_compile_cache
    from repro.experiments import registry, sweep
    from repro.ssdsim import geometry

    if args.list:
        print("registered scenarios:", ", ".join(registry.names()))
        return
    if args.scenario not in registry.names():
        ap.error(f"unknown scenario {args.scenario!r}; have {registry.names()}")

    enable_compile_cache()
    spec = sweep.SweepSpec(
        scenario=args.scenario,
        n_requests=args.requests,
        policies=(geometry.BASELINE, geometry.HOTNESS, geometry.RARO),
        initial_pe=(166, 833),
        seeds=tuple(range(args.seeds)),
        base=geometry.SimConfig(device_age_h=24.0),
    )
    print(f"== sweep: {args.scenario}, {spec.n_runs()} runs "
          f"({len(spec.policies)} policies x {len(spec.initial_pe)} wear "
          f"stages x {args.seeds} seeds), one jit per policy ==")
    results = sweep.run_sweep(spec, verbose=True, devices=args.devices)

    hdr = f"{'run':<44} {'mean us':>9} {'p50 us':>9} {'p95 us':>9} {'p99 us':>9} {'p999 us':>9}"
    print(hdr)
    print("-" * len(hdr))
    for r in results:
        print(f"{r['run']['tag']:<44} {r['mean_read_latency_us']:>9.1f} "
              f"{r['read_lat_p50_us']:>9.1f} {r['read_lat_p95_us']:>9.1f} "
              f"{r['read_lat_p99_us']:>9.1f} {r['read_lat_p999_us']:>9.1f}")

    if args.out:
        paths = sweep.write_artifacts(results, args.out)
        print(f"\nwrote {len(paths)} artifacts to {args.out}/")


if __name__ == "__main__":
    main()
