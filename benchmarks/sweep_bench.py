"""Sweep benchmark: tail-latency section for the harness plus the
device-sharded scaling benchmark (DESIGN.md §7.3).

Two entry points:

``sweep_tail_latency``  — the ``benchmarks.run --only sweep`` section: a
policy x wear x seed grid on the read-disturb-hammer scenario plus a replay
of the bundled MSR-style sample trace, emitting per-run tail latencies and
the headline raro-vs-baseline p99 ratios.

``main`` (this module as a script) — the sweep *scaling* benchmark: the same
grid executed by the single-device vmapped path and by the device-sharded
``shard_map`` path, timed end to end (dispatch + execute + host summarize),
written to ``BENCH_sweep.json``. The sharded path needs multiple visible
devices; on a CPU-only host fake them with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the CI smoke does)
or pass ``--fake-devices N`` before anything imports jax.

  PYTHONPATH=src python -m benchmarks.sweep_bench [--smoke] [--devices N]
      [--fake-devices N] [--repeats R] [--requests N] [--out DIR]
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np


def _p99_ratio_rows(results, scenario: str):
    """Geomean-over-seeds baseline/raro p99 ratio per wear stage."""
    rows = []
    stages = sorted({r["run"]["initial_pe"] for r in results})
    for pe in stages:
        by_pol = {}
        for pol in ("baseline", "raro"):
            v = [r["read_lat_p99_us"] for r in results
                 if r["run"]["initial_pe"] == pe and r["run"]["policy"] == pol]
            if v:
                by_pol[pol] = float(np.exp(np.mean(np.log(np.maximum(v, 1e-9)))))
        if len(by_pol) == 2:
            rows.append((f"sweep/{scenario}/pe{pe}/raro_vs_base_p99",
                         by_pol["baseline"] / by_pol["raro"], "x"))
    return rows


def sweep_tail_latency(n_requests=80_000, msr_requests=24_000, out_dir=None,
                       devices=None):
    """Tail-latency section rows; ``devices`` forwards to the sweep runner
    (None = single-device vmap, N = shard the run axis across N devices)."""
    from repro.experiments import sweep
    from repro.ssdsim import geometry

    base = geometry.SimConfig(device_age_h=24.0)
    rows = []

    hammer = sweep.SweepSpec(
        scenario="read_disturb_hammer",
        n_requests=n_requests,
        policies=(geometry.BASELINE, geometry.RARO),
        initial_pe=(166, 833),
        seeds=(0, 1),
        base=base,
    )
    res = sweep.run_sweep(hammer, verbose=True, devices=devices)
    for r in res:
        rows += sweep.result_rows(r)
    rows += _p99_ratio_rows(res, "read_disturb_hammer")

    # bundled MSR-style trace replayed through the same runner (mixed R/W)
    msr = sweep.SweepSpec(
        scenario="msr_sample",
        n_requests=msr_requests,
        policies=(geometry.BASELINE, geometry.RARO),
        initial_pe=(500,),
        seeds=(0,),
        base=base,
    )
    res_msr = sweep.run_sweep(msr, verbose=True, devices=devices)
    for r in res_msr:
        rows += sweep.result_rows(r)
    rows += _p99_ratio_rows(res_msr, "msr_sample")

    if out_dir is not None:
        paths = sweep.write_artifacts(res + res_msr, out_dir)
        print(f"# wrote {len(paths)} BENCH_*.json artifacts to {out_dir}", flush=True)
    return rows


def sweep_fault_storm(n_requests=40_000, out_dir=None, devices=None):
    """Fault-injection section rows: the ``fault_storm`` trace swept over the
    fault axes (``configs.raro_ssd.fault_storm_sweep``), reporting tail
    latency alongside the fault counters so the recovery paths (ECC penalty,
    re-placement, bad-block retirement) show up in the harness output."""
    from repro.configs import raro_ssd
    from repro.experiments import sweep

    spec = raro_ssd.fault_storm_sweep(n_requests=n_requests)
    res = sweep.run_sweep(spec, verbose=True, devices=devices)
    rows = []
    for r in res:
        rows += sweep.result_rows(r)
    rows += _p99_ratio_rows(res, "fault_storm")

    if out_dir is not None:
        paths = sweep.write_artifacts(res, out_dir)
        print(f"# wrote {len(paths)} BENCH_*.json artifacts to {out_dir}", flush=True)
    return rows


def _endurance_frontier(results):
    """Collapse runs to one frontier point per (policy, gc_objective, pe):
    mean-over-seeds read p99 / WAF / P/E variance / projected lifetime."""
    cells = sorted({(r["run"]["policy"], r["run"]["gc_objective"],
                     r["run"]["initial_pe"]) for r in results})
    points = []
    for pol, gco, pe in cells:
        sel = [r for r in results
               if (r["run"]["policy"], r["run"]["gc_objective"],
                   r["run"]["initial_pe"]) == (pol, gco, pe)]
        points.append({
            "policy": pol,
            "gc_objective": gco,
            "initial_pe": pe,
            "read_lat_p99_us": float(np.mean([r["read_lat_p99_us"] for r in sel])),
            "waf": float(np.mean([r["waf"] for r in sel])),
            "pe_variance": float(np.mean([r["pe_variance"] for r in sel])),
            "pe_max": float(np.mean([r["pe_max"] for r in sel])),
            "lifetime_years": float(np.mean([r["lifetime_years"] for r in sel])),
            "capacity_loss_gib": float(np.mean([r["capacity_loss_gib"] for r in sel])),
        })
    return points


def sweep_endurance(n_requests=24_576, out_dir=None, devices=None):
    """Endurance section rows (DESIGN.md §2E): the
    ``configs.raro_ssd.endurance_sweep`` grid — {baseline, RARO} ×
    {min-valid, lifespan} GC × wear stages — reporting the read-p99 vs WAF
    vs projected-lifetime frontier alongside the per-run rows, plus
    headline lifespan-vs-min-valid deltas. Writes the committed
    ``BENCH_endurance.json`` (frontier + rows) when ``out_dir`` is set."""
    from repro.configs import raro_ssd
    from repro.experiments import sweep

    spec = raro_ssd.endurance_sweep(n_requests=n_requests)
    res = sweep.run_sweep(spec, verbose=True, devices=devices)
    rows = []
    for r in res:
        rows += sweep.result_rows(r, prefix="endurance")

    frontier = _endurance_frontier(res)
    for p in frontier:
        stem = (f"endurance/{p['policy']}_gc_{p['gc_objective']}"
                f"_pe{p['initial_pe']}")
        rows.append((f"{stem}/read_lat_p99_us", p["read_lat_p99_us"], "us"))
        rows.append((f"{stem}/waf", p["waf"], "ratio"))
        rows.append((f"{stem}/pe_variance", p["pe_variance"], "cycles^2"))
        rows.append((f"{stem}/lifetime_years", p["lifetime_years"], "years"))
    # headline: what the lifespan objective buys (and costs) per policy
    for pol in sorted({p["policy"] for p in frontier}):
        by_obj = {}
        for obj in ("min_valid", "lifespan"):
            v = [p for p in frontier
                 if p["policy"] == pol and p["gc_objective"] == obj]
            if v:
                by_obj[obj] = v
        if len(by_obj) == 2:
            for metric, unit in (("waf", "x"), ("pe_variance", "x"),
                                 ("lifetime_years", "x")):
                a = np.mean([p[metric] for p in by_obj["lifespan"]])
                b = np.mean([p[metric] for p in by_obj["min_valid"]])
                rows.append((f"endurance/{pol}/lifespan_vs_min_valid_{metric}",
                             float(a / max(b, 1e-12)), unit))

    if out_dir is not None:
        out = Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        doc = {
            "bench": "endurance",
            "config": {
                "scenario": spec.scenario,
                "n_requests": spec.n_requests,
                "n_runs": spec.n_runs(),
                "policies": sorted({r["run"]["policy"] for r in res}),
                "gc_objectives": list(spec.gc_objective),
                "initial_pe": list(spec.initial_pe),
                "gc_alpha": spec.base.gc_alpha,
                "gc_beta": spec.base.gc_beta,
                "gc_gamma": spec.base.gc_gamma,
            },
            "frontier": frontier,
            "rows": [list(r) for r in rows],
        }
        p = out / "BENCH_endurance.json"
        p.write_text(json.dumps(doc, indent=1, sort_keys=True))
        print(f"# wrote {p}", flush=True)
        paths = sweep.write_artifacts(res, out_dir)
        print(f"# wrote {len(paths)} BENCH_*.json artifacts to {out_dir}", flush=True)
    return rows


def _wearout_frontier(results):
    """Collapse runs to one failure-dashboard cell per (policy,
    gc_objective, wear slope, pe): reliability counters + tail latency."""
    cells = sorted({(r["run"]["policy"], r["run"]["gc_objective"],
                     r["run"]["fault_wear_slope"], r["run"]["initial_pe"])
                    for r in results})
    points = []
    for pol, gco, slope, pe in cells:
        sel = [r for r in results
               if (r["run"]["policy"], r["run"]["gc_objective"],
                   r["run"]["fault_wear_slope"], r["run"]["initial_pe"])
               == (pol, gco, slope, pe)]
        mean = lambda k: float(np.mean([r[k] for r in sel]))  # noqa: E731
        points.append({
            "policy": pol,
            "gc_objective": gco,
            "fault_wear_slope": slope,
            "initial_pe": pe,
            "uncorrectable_reads": mean("uncorrectable_reads"),
            "rebuilds": mean("rebuilds"),
            "data_loss": mean("data_loss"),
            "bad_blocks": mean("bad_blocks"),
            "spares_remaining": mean("spares_remaining"),
            "degraded_writes": mean("degraded_writes"),
            "dropped_writes": mean("dropped_writes"),
            "read_lat_p99_us": mean("read_lat_p99_us"),
            "waf": mean("waf"),
            "pe_max": mean("pe_max"),
        })
    return points


def sweep_wearout(n_requests=24_576, out_dir=None, devices=None):
    """Wear-correlated failure section rows (DESIGN.md §2D): the
    ``configs.raro_ssd.wearout_sweep`` grid — {baseline, RARO} ×
    {min-valid, lifespan} GC × {flat, wear-correlated} rates × drive age
    with die-parity rebuild and a finite spare pool — reporting the failure
    dashboard (uncorrectables / rebuilds / data loss / spare drain /
    degraded writes) alongside tail latency, plus headline
    lifespan-vs-min-valid failure ratios at the wear-correlated points.
    Writes the committed ``BENCH_wearout.json`` when ``out_dir`` is set."""
    from repro.configs import raro_ssd
    from repro.experiments import sweep

    spec = raro_ssd.wearout_sweep(n_requests=n_requests)
    res = sweep.run_sweep(spec, verbose=True, devices=devices)
    rows = []
    for r in res:
        rows += sweep.result_rows(r, prefix="wearout")

    frontier = _wearout_frontier(res)
    for p in frontier:
        stem = (f"wearout/{p['policy']}_gc_{p['gc_objective']}"
                f"_wear{p['fault_wear_slope']:g}_pe{p['initial_pe']}")
        rows.append((f"{stem}/uncorrectable_reads",
                     p["uncorrectable_reads"], "reads"))
        rows.append((f"{stem}/rebuilds", p["rebuilds"], "rebuilds"))
        rows.append((f"{stem}/data_loss", p["data_loss"], "stripes"))
        rows.append((f"{stem}/bad_blocks", p["bad_blocks"], "blocks"))
        rows.append((f"{stem}/spares_remaining",
                     p["spares_remaining"], "blocks"))
        rows.append((f"{stem}/read_lat_p99_us", p["read_lat_p99_us"], "us"))
    # headline: what lifespan-aware GC buys on the failure trajectories at
    # the wear-correlated high-age points (the dashboard's thesis)
    slope_hi = max(p["fault_wear_slope"] for p in frontier)
    pe_hi = max(p["initial_pe"] for p in frontier)
    for pol in sorted({p["policy"] for p in frontier}):
        by_obj = {}
        for obj in ("min_valid", "lifespan"):
            v = [p for p in frontier
                 if (p["policy"], p["gc_objective"], p["fault_wear_slope"],
                     p["initial_pe"]) == (pol, obj, slope_hi, pe_hi)]
            if v:
                by_obj[obj] = v[0]
        if len(by_obj) == 2:
            for metric in ("uncorrectable_reads", "data_loss", "bad_blocks"):
                a = by_obj["lifespan"][metric]
                b = by_obj["min_valid"][metric]
                rows.append(
                    (f"wearout/{pol}/lifespan_vs_min_valid_{metric}",
                     float(a / max(b, 1e-12)), "x")
                )

    if out_dir is not None:
        out = Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        doc = {
            "bench": "wearout",
            "config": {
                "scenario": spec.scenario,
                "n_requests": spec.n_requests,
                "n_runs": spec.n_runs(),
                "policies": sorted({r["run"]["policy"] for r in res}),
                "gc_objectives": list(spec.gc_objective),
                "initial_pe": list(spec.initial_pe),
                "fault_wear_slope": list(spec.fault_wear_slope),
                "fault_wear_power": spec.base.fault_wear_power,
                "read_fail_rate": list(spec.read_fail_rate),
                "prog_fail_rate": list(spec.prog_fail_rate),
                "erase_fail_rate": list(spec.erase_fail_rate),
                "max_read_retries": list(spec.max_read_retries),
                "spare_blocks": list(spec.spare_blocks),
                "parity_rebuild": [bool(v) for v in spec.parity_rebuild],
            },
            "frontier": frontier,
            "rows": [list(r) for r in rows],
        }
        p = out / "BENCH_wearout.json"
        p.write_text(json.dumps(doc, indent=1, sort_keys=True))
        print(f"# wrote {p}", flush=True)
        paths = sweep.write_artifacts(res, out_dir, prefix="wearout")
        print(f"# wrote {len(paths)} BENCH_*.json artifacts to {out_dir}", flush=True)
    return rows


# ------------------------- sharded scaling bench ---------------------------


def scaling_spec(n_requests: int, seeds: int, smoke: bool):
    """The grid the scaling bench times: 2 wear stages x ``seeds`` seeds per
    policy group, on the unit-test geometry when ``smoke``."""
    from repro.experiments import sweep
    from repro.ssdsim import geometry

    base = (geometry.tiny_config() if smoke
            else geometry.SimConfig(device_age_h=24.0))
    return sweep.SweepSpec(
        scenario="read_disturb_hammer",
        n_requests=n_requests,
        policies=(geometry.BASELINE, geometry.RARO),
        initial_pe=(166, 833),
        seeds=tuple(range(seeds)),
        base=base,
    )


def bench_scaling(spec, n_devices: int, repeats: int):
    """Time ``run_sweep`` end to end (dispatch + execute + batched
    device_get + host summarize) on the vmapped single-device path and
    sharded across ``n_devices``; after timing, the two paths' last result
    sets are asserted identical (the equivalence the tests guarantee,
    re-checked on the benchmark grid for free). Yields harness rows."""
    from repro.experiments import sweep

    n_runs = spec.n_runs()
    repeats = max(repeats, 1)  # the loop must bind res / divide by repeats

    def timed(devices):
        sweep.run_sweep(spec, devices=devices)  # warm-up: compile + page in
        t0 = time.perf_counter()
        for _ in range(repeats):
            res = sweep.run_sweep(spec, devices=devices)
        return (time.perf_counter() - t0) / repeats, res

    dt1, res1 = timed(None)
    dtn, resn = timed(n_devices)
    sweep.assert_results_identical(res1, resn)

    yield "sweep/scaling/n_runs", float(n_runs), "runs"
    yield "sweep/scaling/vmap1/wall_s", dt1, "s"
    yield "sweep/scaling/vmap1/runs_per_sec", n_runs / dt1, "runs/s"
    yield f"sweep/scaling/sharded{n_devices}/wall_s", dtn, "s"
    yield f"sweep/scaling/sharded{n_devices}/runs_per_sec", n_runs / dtn, "runs/s"
    yield f"sweep/scaling/sharded{n_devices}/speedup", dt1 / dtn, "x"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="unit-test geometry + small grid (CI)")
    ap.add_argument("--devices", default=None,
                    help="device count for the sharded pass, or 'all' "
                         "(default: every visible device)")
    ap.add_argument("--fake-devices", type=int, default=None, metavar="N",
                    help="set XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N before jax loads (local convenience; CI sets the "
                         "env var itself)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seeds", type=int, default=4,
                    help="seeds per (policy, wear) cell of the timed grid")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=".", metavar="DIR",
                    help="directory for the BENCH_sweep.json artifact")
    args = ap.parse_args()

    from repro.hostdev import fake_host_devices  # jax-free import

    fake_host_devices(args.fake_devices)

    import jax  # after the XLA_FLAGS mutation above

    from repro.compile_cache import enable_compile_cache
    from repro.experiments import sweep

    enable_compile_cache()

    n_devices = (
        len(jax.devices()) if args.devices in (None, "all")
        else int(args.devices)
    )
    # fail fast: the sharded pass runs *after* the vmapped warm-up+timing,
    # so without this an invalid --devices only errors minutes in
    sweep.resolve_devices(n_devices)
    spec = scaling_spec(
        args.requests or (16 * 128 if args.smoke else 40_000),
        args.seeds, args.smoke,
    )

    rows = []
    print("name,value,unit")
    for row in bench_scaling(spec, n_devices, args.repeats):
        rows.append(list(row))
        n, v, u = row
        print(f"{n},{v:.4f},{u}", flush=True)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    doc = {
        "bench": "sweep",
        "config": {
            "smoke": args.smoke,
            "scenario": spec.scenario,
            "n_requests": spec.n_requests,
            "n_runs": spec.n_runs(),
            "seeds": len(spec.seeds),
            "devices": n_devices,
            "visible_devices": len(jax.devices()),
            "platform": jax.devices()[0].platform,
            "repeats": args.repeats,
        },
        "rows": rows,
    }
    p = out / "BENCH_sweep.json"
    p.write_text(json.dumps(doc, indent=1, sort_keys=True))
    print(f"# wrote {p}")


if __name__ == "__main__":
    main()
