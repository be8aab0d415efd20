"""Chip smoke: the simulator's main path, end to end, on a TPU.

Drives the flash simulator at the paper's Table III geometry (the default
``SimConfig``: 2 channels x 2 dies, 1,024 blocks x 1,024 slots, 524,288
logical pages) through its public entry points, checks every phase, and
prints one JSON line as the last line of its output:

  python3 chip_smoke.py              # phases A, B, C and K on one chip
  python3 chip_smoke.py --four-chip  # sharded vs vmapped sweep, four chips

  A  the quickstart: Baseline / Hotness / RARO, Zipf-1.2 reads at middle
     wear, ``engine.run`` + ``engine.summarize``
  B  one mixed RARO run (writes, GC, conversion, wear-scaled faults,
     die-parity rebuild, a finite spare pool) on the channel lattice with
     obs counters; ``state.check_invariants`` on its final state
  C  a small ``run_sweep`` grid on one device (the vmap path)
  K  ``tiered_decode_attention`` and ``quant_pages``, compiled, against
     their ``ref.py`` oracles at the serve widths

A and B run again on the host CPU in the same process: read and user-write
counts must match exactly, float statistics within FLOAT_RTOL. Exits
non-zero, printing no result, when JAX finds no TPU or any phase fails.
Seconds printed along the way are smoke, not device metrics. The
persistent compile cache lives where ``repro.compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_CHUNKS = 64  # requests per run = N_CHUNKS * cfg.chunk (65,536 at Table III)
MIDDLE_PE = 500  # the paper's middle wear stage
ZIPF_THETA = 1.2
# counts that must agree exactly between backends, and float statistics
# that may differ by reassociation and a few ulps of log/pow/log10. On a
# v5e the largest TPU-vs-CPU delta was 1.9e-5 (p99, Hotness/RARO; a few
# reads' Eq.-3 retry counts differ), so the bound keeps 5x headroom.
EXACT_KEYS = ("reads", "writes")
FLOAT_KEYS = ("iops", "retries_per_read", "read_lat_p50_us",
              "read_lat_p99_us", "migrated_pages", "capacity_loss_gib")
FLOAT_RTOL = 1e-4
# compiled kernel against its f32 oracle on the host
KERNEL_TOL = 1e-5

class SmokeFailure(AssertionError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Clock:
    """Wall seconds of each timed block, split into XLA compile seconds
    (persistent-cache reads included; tracing and lowering, which nest,
    stay in run_s) and the rest. Compiles count once :meth:`listen` has
    hooked JAX's compile events."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0

    def listen(self) -> None:
        import jax

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextmanager
    def timed(self, label: str):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        comp = self.compile_s - c0
        wall = time.perf_counter() - t0
        print(f"  {label}: compile_s={comp:.2f} run_s={wall - comp:.2f} "
              f"(smoke)", flush=True)


def _check_placed(tree, dev) -> None:
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        _check(leaf.devices() == {dev},
               f"array on {leaf.devices()}, expected {dev}")


def _peak_bytes(dev):
    stats = dev.memory_stats()
    if stats is None:
        _check(dev.platform != "tpu", f"{dev} reports no memory_stats")
        return None
    return stats.get("peak_bytes_in_use")


def _ran_on(dev, peak_before) -> None:
    """Device ``dev`` held the work just done: its peak usage rose."""
    peak = _peak_bytes(dev)
    if peak is not None:
        _check(peak > peak_before, f"{dev}: peak_bytes_in_use stayed at "
                                   f"{peak_before}, the work ran elsewhere")


def agree(tag: str, got: dict, want: dict, rtol: float = FLOAT_RTOL) -> None:
    """Counts equal exactly, float statistics within ``rtol``; prints every
    relative delta before failing on any."""
    bad = [f"{k}: {got[k]!r} != {want[k]!r}" for k in EXACT_KEYS
           if got[k] != want[k]]
    deltas = []
    for k in FLOAT_KEYS:
        a, b = float(got[k]), float(want[k])
        rel = abs(a - b) / max(abs(b), 1e-30) if a != b else 0.0
        deltas.append(f"{k}={rel:.3g}")
        if rel > rtol:
            bad.append(f"{k}: {a!r} vs {b!r} (rel {rel:.3g} > {rtol})")
    print(f"  {tag}: rel deltas {' '.join(deltas)}", flush=True)
    _check(not bad, f"{tag} disagrees: " + "; ".join(bad))


def run_engine(cfg, trace, dev):
    """``engine.run`` on ``dev``; returns the final state, checked to live
    there."""
    import jax

    from repro.ssdsim import engine

    with jax.default_device(dev):
        s, _ = engine.run(cfg, trace)
        s = jax.block_until_ready(s)
    _check_placed(s, dev)
    return s


def state_bytes(s) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(s))


def phase_a(clock, base, dev, ref_dev):
    """The quickstart's three policies; returns {policy: summary on dev}."""
    from repro.ssdsim import engine, geometry, workload

    out = {}
    for pol in (geometry.BASELINE, geometry.HOTNESS, geometry.RARO):
        name = geometry.POLICY_NAMES[pol]
        cfg = replace(base, policy=pol, initial_pe=MIDDLE_PE, device_age_h=24.0)
        tr = workload.zipf_read_trace(cfg, N_CHUNKS * cfg.chunk, ZIPF_THETA,
                                      seed=1)
        with clock.timed(f"A/{name} {dev.platform}"):
            s = run_engine(cfg, tr, dev)
        m = engine.summarize(s, cfg)
        with clock.timed(f"A/{name} {ref_dev.platform}"):
            r = engine.summarize(run_engine(cfg, tr, ref_dev), cfg)
        print(f"  A/{name}: IOPS={m['iops']:.1f} "
              f"retries/read={m['retries_per_read']:.4f} "
              f"p99={m['read_lat_p99_us']:.1f}us "
              f"capacity loss={m['capacity_loss_gib'] * 1024:.1f} MiB "
              f"migrated pages={m['migrated_pages']:.0f} "
              f"state={state_bytes(s)} B", flush=True)
        _check(m["reads"] == N_CHUNKS * cfg.chunk, f"A/{name}: reads {m['reads']}")
        agree(f"A/{name} {dev.platform} vs {ref_dev.platform}", m, r)
        out[pol] = m
    b, r = out[geometry.BASELINE], out[geometry.RARO]
    print(f"  A: RARO vs Baseline IOPS {r['iops'] / b['iops']:.2f}x", flush=True)
    return out


def phase_b_config(base):
    """Mixed RARO drive with the engine bench's fault/wear/parity/spare
    knobs, on the channel lattice. The GC watermark sits a few blocks under
    the initial free pool, so GC fires within the run."""
    from benchmarks import engine_bench as eb
    from repro.ssdsim import geometry

    used = -(-base.n_logical // base.slots_per_block)
    return replace(
        base, policy=geometry.RARO, initial_pe=MIDDLE_PE, device_age_h=24.0,
        chan_model="lattice", obs_level="counters",
        gc_free_threshold=base.n_blocks - used - 4,
        max_read_retries=eb.FAULT_MAX_READ_RETRIES,
        prog_fail_rate=eb.FAULT_PROG_FAIL_RATE,
        erase_fail_rate=eb.FAULT_ERASE_FAIL_RATE,
        fault_seed=eb.FAULT_SEED,
        read_fail_rate=eb.WEAROUT_READ_FAIL_RATE,
        fault_wear_slope=eb.WEAROUT_WEAR_SLOPE,
        parity_rebuild=True,
        spare_blocks=eb.WEAROUT_SPARE_BLOCKS,
    )


def phase_b(clock, base, dev, ref_dev):
    import jax

    from repro.ssdsim import engine, workload
    from repro.ssdsim import state as st

    cfg = phase_b_config(base)
    tr = workload.mixed_trace(cfg, N_CHUNKS * cfg.chunk, ZIPF_THETA,
                              read_frac=0.7, seed=1)
    with clock.timed(f"B {dev.platform}"):
        s = run_engine(cfg, tr, dev)
    st.check_invariants(jax.device_get(s), cfg, f"B/{dev.platform}")
    m = engine.summarize(s, cfg)
    with clock.timed(f"B {ref_dev.platform}"):
        s_ref = run_engine(cfg, tr, ref_dev)
    st.check_invariants(jax.device_get(s_ref), cfg, f"B/{ref_dev.platform}")
    print(f"  B: check_invariants passed on {dev.platform} and "
          f"{ref_dev.platform}", flush=True)
    conv = sum(sum(row) for row in m["conversions"])
    print(f"  B: reads={m['reads']:.0f} writes={m['writes']:.0f} "
          f"erases={m['erases']:.0f} conversions={conv:.0f} "
          f"migrated={m['migrated_pages']:.0f} waf={m['waf']:.4f} "
          f"uncorrectable={m['uncorrectable_reads']:.0f} "
          f"rebuilds={m['rebuilds']:.0f} prog_fails={m['prog_fails']:.0f} "
          f"bad_blocks={m['bad_blocks']:.0f} "
          f"spares_left={m['spares_remaining']:.0f} "
          f"state={state_bytes(s)} B", flush=True)
    _check(m["writes"] > 0 and m["erases"] > 0 and m["migrated_pages"] > 0,
           "B: writes, GC/erase and conversion must all fire")
    agree(f"B {dev.platform} vs {ref_dev.platform}", m,
          engine.summarize(s_ref, cfg))
    return m


def phase_c(clock, base, dev, a_raro):
    """A RARO x 2 wear x 2 seed ``run_sweep`` grid, vmapped on ``dev``; its
    (middle wear, seed 1) run is phase A's RARO run."""
    import jax

    from repro.experiments import sweep
    from repro.ssdsim import geometry

    n = N_CHUNKS * base.chunk
    spec = sweep.SweepSpec(
        scenario="zipf", n_requests=n, policies=(geometry.RARO,),
        initial_pe=(166, MIDDLE_PE), seeds=(1, 2),
        base=replace(base, device_age_h=24.0),
    )
    peak0 = _peak_bytes(dev)
    with clock.timed(f"C {dev.platform}"), jax.default_device(dev):
        res = sweep.run_sweep(spec)
    _ran_on(dev, peak0)
    _check(len(res) == spec.n_runs(), f"C: {len(res)} results")
    for r in res:
        _check(r["reads"] == n, f"C/{r['run']['tag']}: reads {r['reads']}")
        print(f"  C/{r['run']['tag']}: IOPS={r['iops']:.1f} "
              f"p99={r['read_lat_p99_us']:.1f}us", flush=True)
    same = [r for r in res
            if r["run"]["initial_pe"] == MIDDLE_PE and r["run"]["seed"] == 1]
    agree("C vmap vs A/raro", same[0], a_raro)
    return res


def _tiered_cache(ccfg, steps: int, seed: int):
    """A decoded cache with pages in several tiers: the serve loop's
    append + RARO controller on random K/V."""
    import jax

    from repro.kvcache import paged, tiers

    rcfg = tiers.RAROConfig()
    key = jax.random.PRNGKey(seed)
    shape = (ccfg.n_seqs, ccfg.n_kv_heads, ccfg.head_dim)

    @jax.jit
    def append(c, t):
        k = jax.random.normal(jax.random.fold_in(key, 2 * t), shape) * 0.5
        v = jax.random.normal(jax.random.fold_in(key, 2 * t + 1), shape) * 0.5
        return paged.append(c, ccfg, k, v, tiers.commit_tier(c, ccfg, rcfg))

    @jax.jit
    def control(c, t):
        masses = jax.random.uniform(jax.random.fold_in(key, 900 + t),
                                    (ccfg.n_seqs, ccfg.max_pages)) * 0.05
        return tiers.raro_step(c, ccfg, rcfg, masses)[0]

    c = paged.init(ccfg, jax.numpy.float32)
    for t in range(steps):
        c = append(c, t)
        if t % 3 == 0:
            c = control(c, t)
    return c


def phase_kernels(clock, dev, ref_dev, steps: int = 64, batch: int = 4,
                  seed: int = 0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import modes
    from repro.kernels.quant_page.ops import quant_pages
    from repro.kernels.quant_page.ref import quant_pages_ref
    from repro.kernels.tiered_attention.ops import tiered_decode_attention
    from repro.kernels.tiered_attention.ref import tiered_decode_attention_ref
    from repro.kvcache import quant
    from repro.launch import serve

    mcfg = serve.serve_cfg()
    ccfg = serve.serve_cache_cfg(mcfg, steps, batch)
    compiled = dev.platform == "tpu"
    to_ref = lambda x: jax.device_put(x, ref_dev)  # noqa: E731

    with clock.timed(f"K/tiered {dev.platform}"), jax.default_device(dev):
        cache = _tiered_cache(ccfg, steps, seed)
        q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (batch, mcfg.n_heads, mcfg.head_dim), jnp.float32)
        hlo = tiered_decode_attention.lower(q, cache, ccfg).as_text()
        out, mass = jax.block_until_ready(tiered_decode_attention(q, cache, ccfg))
    _check(("tpu_custom_call" in hlo) == compiled,
           f"K/tiered: kernel {'interpreted' if compiled else 'compiled'} "
           f"on {dev.platform}")
    _check_placed((out, mass), dev)
    present = set(np.asarray(cache.tier).ravel().tolist()) - {-1}
    _check(len(present) >= 2, f"K/tiered: cache holds tiers {present} only")
    with jax.default_device(ref_dev):
        out_r, mass_r = tiered_decode_attention_ref(to_ref(q), to_ref(cache), ccfg)
    d_out = float(np.max(np.abs(np.asarray(out) - np.asarray(out_r))))
    d_mass = float(np.max(np.abs(np.asarray(mass) - np.asarray(mass_r))))
    print(f"  K/tiered: tiers {sorted(present)}, max |out - ref| {d_out:.3g}, "
          f"max |mass - ref| {d_mass:.3g}", flush=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(mass_r),
                               atol=KERNEL_TOL)

    n_pages = ccfg.pool_pages[modes.TIER_INT8]
    page = (ccfg.page_size, ccfg.n_kv_heads, ccfg.head_dim)
    for tier in (modes.TIER_INT8, modes.TIER_INT4):
        for dtype in (jnp.float32, jnp.bfloat16):
            label = f"K/quant tier{tier} {jnp.dtype(dtype).name}"
            with clock.timed(f"{label} {dev.platform}"), jax.default_device(dev):
                x = jax.random.normal(jax.random.PRNGKey(seed + 2 + tier),
                                      (n_pages,) + page, dtype)
                hlo = quant_pages.lower(x, tier=tier).as_text()
                qk, sk, ek = jax.block_until_ready(quant_pages(x, tier=tier))
            _check(("tpu_custom_call" in hlo) == compiled,
                   f"{label}: wrong kernel mode on {dev.platform}")
            _check_placed((qk, sk, ek), dev)
            with jax.default_device(ref_dev):
                qr, sr, er = quant_pages_ref(to_ref(x), tier=tier)
            if tier == modes.TIER_INT4:  # compare unpacked nibbles
                qk, qr = quant.unpack_int4(to_ref(qk)), quant.unpack_int4(qr)
            dq = np.abs(np.asarray(qk, np.int32) - np.asarray(qr, np.int32))
            print(f"  {label}: codes off by one {(dq != 0).mean():.4f}, "
                  f"max |err - ref| "
                  f"{float(np.max(np.abs(np.asarray(ek) - np.asarray(er)))):.3g}",
                  flush=True)
            # scales may differ by an ulp, so codes by one at rounding ties
            _check(dq.max() <= 1 and (dq != 0).mean() < 0.01,
                   f"{label}: codes differ from the oracle")
            np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(ek), np.asarray(er),
                                       rtol=1e-4, atol=1e-6)


def phase_four_chip(clock, base, devs):
    """An 8-run grid sharded over four chips against the same grid vmapped
    on one: identical summaries, and every chip held a share."""
    import jax

    from repro.experiments import sweep
    from repro.ssdsim import geometry

    spec = sweep.SweepSpec(
        scenario="zipf", n_requests=N_CHUNKS * base.chunk,
        policies=(geometry.RARO,), initial_pe=(166, 833), seeds=(1, 2, 3, 4),
        base=replace(base, device_age_h=24.0),
    )
    peaks = [_peak_bytes(d) for d in devs]
    with clock.timed(f"4chip sharded over {len(devs)}"):
        sharded = sweep.run_sweep(spec, devices=list(devs))
    for d, p in zip(devs, peaks):
        _ran_on(d, p)
    print(f"  4chip: every one of {len(devs)} devices held a share", flush=True)
    with clock.timed("4chip vmapped on one"), jax.default_device(devs[0]):
        vmapped = sweep.run_sweep(spec)
    for a, b in zip(sharded, vmapped):
        for k in EXACT_KEYS + FLOAT_KEYS:
            if a[k] != b[k]:
                print(f"  4chip: {a['run']['tag']} {k}: sharded {a[k]!r} "
                      f"vmapped {b[k]!r}", flush=True)
    sweep.assert_results_identical(sharded, vmapped)
    print(f"  4chip: {len(sharded)} runs bitwise identical, sharded vs "
          f"vmapped", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded-vs-vmapped sweep on 4 chips")
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    try:
        import jax

        from repro.compile_cache import enable_compile_cache
        from repro.ssdsim import geometry
    except ImportError as e:
        print(f"chip_smoke: cannot import the simulator ({e}); run it from "
              f"the root of a checkout", file=sys.stderr)
        return 2

    devs = jax.devices()
    need = 4 if args.four_chip else 1
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU present (JAX sees {devs[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = Clock()
    clock.listen()
    dev, cpu = devs[0], jax.devices("cpu")[0]
    base = geometry.SimConfig()
    raw_gib = base.n_slots * base.page_bytes / 2**30
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache_dir}")
    print(f"geometry: {base.n_channels} channels x {base.luns_per_channel} "
          f"dies, {base.n_blocks} blocks x {base.slots_per_block} slots, "
          f"{base.page_kib} KiB pages ({raw_gib:g} GiB raw), "
          f"{base.n_logical} logical pages; {N_CHUNKS} chunks x "
          f"{base.chunk} requests per run", flush=True)

    if args.four_chip:
        phases = [("4chip", lambda: phase_four_chip(clock, base, devs[:4]))]
    else:
        a = {}
        phases = [
            ("A", lambda: a.update(phase_a(clock, base, dev, cpu))),
            ("B", lambda: phase_b(clock, base, dev, cpu)),
            ("C", lambda: phase_c(clock, base, dev, a[geometry.RARO])),
            ("K", lambda: phase_kernels(clock, dev, cpu)),
        ]
    with clock.timed("all phases"):
        for name, fn in phases:
            print(f"phase {name}", flush=True)
            with clock.timed(f"phase {name}"):
                fn()
            print(f"phase {name} passed; peak_bytes_in_use "
                  f"{_peak_bytes(dev)}", flush=True)
    print(f"persistent compile-cache hits: {clock.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
