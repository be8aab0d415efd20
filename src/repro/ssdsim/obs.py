"""In-scan observability (DESIGN.md §7.4): latency attribution, conversion
event tracing and windowed time-series telemetry.

RARO's argument is causal — read slowdown comes from retries, so conversion
should fire only when hot data sits in high-retry QLC blocks — and end-of-run
aggregates can't show *which component* of p99 is retry-induced, *which
trigger* caused each conversion, or *when* retry storms happen. This module
adds three jit/vmap/shard_map-safe instruments, all static-shape accumulator
leaves on :class:`repro.ssdsim.state.SSDState`:

1. **Latency component decomposition** (``obs_lat_mode``, ``obs_lat_comp``):
   every recorded user read is split into queue / sense / retry-penalty /
   transfer time and binned — by its *total* recorded latency, reusing the
   :mod:`repro.ssdsim.telemetry` log-spaced bin geometry — per source flash
   mode. ``obs_lat_mode[m]`` counts reads of mode ``m`` per latency bin (the
   per-mode count histograms sum over modes to ``lat_hist`` bit-exactly:
   identical bin indices, integer-valued f32 adds); ``obs_lat_comp[m, c, b]``
   accumulates component ``c``'s microseconds over the reads in (mode, bin),
   so "retries contribute X µs of QLC p99" is a direct readout
   (:func:`tail_attribution`).

2. **Conversion/GC/reclaim event ring buffer** (``obs_events``,
   ``obs_ev_count``): a fixed-capacity ring recorded inside the scan at
   every relocation site. Each event carries sim-time, block id (-1 for
   page-granular conversions), from/to mode, a trigger reason code, the
   Eq.-3 mean retry estimate of the pages moved, and the valid page count.
   Overwrite-oldest semantics: the write cursor is ``obs_ev_count mod
   capacity`` and ``obs_ev_count`` keeps the true total, so truncation is
   always explicit (``dropped = max(total - capacity, 0)``).

3. **Windowed time series** (``obs_ts``): reads / retries / queue delay /
   writes / conversions / erases / migrated pages / uncorrectables /
   relocation pages (the windowed-WAF numerator) bucketed by simulated-time
   window (``cfg.obs_window_ms`` per window, ``cfg.obs_windows`` windows; the
   final window absorbs everything past the covered range, again explicit
   rather than silent). Retry storms and conversion waves show up as
   trajectories instead of totals.

Cost model (``cfg.obs_level``): ``"off"`` traces **no** observability ops at
all — every obs leaf is zero-length, so the scan carry and compiled program
are unchanged up to empty arrays (the PR 4/5 regression gate guards the
claim). ``"counters"`` adds the per-mode count histograms, the time
series (a handful of scatter-adds per chunk) and the count of chunks in
which each watermark-gated pass ran (``obs_gated_chunks``). ``"full"`` adds
the component decomposition and the event ring buffer.

Host-side decoders (numpy, usable on device_get'ed sweep leaves) live at the
bottom: :func:`decode_events`, :func:`event_conversion_matrix`,
:func:`decode_timeseries`, :func:`decomposition`, :func:`tail_attribution`.
The Chrome-trace exporter builds on them in
:mod:`repro.ssdsim.trace_export`.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import modes
from repro.ssdsim import geometry, telemetry

# --------------------------- instrument geometry ---------------------------

LEVELS = ("off", "counters", "full")

# latency components, in recorded-latency order: queueing delay behind the
# die, the base sense, the extra senses bought by retries, the wait for the
# channel bus (transfer queueing — nonzero only under the lattice model),
# the channel transfer service itself, and the die-parity rebuild critical
# path of uncorrectable reads recovered via the stripe (DESIGN.md §2D; zero
# mass unless ``parity_rebuild`` is armed)
COMP_QUEUE = 0
COMP_SENSE = 1
COMP_RETRY = 2
COMP_CHANWAIT = 3
COMP_XFER = 4
COMP_REBUILD = 5
N_COMPONENTS = 6
COMPONENT_NAMES = ("queue", "sense", "retry", "chan_wait", "transfer",
                   "rebuild")

# event record fields (one f32 row per event; ids/counts are small integers,
# exact in f32, which keeps the ring a single dense array — one scatter)
EV_T_MS = 0
EV_BLOCK = 1  # -1 for page-granular conversion events
EV_FROM = 2
EV_TO = 3
EV_REASON = 4
EV_RETRY = 5  # Eq.-3 mean retry estimate over the pages moved
EV_PAGES = 6  # valid pages moved
N_EV_FIELDS = 7

# trigger reason codes
REASON_CONV_PAGE = 0  # policy-triggered page-granular conversion (Fig. 11)
REASON_GC = 1  # fused multi-victim GC relocation
REASON_RECLAIM = 2  # elastic capacity recovery demotion (paper §IV-E)
REASON_CONV_BLOCK = 3  # direct block conversion (ftl.migrate_block API)
REASON_BAD_BLOCK = 4  # erase failure -> bad-block retirement (DESIGN.md §2D)
REASON_NAMES = ("conversion", "gc", "reclaim", "block_conversion",
                "bad_block_retire")

# time-series rows
TS_READS = 0
TS_RETRIES = 1
TS_QUEUE_MS = 2
TS_WRITES = 3
TS_CONVERSIONS = 4  # n_conversions increments (pages for page-granular ops)
TS_ERASES = 5
TS_MIGRATED = 6
TS_UNCORR = 7  # uncorrectable reads (ECC recovery events, DESIGN.md §2D)
TS_RELOC = 8  # relocation-programmed pages (WAF numerator, DESIGN.md §2E)
N_SERIES = 9
SERIES_NAMES = (
    "reads", "retries", "queue_ms", "writes", "conversions", "erases",
    "migrated_pages", "uncorrectable", "reloc_pages",
)

# watermark-gated background passes (``ftl.gated``): the chunks in which each
# ran. In a vmapped sweep a pass runs for the whole batch when any lane of it
# needs the pass, so every lane of a batch counts the same chunks.
GATE_RECLAIM = 0
GATE_GC = 1
N_GATES = 2


def enabled(cfg: geometry.SimConfig) -> bool:
    """Counters or better are being collected (trace-time gate)."""
    return cfg.obs_level != "off"


def full(cfg: geometry.SimConfig) -> bool:
    """Component decomposition + event ring are being collected."""
    return cfg.obs_level == "full"


def init_leaves(cfg: geometry.SimConfig) -> dict:
    """Zero accumulators for ``state.init_state`` — shapes depend only on
    the (static) config, and collapse to zero-length when an instrument is
    off so the disabled path carries nothing through the scan."""
    if cfg.obs_level not in LEVELS:
        raise ValueError(
            f"obs_level must be one of {LEVELS}, got {cfg.obs_level!r}"
        )
    n_mode = modes.N_MODES if enabled(cfg) else 0
    n_full = modes.N_MODES if full(cfg) else 0
    cap = int(cfg.obs_event_capacity) if full(cfg) else 0
    win = int(cfg.obs_windows) if enabled(cfg) else 0
    if full(cfg) and cap < 1:
        raise ValueError("obs_event_capacity must be >= 1 at obs_level='full'")
    if enabled(cfg) and win < 1:
        raise ValueError("obs_windows must be >= 1 when observability is on")
    return dict(
        obs_lat_mode=jnp.zeros((n_mode, telemetry.N_LAT_BINS), jnp.float32),
        obs_lat_comp=jnp.zeros(
            (n_full, N_COMPONENTS, telemetry.N_LAT_BINS), jnp.float32
        ),
        obs_events=jnp.zeros((cap, N_EV_FIELDS), jnp.float32),
        obs_ev_count=jnp.int32(0),
        obs_ts=jnp.zeros((win, N_SERIES), jnp.float32),
        obs_gated_chunks=jnp.zeros((N_GATES if enabled(cfg) else 0,),
                                   jnp.int32),
    )


# ------------------------------ in-scan hooks ------------------------------


def _window_of(cfg: geometry.SimConfig, t_ms):
    """Window index for a sim time; the last window absorbs overflow."""
    w = jnp.floor(jnp.asarray(t_ms, jnp.float32) / cfg.obs_window_ms)
    return jnp.clip(w.astype(jnp.int32), 0, int(cfg.obs_windows) - 1)


def record_reads(s, cfg: geometry.SimConfig, *, mode, rd, lat_us, queue_us,
                 sense_us, retry_us, chanw_us, xfer_us, retries, t_ms,
                 uncorr=None, rebuild_us=None):
    """Per-read instruments for one chunk (engine read path).

    ``mode``/``lat_us``/... are per-lane arrays; ``rd`` masks user reads;
    ``t_ms`` is the per-lane sim time used for windowing (departure time
    open-loop, the chunk clock closed-loop). ``chanw_us`` is the transfer
    *queueing* behind the channel bus — split from the transfer service so
    bus contention is attributable separately (zero under the legacy
    channel model, where transfer never queues). ``uncorr`` (optional bool
    lanes, fault injection on) feeds the uncorrectable-read series.
    Masked-out lanes are dropped via out-of-range indices — the repo-wide
    scatter discipline.
    """
    if not enabled(cfg):
        return s
    nbin = telemetry.N_LAT_BINS
    b = telemetry.latency_bin(lat_us)
    m = jnp.clip(mode, 0, modes.N_MODES - 1)
    # per-mode count histogram: same bin index as telemetry.record uses for
    # lat_hist, so summing over modes reproduces it bit-exactly
    mode_drop = jnp.where(rd, m, modes.N_MODES)
    lat_mode = s.obs_lat_mode.at[mode_drop, b].add(1.0, mode="drop")

    # time series: reads / retries / queue per window of each read's own time
    w = jnp.where(rd, _window_of(cfg, t_ms), int(cfg.obs_windows))
    ts = s.obs_ts
    ts = ts.at[w, TS_READS].add(1.0, mode="drop")
    ts = ts.at[w, TS_RETRIES].add(
        jnp.asarray(retries, jnp.float32), mode="drop"
    )
    ts = ts.at[w, TS_QUEUE_MS].add(
        jnp.asarray(queue_us, jnp.float32) / 1000.0, mode="drop"
    )
    if uncorr is not None:
        ts = ts.at[w, TS_UNCORR].add(
            jnp.asarray(uncorr, jnp.float32), mode="drop"
        )
    s = s._replace(obs_lat_mode=lat_mode, obs_ts=ts)

    if not full(cfg):
        return s
    comp = s.obs_lat_comp
    pairs = [
        (COMP_QUEUE, queue_us),
        (COMP_SENSE, sense_us),
        (COMP_RETRY, retry_us),
        (COMP_CHANWAIT, chanw_us),
        (COMP_XFER, xfer_us),
    ]
    if rebuild_us is not None:
        pairs.append((COMP_REBUILD, rebuild_us))
    for c, v in pairs:
        comp = comp.at[mode_drop, c, b].add(
            jnp.asarray(v, jnp.float32), mode="drop"
        )
    return s._replace(obs_lat_comp=comp)


def record_chunk(s, cfg: geometry.SimConfig, *, t_ms, writes, conversions,
                 erases, migrated, reloc=None):
    """Chunk-granularity series (background-FTL counter deltas): everything
    in the chunk lands in the window of the chunk's end-of-step clock.
    ``reloc`` (optional) feeds the relocation-pages series behind the
    windowed WAF readout of :func:`decode_timeseries`."""
    if not enabled(cfg):
        return s
    w = _window_of(cfg, t_ms)
    ts = s.obs_ts
    rows = [
        (TS_WRITES, writes),
        (TS_CONVERSIONS, conversions),
        (TS_ERASES, erases),
        (TS_MIGRATED, migrated),
    ]
    if reloc is not None:
        rows.append((TS_RELOC, reloc))
    for row, v in rows:
        ts = ts.at[w, row].add(jnp.asarray(v, jnp.float32))
    return s._replace(obs_ts=ts)


def record_gate(s, cfg: geometry.SimConfig, gate: int, opened):
    """Count the chunk in ``gate``'s slot when its pass ran (``opened``, the
    scalar predicate of the branch ``ftl.gated`` took)."""
    if not enabled(cfg):
        return s
    return s._replace(
        obs_gated_chunks=s.obs_gated_chunks.at[gate].add(
            jnp.asarray(opened, jnp.int32)
        )
    )


def record_events(s, cfg: geometry.SimConfig, *, mask, block, from_mode,
                  to_mode, reason, retry_est, pages):
    """Append ``mask``-ed events to the ring buffer (relocation sites).

    All arguments are (K,) lanes (``reason`` may be a python int). Events
    are written at ``(obs_ev_count + rank) mod capacity`` in lane order, so
    the ring holds the most recent ``capacity`` events and the counter keeps
    the true total — overwrite-oldest with explicit truncation.
    """
    if not full(cfg):
        return s
    cap = s.obs_events.shape[0]
    mask = jnp.asarray(mask, bool)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    pos = (s.obs_ev_count + rank) % cap
    idx = jnp.where(mask, pos, cap)  # cap = out of range -> dropped
    rows = jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(v, jnp.float32), mask.shape)
            for v in (
                s.clock_ms, block, from_mode, to_mode, reason, retry_est,
                pages,
            )
        ],
        axis=-1,
    )
    return s._replace(
        obs_events=s.obs_events.at[idx].set(rows, mode="drop"),
        obs_ev_count=s.obs_ev_count + mask.sum().astype(jnp.int32),
    )


# ----------------------------- host decoders -------------------------------


def decode_events(s, cfg: geometry.SimConfig):
    """Decode the ring into structured records, oldest first.

    Returns ``(records, total, dropped)``: ``records`` is a list of dicts
    (one per event still in the ring), ``total`` the true number of events
    emitted, ``dropped`` how many were overwritten (``total - len(records)``).
    Works on device or numpy leaves (the sweep runner hands numpy).
    """
    ev = np.asarray(s.obs_events, np.float32)
    total = int(np.asarray(s.obs_ev_count))
    cap = ev.shape[0]
    if cap == 0 or total == 0:
        return [], total, total
    n = min(total, cap)
    # ring order: the oldest retained event sits at total mod cap when the
    # ring has wrapped, else at 0
    start = total % cap if total > cap else 0
    order = (start + np.arange(n)) % cap
    records = []
    for row in ev[order]:
        reason = int(row[EV_REASON])
        records.append(
            dict(
                t_ms=float(row[EV_T_MS]),
                block=int(row[EV_BLOCK]),
                from_mode=int(row[EV_FROM]),
                to_mode=int(row[EV_TO]),
                from_mode_name=modes.MODE_NAMES[int(row[EV_FROM])],
                to_mode_name=modes.MODE_NAMES[int(row[EV_TO])],
                reason=reason,
                reason_name=REASON_NAMES[reason],
                retry_est=float(row[EV_RETRY]),
                pages=int(row[EV_PAGES]),
                # the increment this event contributed to n_conversions:
                # page-granular conversions count pages, block ops count 1
                conversions=int(row[EV_PAGES]) if reason == REASON_CONV_PAGE
                else 1,
            )
        )
    return records, total, total - n


def event_conversion_matrix(records) -> np.ndarray:
    """(3, 3) from-mode x to-mode conversion counts reconstructed from
    decoded events — equals ``SSDState.n_conversions`` whenever the ring
    did not overflow (``dropped == 0``)."""
    m = np.zeros((modes.N_MODES, modes.N_MODES), np.float64)
    for r in records:
        m[r["from_mode"], r["to_mode"]] += r["conversions"]
    return m


def decode_timeseries(s, cfg: geometry.SimConfig) -> dict:
    """Windowed series as a dict of numpy arrays (+ derived means)."""
    ts = np.asarray(s.obs_ts, np.float64)
    out = {"window_start_ms": np.arange(ts.shape[0]) * cfg.obs_window_ms,
           "window_ms": float(cfg.obs_window_ms)}
    for i, name in enumerate(SERIES_NAMES):
        out[name] = ts[:, i]
    reads = np.maximum(out["reads"], 1.0)
    out["mean_queue_delay_us"] = out["queue_ms"] / reads * 1e3
    out["retries_per_read"] = out["retries"] / reads
    # windowed write amplification (DESIGN.md §2E): per-window delta WAF,
    # pinned to 1.0 in windows with no host writes (idle or read-only)
    writes = out["writes"]
    out["waf_window"] = np.where(
        writes > 0,
        (writes + out["reloc_pages"]) / np.maximum(writes, 1.0),
        1.0,
    )
    return out


def decomposition(s, cfg: geometry.SimConfig) -> dict:
    """Per-mode latency decomposition: read counts and per-component µs per
    latency bin, plus the telemetry bin edges."""
    return dict(
        counts=np.asarray(s.obs_lat_mode, np.float64),
        component_us=np.asarray(s.obs_lat_comp, np.float64),
        edges_us=telemetry.bin_edges_us(),
        components=COMPONENT_NAMES,
        modes=modes.MODE_NAMES,
    )


def tail_attribution(s, cfg: geometry.SimConfig, q: float = 0.99) -> dict:
    """Component shares of the latency mass at and above each mode's
    q-quantile bin — the "retries contribute X µs of QLC p99" readout.

    Returns per-mode dicts: the quantile's bin edge, the reads in the tail,
    and per-component µs totals and shares over those tail reads. Modes with
    no reads report zeros.
    """
    counts = np.asarray(s.obs_lat_mode, np.float64)
    comp = np.asarray(s.obs_lat_comp, np.float64)
    out = {}
    for m, name in enumerate(modes.MODE_NAMES):
        if counts.shape[0] == 0 or counts[m].sum() <= 0:
            out[name] = dict(
                tail_reads=0.0, tail_edge_us=0.0,
                component_us={c: 0.0 for c in COMPONENT_NAMES},
                component_share={c: 0.0 for c in COMPONENT_NAMES},
            )
            continue
        b = telemetry.quantile_bin(counts[m], q)
        tail_us = comp[m, :, b:].sum(axis=1) if comp.shape[0] else np.zeros(
            N_COMPONENTS
        )
        total = max(tail_us.sum(), 1e-12)
        out[name] = dict(
            tail_reads=float(counts[m, b:].sum()),
            tail_edge_us=float(telemetry.bin_edges_us()[b]),
            component_us={c: float(v)
                          for c, v in zip(COMPONENT_NAMES, tail_us)},
            component_share={c: float(v / total)
                             for c, v in zip(COMPONENT_NAMES, tail_us)},
        )
    return out


def summary(s, cfg: geometry.SimConfig) -> dict:
    """JSON-safe flat additions for ``engine.summarize`` (floats and nested
    lists only — the sweep's exact-equality checker ``np.asarray``'s every
    value, so no nested dicts).

    Keys (present at ``counters`` and up; decomposition/event keys need
    ``full``):

    - ``lat_mode_counts`` — (3, N_LAT_BINS) per-mode read-count histogram
    - ``gated_pass_chunks`` — (N_GATES,) chunks in which the reclaim and GC
      passes ran (for a sweep lane: chunks its batch ran them)
    - ``lat_attrib_us`` — (3, N_COMPONENTS) total µs per mode x component
    - ``tail_retry_share`` — (3,) retry share of each mode's p99 tail mass
    - ``conversion_events`` — (3, 3) decoded from-x-to event counts (equals
      ``conversions`` when ``obs_events_dropped`` is 0)
    - ``obs_events_total`` / ``obs_events_dropped`` — ring truncation, explicit
    """
    if not enabled(cfg):
        return {}
    out = {
        "lat_mode_counts": np.asarray(s.obs_lat_mode, np.float64).tolist(),
        "gated_pass_chunks": np.asarray(s.obs_gated_chunks, np.float64).tolist(),
    }
    if not full(cfg):
        return out
    comp = np.asarray(s.obs_lat_comp, np.float64)
    attrib = tail_attribution(s, cfg)
    records, total, dropped = decode_events(s, cfg)
    out.update(
        lat_attrib_us=comp.sum(axis=2).tolist(),
        tail_retry_share=[
            attrib[name]["component_share"]["retry"]
            for name in modes.MODE_NAMES
        ],
        conversion_events=event_conversion_matrix(records).tolist(),
        obs_events_total=float(total),
        obs_events_dropped=float(dropped),
    )
    return out
