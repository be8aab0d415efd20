"""Chunked vectorized simulation engine (DESIGN.md §2A).

One engine step processes ``cfg.chunk`` requests: reads are fully
vectorized (metadata gathers + segment-sum accounting), user writes run
through the batched write path (per-LUN prefix sums + masked scatters; the
sequential scan survives only as the test reference), then the policy's
per-read trigger pipeline runs on the chunk's unique read set and
conversions/reclaim/GC execute as pressure-gated background FTL tasks,
exactly like FEMU's background loop between request bursts. All block
relocation — multi-victim GC (up to ``cfg.gc_victims_per_pass`` per
firing), reclaim demotion and conversion — runs through the one fused
``ftl.relocate_group`` kernel (DESIGN.md §2A).

Two timing models share the engine (DESIGN.md §2C):

  closed loop (trace without ``arrival_ms``) — requests are serviced
  back-to-back; recorded read latency = sense/retry + transfer and the sim
  clock follows cumulative LUN busy time. The original behavior, bit-for-bit.

  open loop (trace with ``arrival_ms``) — each request has an arrival
  timestamp; requests queue FCFS per die behind earlier requests and behind
  background FTL work (migrations/reclaim/GC/erase), and the recorded
  latency adds the queueing delay, with departures from a vectorized
  per-lane Lindley recursion (:func:`_queue_departures`) against the
  ``die_avail_ms`` clocks. Under ``cfg.chan_model == "legacy"`` transfer is
  appended to the recorded latency but never queues (the historical
  one-clock-per-LUN model); under ``"lattice"`` the same recursion runs
  twice as a two-resource tandem (:func:`_tandem_departures`) — die pass
  for sense/program occupancy, then a channel pass where every page
  transfer serializes on its die's channel bus against ``chan_avail_ms``,
  so a read departs at max(die_free, chan_free_after_prior_transfers) +
  sense + retries + xfer.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import faults as flt
from repro.core import hotness, modes, reclaim, retry
from repro.ssdsim import ftl, geometry, obs, policies, telemetry
from repro.ssdsim import state as st

OP_READ = 0
OP_WRITE = 1


class ChunkMetrics(NamedTuple):
    capacity_pages: jnp.ndarray
    free_blocks: jnp.ndarray
    mode_hist: jnp.ndarray  # (3,) blocks per mode (non-free)
    reads: jnp.ndarray
    retries: jnp.ndarray
    svc_ms: jnp.ndarray  # total recorded read latency this chunk
    migrated: jnp.ndarray
    lat_hist: jnp.ndarray  # (telemetry.N_LAT_BINS,) this chunk's read latencies
    w_lat_hist: jnp.ndarray  # (telemetry.N_LAT_BINS,) this chunk's write latencies
    q_ms: jnp.ndarray  # total read queueing delay this chunk (0 closed-loop)
    chanq_ms: jnp.ndarray  # total read channel-wait this chunk (lattice only)
    user_pages: jnp.ndarray  # host pages written this chunk (WAF numerator lhs)
    reloc_pages: jnp.ndarray  # GC/conversion/reclaim pages moved this chunk


def _queue_departures(avail0_ms, arrival_ms, occ_ms, lun, active, n_luns: int):
    """Per-resource FCFS departure times for one chunk (vectorized Lindley).

    ``lun`` assigns each lane to a resource column (a die's command queue,
    or a channel bus in the lattice model's transfer pass). The classic
    recursion per resource, in request order,

        start_k = max(A_k, D_{k-1});  D_k = start_k + S_k

    closed-forms — with P_k the per-resource inclusive prefix sum of
    service times S and A_j the arrival times — to

        D_k = P_k + max(avail0_lun, max_{j<=k}(A_j - P_{j-1}))

    so one masked ``cumsum`` and one masked ``cummax`` per resource column
    replace a per-request scan. Arrivals need not be sorted: out-of-order
    A_j simply serve in lane (request-admission) order, which is what the
    tandem channel pass relies on. Inactive lanes neither occupy the
    resource nor constrain the max; a resource with no requests this chunk
    keeps ``avail0_lun``. Returns (per-lane departure times, final
    per-resource availability), both in ms.
    """
    oh = (lun[:, None] == jnp.arange(n_luns, dtype=jnp.int32)[None, :]) & active[:, None]
    sv = jnp.where(oh, occ_ms[:, None], 0.0)
    prefix = jnp.cumsum(sv, axis=0)  # (C, n_luns) inclusive per-lane P_k
    slack = jnp.where(oh, arrival_ms[:, None] - (prefix - sv), -jnp.inf)
    m = jnp.maximum(lax.cummax(slack, axis=0), avail0_ms[None, :])
    depart = prefix + m
    lane_dep = jnp.take_along_axis(
        depart, jnp.clip(lun, 0, n_luns - 1)[:, None], axis=1
    )[:, 0]
    return lane_dep, depart[-1]


def _tandem_departures(die_avail0, chan_avail0, arrival_ms, die_occ_ms,
                       xfer_ms, die, chan, rd, active, n_dies: int,
                       n_channels: int):
    """Two-resource tandem Lindley recursion (``chan_model="lattice"``).

    Stage 1 — the die: every active request queues FCFS on its die for its
    command occupancy (sense+retries for reads, page program for writes),
    exactly the legacy recursion. Stage 2 — the channel bus: every request's
    page transfer then queues FCFS on the die's channel for ``xfer_ms``. A
    read's transfer becomes eligible when its sense finishes (the die-pass
    departure: data sits in the page register, freeing the die — the
    decoupling that keeps both passes closed-form); a write's transfer is
    eligible at the request's arrival (the controller stages data to the
    die over the bus before/while the die drains earlier work, so write
    transfers contend for the bus without coupling the passes). The channel
    serves transfers in request-admission order (FCFS per bus).

    Returns ``(die_dep, chan_dep, die_avail, chan_avail)``: per-lane die
    and channel departure times plus the final per-resource clocks. A read
    departs the device at ``chan_dep`` = max(die_free,
    chan_free_after_prior_transfers) + sense + retries + xfer; a write
    departs the die at ``die_dep`` (its recorded latency appends the
    transfer it already paid on admission).
    """
    die_dep, die_avail = _queue_departures(
        die_avail0, arrival_ms, die_occ_ms, die, active, n_dies
    )
    chan_arr = jnp.where(rd, die_dep, arrival_ms)
    chan_dep, chan_avail = _queue_departures(
        chan_avail0, chan_arr, xfer_ms, chan, active, n_channels
    )
    return die_dep, chan_dep, die_avail, chan_avail


def lookup(s: st.SSDState, lpns, cfg: geometry.SimConfig, wide: bool = False):
    """Gather physical metadata + Eq.-3 retry estimate for logical pages.
    ``wide`` reads the mapping and timestamps through their 128-wide views
    (``state.take``), as a vmapped step does."""
    lp = jnp.maximum(lpns, 0)
    slot = st.take(s.l2p, lp, wide)
    ok = (lpns >= 0) & (slot >= 0)
    slot = jnp.where(ok, slot, 0)
    blk = slot // cfg.slots_per_block
    mode = s.block_mode[blk]
    age_h = cfg.device_age_h + (
        s.clock_ms - st.take(s.page_write_ms, slot, wide)) / 3.6e6
    retries = retry.page_retries(mode, s.block_pe[blk], age_h, s.block_reads[blk], slot)
    return slot, blk, mode, retries, ok


def write_path_reference(s: st.SSDState, lpns, is_write, cfg: geometry.SimConfig):
    """Sequential user-write path — the original per-request ``lax.scan``.

    Retained purely as the behavioral reference for
    :func:`write_path_batched`; the property tests assert the two produce
    equivalent state on arbitrary mixed traces (DESIGN.md §2A). The engine
    itself always runs the batched path.
    """
    spb = cfg.slots_per_block
    ppb = geometry.pages_per_block(cfg)
    ppb_q = ppb[modes.QLC]
    w_lat_us = modes.WRITE_LATENCY_US[modes.QLC] + cfg.transfer_us

    def wstep(s, x):
        lpn, active = x

        def do(s):
            lun = (lpn % cfg.n_luns).astype(jnp.int32)
            d = s.open_user[lun]
            dd0 = jnp.maximum(d, 0)
            need_new = (d < 0) | (s.block_next[dd0] >= ppb_q)
            a = ftl.alloc_free_block(s, prefer_lun=lun, cfg=cfg)
            d2 = jnp.where(need_new, a, d)
            ok = d2 >= 0
            dd = jnp.maximum(d2, 0)
            # open fresh block in QLC mode
            s = s._replace(
                block_mode=s.block_mode.at[dd].set(
                    jnp.where(ok & need_new, modes.QLC, s.block_mode[dd])
                ),
                block_state=s.block_state.at[dd].set(
                    jnp.where(ok & need_new, st.OPEN, s.block_state[dd])
                ),
                free_count=s.free_count - jnp.where(ok & need_new, 1, 0),
            )
            # invalidate previous mapping
            old = s.l2p[lpn]
            has_old = ok & (old >= 0)
            old_blk = jnp.maximum(old, 0) // spb
            s = s._replace(
                p2l=s.p2l.at[jnp.where(has_old, old, cfg.n_slots)].set(-1, mode="drop"),
                block_valid=s.block_valid.at[jnp.where(has_old, old_blk, s.block_valid.shape[0])].add(
                    -1, mode="drop"
                ),
            )
            slot = dd * spb + s.block_next[dd]
            nxt = s.block_next[dd] + 1
            full = nxt >= ppb_q
            s = s._replace(
                l2p=s.l2p.at[jnp.where(ok, lpn, cfg.n_logical)].set(slot, mode="drop"),
                p2l=s.p2l.at[jnp.where(ok, slot, cfg.n_slots)].set(lpn, mode="drop"),
                page_write_ms=s.page_write_ms.at[jnp.where(ok, slot, cfg.n_slots)].set(
                    s.clock_ms, mode="drop"
                ),
                block_next=s.block_next.at[dd].add(jnp.where(ok, 1, 0)),
                block_valid=s.block_valid.at[dd].add(jnp.where(ok, 1, 0)),
                block_state=s.block_state.at[dd].set(
                    jnp.where(ok & full, st.FULL, s.block_state.at[dd].get())
                ),
                open_user=s.open_user.at[lun].set(jnp.where(ok & ~full, d2, -1)),
                die_busy_ms=s.die_busy_ms.at[lun].add(
                    jnp.where(ok, modes.WRITE_LATENCY_US[modes.QLC] / 1000.0, 0.0)
                ),
                n_writes=s.n_writes + jnp.where(ok, 1.0, 0.0),
                w_lat_hist=telemetry.record(s.w_lat_hist, w_lat_us, ok),
            )
            return s

        return lax.cond(active, do, lambda s_: s_, s), None

    s, _ = lax.scan(wstep, s, (jnp.maximum(lpns, 0), is_write & (lpns >= 0)))
    return s


def write_path_batched(s: st.SSDState, lpns, is_write, cfg: geometry.SimConfig,
                       w_lat_us=None, faults: flt.FaultParams | None = None):
    """Vectorized user-write path (DESIGN.md §2A).

    The chunk's writes are grouped by LUN and assigned destination slots with
    per-LUN prefix sums against ``block_next``; open-block rollovers become a
    small static unroll of allocation *events* (at most
    ``n_luns * ceil(chunk / pages_per_qlc_block)``), replayed in request
    order so allocation decisions match :func:`write_path_reference` exactly.
    All L2P/P2L/timestamp/accounting updates are masked scatters — no
    per-request scan.

    ``w_lat_us`` optionally overrides the per-lane latency recorded in the
    write histogram (the open-loop engine passes queueing-inclusive sojourn
    times); the default is the closed-loop QLC program + transfer constant.

    With ``faults`` active (DESIGN.md §2D), each program draws a
    deterministic failure keyed on (slot, block P/E). A failed program
    wastes its slot (programmed-but-invalid, reclaimed by GC like any stale
    page) and the page data — still in the controller buffer — is re-placed
    through :func:`ftl._place_pages` onto a fresh block, where the program
    is verified-good (real firmware program-verifies the retry target). The
    superseded pre-chunk mapping is invalidated either way: if both the
    program *and* its re-placement fail (free pool exhausted under
    retirement pressure), the write is dropped and counted in
    ``n_dropped_writes`` rather than corrupting the mapping; dropped writes
    still occupy their LUN for a program time, so the queue stalls and the
    Lindley clocks advance instead of the device absorbing infinite load.
    """
    spb = cfg.slots_per_block
    ppb_q = int(geometry.pages_per_block_host(cfg)[modes.QLC])
    C = lpns.shape[0]
    nL, B = cfg.n_luns, cfg.n_blocks
    S, L = cfg.n_slots, cfg.n_logical

    lp = jnp.maximum(lpns, 0)
    w = is_write & (lpns >= 0)
    if faults is not None:
        # spare-pool exhaustion flips the device read-only (DESIGN.md §2D):
        # real drives stop accepting host writes once retirement outruns
        # over-provisioning. Writes in a degraded chunk are dropped whole —
        # counted in ``n_degraded_writes``, never admitted, so no mapping
        # entry is touched and every already-written page stays readable.
        # With an unbounded pool (``spare_blocks < 0``) ``degraded`` is a
        # constant False and the write set is untouched bit for bit.
        degraded = s.spare_count <= jnp.int32(0)
        n_degraded = (w & degraded).sum().astype(jnp.float32)
        w = w & ~degraded
        s = s._replace(n_degraded_writes=s.n_degraded_writes + n_degraded)
    lun = (lp % nL).astype(jnp.int32)

    # per-LUN write ranks via prefix sums
    oh = (lun[:, None] == jnp.arange(nL, dtype=jnp.int32)[None, :]) & w[:, None]
    cum = jnp.cumsum(oh.astype(jnp.int32), axis=0)
    rank = jnp.take_along_axis(cum, lun[:, None], axis=1)[:, 0] - 1
    nw = cum[-1]  # (nL,) writes per LUN

    d0 = s.open_user
    next0 = jnp.where(d0 >= 0, s.block_next[jnp.maximum(d0, 0)], 0)
    avail0 = jnp.where(d0 >= 0, jnp.maximum(ppb_q - next0, 0), 0)

    # ---- allocation events: one per open-block rollover ----
    n_ev = -(-C // ppb_q)  # static: max fresh blocks per LUN per chunk
    E = nL * n_ev
    over = rank - avail0[lun]  # this write's slot count past the open block
    is_trig = w & (over >= 0) & (over % ppb_q == 0)
    ev = lun * n_ev + jnp.clip(over // ppb_q, 0, n_ev - 1)
    pos_i = jnp.arange(C, dtype=jnp.int32)
    trig_pos = (
        jnp.full((E,), C, jnp.int32)
        .at[jnp.where(is_trig, ev, E)]
        .min(pos_i, mode="drop")
    )
    order = jnp.argsort(trig_pos)  # triggered events first, in request order

    dest_ev = jnp.full((E,), -1, jnp.int32)
    for j in range(E):  # static unroll; E is a handful of events
        e = order[j]
        active = trig_pos[e] < C
        a = ftl.alloc_free_block(s, prefer_lun=e // n_ev, cfg=cfg)
        got = active & (a >= 0)
        aa = jnp.maximum(a, 0)
        dest_ev = dest_ev.at[e].set(jnp.where(got, a, -1))
        s = s._replace(
            block_mode=s.block_mode.at[aa].set(
                jnp.where(got, modes.QLC, s.block_mode[aa])
            ),
            block_state=s.block_state.at[aa].set(
                jnp.where(got, st.OPEN, s.block_state[aa])
            ),
            free_count=s.free_count - jnp.where(got, 1, 0),
        )

    # ---- per-write destination slots ----
    in_open = w & (over < 0)
    ev_i = lun * n_ev + jnp.clip(jnp.maximum(over, 0) // ppb_q, 0, n_ev - 1)
    dest_blk = jnp.where(in_open, d0[lun], dest_ev[ev_i])
    off = jnp.where(in_open, next0[lun] + rank, jnp.maximum(over, 0) % ppb_q)
    ok = w & (dest_blk >= 0)
    db = jnp.maximum(dest_blk, 0)
    slot = db * spb + off

    # program-failure draw (DESIGN.md §2D): a failed lane still consumes its
    # slot (programmed-but-invalid) but never maps; its data is re-placed
    # below after the scatters commit
    if faults is not None:
        pfail = ok & flt.prog_fails(
            faults, slot, s.block_pe[db], modes.PE_LIMIT[s.block_mode[db]]
        )
    else:
        pfail = jnp.zeros_like(ok)

    # duplicate LPNs within the chunk: only the last attempted write
    # supersedes the mapping; earlier ones still consume slots and are
    # immediately invalid
    last_pos = (
        jnp.full((L,), -1, jnp.int32)
        .at[jnp.where(ok, lp, L)]
        .max(pos_i, mode="drop")
    )
    is_last = ok & (last_pos[lp] == pos_i)
    mapped = is_last & ~pfail  # last attempt actually decoded into its slot
    refail = is_last & pfail  # last attempt failed -> re-place the data

    # invalidate pre-chunk mappings, once per unique written LPN: the new
    # write supersedes the old data even when its program failed (the fresh
    # copy lives in the controller buffer until re-placed)
    old = s.l2p[lp]
    inv = is_last & (old >= 0)
    old_safe = jnp.maximum(old, 0)

    l2p = s.l2p.at[jnp.where(mapped, lp, L)].set(slot, mode="drop")
    l2p = l2p.at[jnp.where(refail, lp, L)].set(-1, mode="drop")
    p2l = s.p2l.at[jnp.where(ok, slot, S)].set(jnp.where(mapped, lp, -1), mode="drop")
    p2l = p2l.at[jnp.where(inv, old, S)].set(-1, mode="drop")
    pwt = s.page_write_ms.at[jnp.where(ok, slot, S)].set(s.clock_ms, mode="drop")

    oki = ok.astype(jnp.int32)
    bn_add = jax.ops.segment_sum(oki, db, num_segments=B)
    bv_add = jax.ops.segment_sum(mapped.astype(jnp.int32), db, num_segments=B)
    bv_sub = jax.ops.segment_sum(inv.astype(jnp.int32), old_safe // spb, num_segments=B)
    block_next = s.block_next + bn_add
    block_valid = s.block_valid + bv_add - bv_sub
    touched = bn_add > 0
    block_state = jnp.where(
        touched, jnp.where(block_next >= ppb_q, st.FULL, st.OPEN), s.block_state
    )

    # final open-block cursor per LUN (the scan's last-write outcome)
    last_over = (nw - 1) - avail0
    last_ev = jnp.arange(nL, dtype=jnp.int32) * n_ev + jnp.clip(
        jnp.maximum(last_over, 0) // ppb_q, 0, n_ev - 1
    )
    d_last = jnp.where(last_over < 0, d0, dest_ev[last_ev])
    last_full = block_next[jnp.maximum(d_last, 0)] >= ppb_q
    open_user = jnp.where(
        nw > 0, jnp.where((d_last >= 0) & ~last_full, d_last, -1), s.open_user
    )

    okc = jax.ops.segment_sum(oki, lun, num_segments=nL)
    if w_lat_us is None:
        w_lat_us = jnp.full(
            (C,), modes.WRITE_LATENCY_US[modes.QLC] + cfg.transfer_us, jnp.float32
        )
    busy_luns = okc * (modes.WRITE_LATENCY_US[modes.QLC] / 1000.0)
    if faults is not None:
        # graceful degradation: allocation-exhausted writes (retirement
        # pressure emptied the pool) stall their LUN for a program time so
        # the queue backs up instead of the device absorbing infinite load
        drop_alloc = w & ~ok
        busy_luns = busy_luns + jax.ops.segment_sum(
            drop_alloc.astype(jnp.float32), lun, num_segments=nL
        ) * (modes.WRITE_LATENCY_US[modes.QLC] / 1000.0)
    s = s._replace(
        l2p=l2p,
        p2l=p2l,
        page_write_ms=pwt,
        block_next=block_next,
        block_valid=block_valid,
        block_state=block_state,
        open_user=open_user,
        die_busy_ms=s.die_busy_ms + busy_luns,
        n_writes=s.n_writes + ok.sum().astype(jnp.float32),
        w_lat_hist=telemetry.record(s.w_lat_hist, w_lat_us, ok),
    )
    if faults is not None:
        # re-place the data of failed last-attempt programs onto fresh
        # block(s); anything _place_pages could not seat (pool exhausted) is
        # a dropped write — counted, never a corrupted mapping
        s = s._replace(
            n_prog_fails=s.n_prog_fails + pfail.sum().astype(jnp.float32)
        )
        s = ftl._place_pages(s, lp, refail, modes.QLC, cfg, -(-C // ppb_q) + 1)
        still = refail & (s.l2p[lp] < 0)
        n_drop = (drop_alloc.sum() + still.sum()).astype(jnp.float32)
        s = s._replace(n_dropped_writes=s.n_dropped_writes + n_drop)
    return s


def step_chunk(s: st.SSDState, req, cfg: geometry.SimConfig, has_writes: bool,
               knobs: policies.RunKnobs | None = None,
               lane_axis: str | None = None):
    """One engine step. ``req`` is ``(lpns, ops)`` for the closed-loop model
    or ``(lpns, ops, arrival_ms)`` for the open-loop arrival model. ``knobs``
    optionally supplies traced overrides for the batchable policy/wear knobs
    (sweep runner); ``None`` reads them from ``cfg`` as before.
    ``lane_axis`` is the name of the vmap axis when the step runs batched
    (sweep runner): the watermark-gated reclaim and GC passes then run in a
    chunk only when some lane of the batch needs them (``ftl.gated``), a
    lane that may not convert passes its conversions no page
    (``ftl.maybe_migrate_pages``), and the lookups index the mapping and
    timestamps 128 wide (``state.take``)."""
    lpns, ops = req[0], req[1]
    arrival = req[2] if len(req) == 3 else None
    wide = lane_axis is not None
    with jax.named_scope("step_read"):
        is_read = ops == OP_READ
        fp = flt.params_for(cfg, knobs)  # None = no fault ops traced at all
        # chunk-start write counters: the windowed WAF series is the per-chunk
        # delta of (host pages, relocated pages), not the cumulative ratio
        w_c0, r_c0 = s.n_writes, s.n_reloc_pages

        # ---------------- reads (vectorized) ----------------
        slot, blk, mode, retries, ok = lookup(s, lpns, cfg, wide)
        rd = is_read & ok
        svc_us = jnp.where(rd, retry.read_latency_us(mode, retries), 0.0)
        if fp is not None:
            # uncorrectable reads (DESIGN.md §2D): over-budget retry estimates
            # do not decode on-chip — burn the budget, then pay the recovery
            # penalty (flat ECC soft-decode, or a die-parity rebuild when
            # armed). On top of the budget path every read draws a wear-scaled
            # probabilistic uncorrectable (``read_fail_rate``). retries
            # collapses to the budget actually spent only for budget-overs so
            # the retry stats stay truthful; a probabilistic uncorrectable
            # decoded in its estimated retries before the late ECC failure.
            mrr = fp.max_read_retries
            pe_r = s.block_pe[blk]
            rated_r = modes.PE_LIMIT[mode]
            over = rd & (mrr >= 0) & (retries > mrr)
            uncorr = over | (rd & flt.read_fails(fp, slot, pe_r, rated_r))
            retries = jnp.where(over, jnp.maximum(mrr, 0), retries)
            rec_us = flt.recovery_us(fp, mode, cfg)
            svc_us = jnp.where(
                rd,
                retry.read_latency_us(mode, retries)
                + jnp.where(uncorr, rec_us, 0.0),
                0.0,
            )
            # per-lane rebuild mass: the recovery time of uncorrectable lanes
            # recovered via die-parity (split out of the retry component in the
            # obs attribution so rebuild cost is visible on its own). A
            # single-die device has no stripe peers, so parity can never
            # reconstruct there — recovery_us already fell back to the flat
            # penalty and the rebuild lane must stay empty
            if cfg.n_dies > 1:
                is_rb = uncorr & (fp.parity_rebuild > 0)
            else:
                is_rb = jnp.zeros_like(uncorr)
            rb_lane_us = jnp.where(is_rb, rec_us, 0.0)
        else:
            uncorr = None
            rb_lane_us = jnp.zeros_like(svc_us)
        xfer_us = jnp.where(rd, cfg.transfer_us, 0.0)
        die = cfg.die_of_block(blk)
        chan = cfg.channel_of_die(die)

        # ---------------- open-loop queueing (DESIGN.md §2C) ----------------
        if arrival is not None:
            scale = (
                jnp.float32(1.0)
                if knobs is None or knobs.arrival_scale is None
                else knobs.arrival_scale.astype(jnp.float32)
            )
            t_arr = arrival / scale  # scale multiplies the offered rate
            wv = (ops == OP_WRITE) & (lpns >= 0)
            active = rd | wv
            q_die = jnp.where(rd, die, jnp.maximum(lpns, 0) % cfg.n_dies).astype(jnp.int32)
            # die occupancy: sense+retries for reads, page program for writes —
            # the same terms the closed-loop model books into die_busy_ms.
            occ_us = jnp.where(rd, svc_us, modes.WRITE_LATENCY_US[modes.QLC])
            if cfg.chan_model == "lattice":
                # two-resource tandem: sense/program queues on the die, then the
                # page transfer queues on the die's channel bus
                die_dep, chan_dep, die_avail, chan_avail = _tandem_departures(
                    s.die_avail_ms, s.chan_avail_ms, t_arr,
                    jnp.where(active, occ_us, 0.0) / 1000.0,
                    jnp.where(active, cfg.transfer_us, 0.0) / 1000.0,
                    q_die, cfg.channel_of_die(q_die), rd, active,
                    cfg.n_dies, cfg.n_channels,
                )
                dep_ms = jnp.where(rd, chan_dep, die_dep)
                sojourn_us = jnp.where(
                    rd,
                    (chan_dep - t_arr) * 1000.0,
                    (die_dep - t_arr) * 1000.0 + cfg.transfer_us,
                )
                queue_us = jnp.maximum((die_dep - t_arr) * 1000.0 - occ_us, 0.0)
                chanw_us = jnp.where(
                    rd,
                    jnp.maximum((chan_dep - die_dep) * 1000.0 - cfg.transfer_us,
                                0.0),
                    0.0,
                )
            else:
                # legacy: channel transfer is appended to the recorded latency
                # but does not occupy a resource (it overlaps the next sense)
                dep_ms, die_avail = _queue_departures(
                    s.die_avail_ms, t_arr, jnp.where(active, occ_us, 0.0) / 1000.0,
                    q_die, active, cfg.n_dies,
                )
                chan_avail = s.chan_avail_ms
                sojourn_us = (dep_ms - t_arr) * 1000.0 + cfg.transfer_us
                queue_us = jnp.maximum(sojourn_us - occ_us - cfg.transfer_us, 0.0)
                chanw_us = jnp.zeros_like(queue_us)
            rec_lat_us = sojourn_us  # queue + sense/retry (or program) + wait + xfer
            chunk_q = jnp.where(rd, queue_us, 0.0).sum() / 1000.0
            chunk_chanw = jnp.where(rd, chanw_us, 0.0).sum() / 1000.0
            chunk_svc = jnp.where(rd, rec_lat_us, 0.0).sum() / 1000.0
            chunk_hist = telemetry.record(
                jnp.zeros((telemetry.N_LAT_BINS,), jnp.float32), rec_lat_us, rd
            )
        else:
            chunk_q = jnp.float32(0.0)
            chunk_chanw = jnp.float32(0.0)
            chunk_svc = (svc_us + xfer_us).sum() / 1000.0
            chunk_hist = telemetry.record(
                jnp.zeros((telemetry.N_LAT_BINS,), jnp.float32), svc_us + xfer_us, rd
            )

        die_add = jax.ops.segment_sum(svc_us, die, num_segments=cfg.n_dies) / 1000.0
        chan_add = jax.ops.segment_sum(xfer_us, chan, num_segments=cfg.n_channels) / 1000.0
        chunk_reads = rd.sum().astype(jnp.float32)
        chunk_retries = jnp.where(rd, retries, 0).sum().astype(jnp.float32)

        s = s._replace(
            die_busy_ms=s.die_busy_ms + die_add,
            chan_busy_ms=s.chan_busy_ms + chan_add,
            block_reads=s.block_reads
            + jax.ops.segment_sum(rd.astype(jnp.int32), blk, num_segments=cfg.n_blocks),
            svc_sum_ms=s.svc_sum_ms + chunk_svc,
            q_sum_ms=s.q_sum_ms + chunk_q,
            chanq_sum_ms=s.chanq_sum_ms + chunk_chanw,
            n_reads=s.n_reads + chunk_reads,
            n_retries=s.n_retries + chunk_retries,
            lat_hist=s.lat_hist + chunk_hist,
        )
        if uncorr is not None:
            # die-parity rebuild accounting (DESIGN.md §2D): every rebuilt lane
            # counts; a second uncorrectable among the stripe peers during the
            # rebuild is true data loss (the sim keeps serving the stale page —
            # no mapping entry is harmed, only the counter records it)
            n_rb = is_rb.sum().astype(jnp.float32)
            if cfg.n_dies > 1:
                loss = is_rb & flt.rebuild_second_fault(
                    fp, slot, pe_r, rated_r, cfg.n_dies - 1
                )
                n_dl = loss.sum().astype(jnp.float32)
            else:
                n_dl = jnp.float32(0.0)
            s = s._replace(
                n_uncorrectable=s.n_uncorrectable + uncorr.sum().astype(jnp.float32),
                n_rebuilds=s.n_rebuilds + n_rb,
                n_data_loss=s.n_data_loss + n_dl,
            )

        # ---------------- observability: read-path attribution ----------------
        if obs.enabled(cfg):
            # decompose each recorded read into queue / sense / retry / transfer
            # components; the binning latency is exactly what lat_hist records,
            # so the per-mode count histograms sum back to it bit for bit
            base_us = jnp.where(rd, modes.READ_LATENCY_US[mode], 0.0)
            if arrival is not None:
                q_us = jnp.where(rd, queue_us, 0.0)
                cw_us = jnp.where(rd, chanw_us, 0.0)
                t_read_ms = dep_ms  # window by each read's own departure time
                lat_us = rec_lat_us
            else:
                q_us = jnp.zeros_like(svc_us)
                cw_us = jnp.zeros_like(svc_us)
                t_read_ms = jnp.broadcast_to(s.clock_ms, svc_us.shape)
                lat_us = svc_us + xfer_us
            s = obs.record_reads(
                s, cfg, mode=mode, rd=rd, lat_us=lat_us, queue_us=q_us,
                sense_us=base_us, retry_us=svc_us - base_us - rb_lane_us,
                chanw_us=cw_us, xfer_us=xfer_us, retries=retries, t_ms=t_read_ms,
                uncorr=uncorr, rebuild_us=rb_lane_us,
            )
            obs0 = (s.n_writes, s.n_conversions.sum(), s.n_erases,
                    s.n_migrated_pages, s.n_reloc_pages)

    # ---------------- heat update ----------------
    with jax.named_scope("step_heat"):
        touched = rd | (ops == OP_WRITE)
        heat = hotness.decay_heat(s.heat, cfg.heat)
        heat = heat.at[jnp.where(touched, lpns, cfg.n_logical)].add(1.0, mode="drop")
        s = s._replace(heat=heat)

    # ---------------- user writes ----------------
    if has_writes:
        with jax.named_scope("step_write"):
            w_hist0 = s.w_lat_hist
            s = write_path_batched(
                s, lpns, ops == OP_WRITE, cfg,
                w_lat_us=rec_lat_us if arrival is not None else None,
                faults=fp,
            )
            chunk_w_hist = s.w_lat_hist - w_hist0
    else:
        chunk_w_hist = jnp.zeros((telemetry.N_LAT_BINS,), jnp.float32)

    # background FTL work from here on (migrations/reclaim/GC) extends the
    # die availability clocks: the next chunk's arrivals queue behind it
    busy_mark = s.die_busy_ms

    # die-parity rebuild peer charges (DESIGN.md §2D): each rebuilt read
    # senses the stripe's peer dies and moves their pages over the channel
    # buses. The victim lane already carries the critical path in its own
    # recorded latency (``recovery_us``); here the *peer* resources are
    # charged on the timing lattice like any background work — a sense per
    # peer die, a transfer per peer page on its channel — so subsequent
    # arrivals queue behind the rebuild. With ``parity_rebuild`` off (or a
    # one-die geometry) every charge is exactly 0.0 and the clocks are
    # untouched bit for bit.
    with jax.named_scope("step_read"):
        if fp is not None and cfg.n_dies > 1:
            rb_sense_us = jnp.where(is_rb, modes.READ_LATENCY_US[mode], 0.0)
            own_sense = jax.ops.segment_sum(rb_sense_us, die,
                                            num_segments=cfg.n_dies)
            rb_die_ms = (rb_sense_us.sum() - own_sense) / 1000.0
            n_rb_chan = jax.ops.segment_sum(
                is_rb.astype(jnp.float32), chan, num_segments=cfg.n_channels
            )
            rb_chan_ms = (
                (is_rb.sum().astype(jnp.float32) * cfg.luns_per_channel - n_rb_chan)
                * cfg.transfer_us
            ) / 1000.0
            s = s._replace(
                die_busy_ms=s.die_busy_ms + rb_die_ms,
                chan_busy_ms=s.chan_busy_ms + rb_chan_ms,
            )
            if arrival is not None and cfg.chan_model == "lattice":
                chan_avail = chan_avail + rb_chan_ms

    # ---------------- policy: conversion migrations ----------------
    if cfg.policy != geometry.BASELINE:
        with jax.named_scope("step_convert"):
            # dedup of the chunk's read set: one int32 sort + adjacent-equal
            # mask. Replaces jnp.unique(size=chunk) (~9x slower: it layers
            # cumsum/scatter compaction on top of the same sort). Masked lanes
            # sort to the top as n_logical and drop to -1; survivors stay in
            # ascending LPN order, so heat ties in the top-k below break
            # identically to the jnp.unique ordering. (A sort-free scatter-mark
            # on an (L,)-sized scratch was measured slower: the per-chunk fill
            # of the scratch dominates at real geometry.)
            srt = jnp.sort(jnp.where(rd, lpns, cfg.n_logical))
            dup = jnp.concatenate([jnp.zeros((1,), bool), srt[1:] == srt[:-1]])
            uniq = jnp.where((srt >= cfg.n_logical) | dup, -1, srt)
            slot_u, blk_u, mode_u, retr_u, ok_u = lookup(s, uniq, cfg, wide)
            heat_u = s.heat[jnp.maximum(uniq, 0)]
            sel = policies.select_migrations(
                cfg, uniq, mode_u, retr_u, heat_u, ok_u, s.block_pe[blk_u], knobs=knobs
            )
            for tgt in (modes.SLC, modes.TLC):
                s = ftl.maybe_migrate_pages(s, sel[tgt], tgt, cfg, faults=fp,
                                            lane_axis=lane_axis)

        # ---------------- elastic capacity recovery ----------------
        with jax.named_scope("step_reclaim"):
            if cfg.reclaim_enabled:
                cls_rd = hotness.classify(s.heat[jnp.maximum(lpns, 0)], cfg.heat)
                hw = rd & (cls_rd >= modes.WARM)
                touched_blk = (
                    jax.ops.segment_max(
                        hw.astype(jnp.int32), blk, num_segments=cfg.n_blocks
                    )
                    > 0
                )
                s = s._replace(
                    block_cold_age=jnp.where(touched_blk, 0, s.block_cold_age + 1)
                )
                free_frac = ftl.free_block_count(s) / cfg.n_blocks
                rcfg = reclaim.ReclaimConfig(max_per_pass=cfg.max_conversions_per_chunk)

                def _reclaim_pass(s):
                    # Per-block residual heat = max heat over the block's valid
                    # pages (the demotion tie-breaker: among equally long-cold
                    # blocks, the one with the least residual heat demotes
                    # first). The full-device segment_max is hoisted here so it
                    # runs once per pass and — via the pressure cond below — only
                    # when a demotion can actually fire.
                    slot_blk = (
                        jnp.arange(cfg.n_slots, dtype=jnp.int32) // cfg.slots_per_block
                    )
                    page_heat = jnp.where(s.p2l >= 0, s.heat[jnp.maximum(s.p2l, 0)], 0.0)
                    block_heat = jnp.maximum(
                        jax.ops.segment_max(page_heat, slot_blk, num_segments=cfg.n_blocks),
                        0.0,
                    )
                    victims, v_ok, v_tgt = reclaim.score_victims(
                        s, cfg, reclaim.DEMOTION, block_heat=block_heat,
                        free_frac=free_frac, reclaim_cfg=rcfg,
                    )
                    return ftl.reclaim_victims(s, victims, v_ok, v_tgt, cfg,
                                               faults=fp)

                s, opened = ftl.gated(
                    free_frac < rcfg.low_watermark, _reclaim_pass, s, lane_axis
                )
                s = obs.record_gate(s, cfg, obs.GATE_RECLAIM, opened)

    # ---------------- GC (fused multi-victim, deficit-aware) ----------------
    with jax.named_scope("step_gc"):
        s = ftl.gc_step(s, cfg, faults=fp, knobs=knobs, lane_axis=lane_axis)

    # clock follows the busiest die (device saturated under FIO load)
    with jax.named_scope("step_account"):
        s = s._replace(clock_ms=jnp.maximum(s.clock_ms, s.die_busy_ms.max()))

        if arrival is not None:
            # block the next chunk's arrivals behind this chunk's background
            # work, and let wall time follow real arrivals (idle gaps age pages)
            die_avail = die_avail + (s.die_busy_ms - busy_mark)
            s = s._replace(
                die_avail_ms=die_avail,
                chan_avail_ms=chan_avail,
                clock_ms=jnp.maximum(
                    s.clock_ms,
                    jnp.maximum(t_arr[-1],
                                jnp.maximum(die_avail.max(), chan_avail.max())),
                ),
            )

        # ---------------- observability: per-window chunk series ----------------
        if obs.enabled(cfg):
            s = obs.record_chunk(
                s, cfg, t_ms=s.clock_ms,
                writes=s.n_writes - obs0[0],
                conversions=s.n_conversions.sum() - obs0[1],
                erases=s.n_erases - obs0[2],
                migrated=s.n_migrated_pages - obs0[3],
                reloc=s.n_reloc_pages - obs0[4],
            )

        nonfree = s.block_state != st.FREE
        mode_hist = jax.ops.segment_sum(
            nonfree.astype(jnp.int32), s.block_mode, num_segments=3
        )
        y = ChunkMetrics(
            capacity_pages=st.usable_capacity_pages(s, cfg),
            free_blocks=ftl.free_block_count(s),
            mode_hist=mode_hist,
            reads=chunk_reads,
            retries=chunk_retries,
            svc_ms=chunk_svc,
            migrated=s.n_migrated_pages,
            lat_hist=chunk_hist,
            w_lat_hist=chunk_w_hist,
            q_ms=chunk_q,
            chanq_ms=chunk_chanw,
            user_pages=s.n_writes - w_c0,
            reloc_pages=s.n_reloc_pages - r_c0,
        )
    return s, y


@partial(jax.jit, static_argnums=(0, 3))
def _run_jit(cfg: geometry.SimConfig, lpns, ops, has_writes: bool):
    s0 = st.init_state(cfg)

    def body(s, x):
        return step_chunk(s, x, cfg, has_writes)

    return lax.scan(body, s0, (lpns, ops))


@partial(jax.jit, static_argnums=(0, 4))
def _run_open_jit(cfg: geometry.SimConfig, lpns, ops, arrival_ms,
                  has_writes: bool):
    s0 = st.init_state(cfg)

    def body(s, x):
        return step_chunk(s, x, cfg, has_writes)

    return lax.scan(body, s0, (lpns, ops, arrival_ms))


def run(cfg: geometry.SimConfig, trace, has_writes: bool | None = None):
    """Run a full trace. ``trace`` is a dict with 'lpn' and 'op' arrays of
    shape (n_chunks, cfg.chunk); an optional 'arrival_ms' array of the same
    shape switches the engine to the open-loop arrival model. Returns
    (final_state, ChunkMetrics stacked).
    """
    with jax.profiler.TraceAnnotation("repro.run.put"):
        if has_writes is None:
            has_writes = bool((trace["op"] == OP_WRITE).any())
        lpns = jnp.asarray(trace["lpn"], jnp.int32)
        ops = jnp.asarray(trace["op"], jnp.int32)
        arr = (jnp.asarray(trace["arrival_ms"], jnp.float32)
               if "arrival_ms" in trace else None)
    with jax.profiler.TraceAnnotation("repro.run.dispatch"):
        if arr is not None:
            return _run_open_jit(cfg, lpns, ops, arr, has_writes)
        return _run_jit(cfg, lpns, ops, has_writes)


@partial(jax.profiler.annotate_function, name="repro.summarize")
def summarize(s: st.SSDState, cfg: geometry.SimConfig, threads: int = 4):
    """Headline numbers for the paper's figures.

    Every value is JSON-safe (floats and nested lists only — no ndarrays,
    no nested dicts): the sweep runner writes the dict straight to
    ``summaries.json`` and ``assert_results_identical`` np.asarray's each
    value, so both representations must round-trip."""
    import numpy as np

    n_reads = float(s.n_reads)
    # under the open-loop model elapsed time is the last die- (or, lattice,
    # channel-) availability clock (includes idle gaps); closed-loop
    # die_avail_ms/chan_avail_ms stay 0 so the busy-time makespan is
    # unchanged. Host-side numpy on purpose: the sweep runner hands this
    # function device_get'ed numpy leaves and summarize must not enqueue
    # device work behind them (DESIGN.md §7.3).
    makespan_ms = float(
        max(np.max(s.die_busy_ms), np.max(s.chan_busy_ms),
            np.max(s.die_avail_ms), np.max(s.chan_avail_ms))
    )
    mean_lat_ms = float(s.svc_sum_ms) / max(n_reads, 1.0)
    if threads == 1:
        # synchronous single-thread: no inter-LUN overlap; background work
        # (migrations/GC) still steals device time via the makespan term.
        iops = 1000.0 / mean_lat_ms if mean_lat_ms > 0 else 0.0
    else:
        iops = n_reads / max(makespan_ms / 1000.0, 1e-9)
    cap = float(st.capacity_gib(s, cfg, xp=np))
    init_cap = cfg.n_blocks * cfg.slots_per_block * cfg.page_bytes / 2**30
    pct = telemetry.percentiles(s.lat_hist)
    wpct = telemetry.percentiles(s.w_lat_hist)
    # ---- endurance / WAF telemetry (DESIGN.md §2E) ----
    user_pages = float(s.n_writes)
    reloc_pages = float(s.n_reloc_pages)
    waf = (user_pages + reloc_pages) / user_pages if user_pages > 0 else 1.0
    block_pe = np.asarray(s.block_pe, np.float64)
    live = ~np.asarray(s.block_bad)
    pe_live = block_pe[live] if live.any() else block_pe
    block_mode_h = np.asarray(s.block_mode)
    pe_mean_by_mode = []
    for m in range(modes.N_MODES):
        sel = live & (block_mode_h == m)
        pe_mean_by_mode.append(float(block_pe[sel].mean()) if sel.any() else 0.0)
    # lifetime projection: rated QLC endurance (the device's native mode)
    # over the observed host write rate, discounted by the measured WAF
    cap_bytes = cap * 2**30
    tbw = modes.tbw_bytes(cap_bytes, modes.RATED_PE[modes.QLC], waf)
    host_bytes_per_day = (user_pages * cfg.page_bytes
                          / max(makespan_ms, 1e-9) * 86_400_000.0)
    # ---- spare pool / degraded-mode accounting (DESIGN.md §2D) ----
    pool_total = int(s.spare_total)
    bounded = pool_total < 2**30  # st.SPARE_UNLIMITED sentinel
    spares_total = float(pool_total) if bounded else -1.0
    spares_remaining = float(s.spare_count) if bounded else -1.0
    qlc_ppb = int(geometry.pages_per_block_host(cfg)[modes.QLC])
    spare_covered_gib = (
        min(float(s.bad_count), float(pool_total)) * qlc_ppb * cfg.page_bytes
        / 2**30
        if bounded
        else float(s.bad_count) * qlc_ppb * cfg.page_bytes / 2**30
    )
    degraded_flag = 1.0 if bounded and int(s.spare_count) <= 0 else 0.0
    return dict(
        iops=iops,
        mean_read_latency_us=mean_lat_ms * 1000.0,
        read_lat_p50_us=pct[0.5],
        read_lat_p95_us=pct[0.95],
        read_lat_p99_us=pct[0.99],
        read_lat_p999_us=pct[0.999],
        write_lat_p50_us=wpct[0.5],
        write_lat_p95_us=wpct[0.95],
        write_lat_p99_us=wpct[0.99],
        write_lat_p999_us=wpct[0.999],
        read_queue_delay_us=float(s.q_sum_ms) / max(n_reads, 1.0) * 1000.0,
        read_chan_wait_us=float(s.chanq_sum_ms) / max(n_reads, 1.0) * 1000.0,
        retries_per_read=float(s.n_retries) / max(n_reads, 1.0),
        capacity_gib=cap,
        capacity_loss_gib=init_cap - cap,
        migrated_pages=float(s.n_migrated_pages),
        erases=float(s.n_erases),
        conversions=np.asarray(s.n_conversions).tolist(),
        reads=n_reads,
        writes=float(s.n_writes),
        # fault / recovery accounting (DESIGN.md §2D); all exactly 0.0 when
        # fault injection is off
        uncorrectable_reads=float(s.n_uncorrectable),
        prog_fails=float(s.n_prog_fails),
        erase_fails=float(s.n_erase_fails),
        dropped_writes=float(s.n_dropped_writes),
        bad_blocks=float(s.bad_count),
        # wear / rebuild / spare-pool accounting (DESIGN.md §2D): spares_*
        # report -1.0 for an unbounded pool; ``spare_covered_gib`` is the
        # retired capacity the over-provisioning pool backfills, so
        # ``effective_capacity_gib`` is what the host still sees
        rebuilds=float(s.n_rebuilds),
        data_loss=float(s.n_data_loss),
        degraded_writes=float(s.n_degraded_writes),
        spares_total=spares_total,
        spares_remaining=spares_remaining,
        spare_covered_gib=spare_covered_gib,
        effective_capacity_gib=cap + spare_covered_gib,
        degraded=degraded_flag,
        # endurance / WAF (DESIGN.md §2E); waf pins to 1.0 and
        # lifetime_years to 0.0 when the run had no host writes
        user_pages=user_pages,
        reloc_pages=reloc_pages,
        waf=waf,
        pe_mean=float(pe_live.mean()),
        pe_variance=float(pe_live.var()),
        pe_max=float(pe_live.max()),
        pe_mean_by_mode=pe_mean_by_mode,
        tbw_gib=tbw / 2**30,
        dwpd=modes.dwpd(host_bytes_per_day, cap_bytes) if user_pages > 0 else 0.0,
        lifetime_years=(modes.lifetime_years(tbw, host_bytes_per_day)
                        if user_pages > 0 else 0.0),
        **obs.summary(s, cfg),
    )
