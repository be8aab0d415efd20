"""FTL mechanics: block allocation, block-granularity migration/conversion
(paper Fig. 8-10), fused multi-victim GC, fused reclaim demotion. Everything
is jit-safe with static shapes; per-block operations work on the block's
fixed slots_per_block window.

Background block relocation — GC relocation, reclaim demotion and block
conversion — is ONE code path (DESIGN.md §2A): :func:`relocate_group`
gathers the victims' valid pages, books their Eq.-3 read cost, places them
through the shared :func:`_place_pages` core and erases every victim in one
vectorized :func:`_erase_many`. The original scalar single-victim path
survives only as ``gc_pass_reference`` / ``_migrate_block_reference`` (the
behavioral reference for the bit-identity tests, like
``engine.write_path_reference``).

Scatter discipline: masked-out lanes are redirected to an out-of-range index
and dropped (``mode='drop'``) — never write a dummy in-range index, because
duplicate-index ``set`` conflicts are unordered in XLA.

Free-pool bookkeeping (DESIGN.md §2A): ``SSDState.free_count`` is the exact
number of FREE blocks, incremented per erased victim by ``_erase_many`` and
decremented at the two places a FREE block is opened (``_place_pages`` and
the engine write path). ``SSDState.free_hint`` holds one candidate free
block per LUN, refreshed on erase; ``alloc_free_block`` trusts a hint only
after re-checking ``block_state`` and falls back to the O(n_blocks) scan
when no hint is live.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import faults as flt
from repro.core import modes, reclaim, retry
from repro.ssdsim import geometry, obs, state as st

# Max destination blocks one conversion can need: one partially-filled open
# migration block plus ceil(1024/256) = 4 fresh SLC blocks.
MAX_DEST = 5


# upper bound on per-block P/E for the youngest-first composite key: the
# die-affinity bonus must dominate any wear difference, so P/E clips here
_ALLOC_PE_CAP = 1 << 22


def _alloc_scan(s: st.SSDState, prefer_lun=None, cfg: geometry.SimConfig | None = None):
    """Full block_state scan (slow path): free block, prefer matching LUN."""
    free = s.block_state == st.FREE
    if prefer_lun is not None:
        blk = jnp.arange(s.block_mode.shape[0], dtype=jnp.int32)
        lun_match = cfg.die_of_block(blk) == prefer_lun
        score = free.astype(jnp.int32) * 2 + (free & lun_match).astype(jnp.int32)
    else:
        score = free.astype(jnp.int32)
    idx = jnp.argmax(score).astype(jnp.int32)
    return jnp.where(score[idx] > 0, idx, -1)


def _alloc_scan_youngest(s: st.SSDState, prefer_lun=None,
                         cfg: geometry.SimConfig | None = None):
    """Wear-levelled scan: the lowest-P/E free block, die affinity first.

    Composite argmin key ``mismatch * CAP + pe`` — a die-matching block
    always beats a mismatched one, wear breaks the tie within each class,
    and ``argmin`` resolves equal wear to the lowest block id (the same
    tie-break the lowest-id scan uses)."""
    free = s.block_state == st.FREE
    pe = jnp.clip(s.block_pe, 0, _ALLOC_PE_CAP - 1)
    if prefer_lun is not None:
        blk = jnp.arange(s.block_mode.shape[0], dtype=jnp.int32)
        mismatch = (cfg.die_of_block(blk) != prefer_lun).astype(jnp.int32)
        key = mismatch * _ALLOC_PE_CAP + pe
    else:
        key = pe
    key = jnp.where(free, key, jnp.iinfo(jnp.int32).max)
    idx = jnp.argmin(key).astype(jnp.int32)
    return jnp.where(free[idx], idx, -1)


def alloc_free_block(s: st.SSDState, prefer_lun=None, cfg: geometry.SimConfig | None = None):
    """Index of a free block (prefer matching LUN), or -1 if none.

    O(1) fast path through the per-LUN free hints; the hint is validated
    against ``block_state`` (hints go stale when consumed) and the full scan
    runs only when it is dead. With ``prefer_lun`` only that LUN's hint is
    trusted, so LUN affinity is never worse than the scan's.

    ``cfg.alloc_policy == "youngest"`` (wear-levelled allocation) always
    takes the full scan — a hint is *a* free block on the die, not the
    youngest one — picking the lowest-P/E free block with die affinity
    intact. The default ``"lowest_id"`` path is untouched (pinned
    bit-identical by tests/test_wearout.py).
    """
    if cfg is not None and cfg.alloc_policy == "youngest":
        return _alloc_scan_youngest(s, prefer_lun, cfg)
    hints = s.free_hint
    live = (hints >= 0) & (s.block_state[jnp.maximum(hints, 0)] == st.FREE)
    if prefer_lun is not None:
        h = hints[prefer_lun]
        hit = live[prefer_lun]
    else:
        j = jnp.argmax(live)
        h = hints[j]
        hit = live[j]
    return lax.cond(
        hit,
        lambda: h.astype(jnp.int32),
        lambda: _alloc_scan(s, prefer_lun, cfg),
    )


def free_block_count(s: st.SSDState):
    """Exact FREE-block count, O(1) via the incremental bookkeeping."""
    return s.free_count


def _book_rebuilds(s: st.SSDState, faults: flt.FaultParams, uncorr, slots,
                   pe, rated, cfg: geometry.SimConfig):
    """Book one batch of uncorrectable reads: count them and, with parity
    rebuild armed, the stripe reconstructions they trigger plus any
    second-fault data loss among the peer reads (DESIGN.md §2D). Shared by
    the background relocation readers; the engine's user read path performs
    the same accounting inline (it additionally charges the peer dies on
    the timing lattice)."""
    n_unc = uncorr.sum().astype(jnp.float32)
    on = faults.parity_rebuild > 0
    if cfg.n_dies > 1:
        n_rb = jnp.where(on, n_unc, 0.0)
        loss = uncorr & on & flt.rebuild_second_fault(
            faults, slots, pe, rated, cfg.n_dies - 1)
        n_dl = loss.sum().astype(jnp.float32)
    else:  # no stripe peers -> no rebuild, no loss
        n_rb = jnp.float32(0.0)
        n_dl = jnp.float32(0.0)
    return s._replace(
        n_uncorrectable=s.n_uncorrectable + n_unc,
        n_rebuilds=s.n_rebuilds + n_rb,
        n_data_loss=s.n_data_loss + n_dl,
    )


def _erase_many(s: st.SSDState, victims, grp, cfg: geometry.SimConfig,
                faults: flt.FaultParams | None = None):
    """Erase every ``grp``-masked victim block in one vectorized pass:
    masked per-victim slot-window clears for ``p2l``, masked per-block
    scatters reset the block metadata, a ``segment_sum`` books per-LUN
    erase latency, and a per-LUN "any erased block" reduction refreshes
    ``free_hint``.

    The single production erase primitive (GC, reclaim and conversion all
    reach it through :func:`relocate_group`); bit-identical to the scalar
    ``_erase`` reference for a single victim, and ~2x cheaper than the K
    sequential ``lax.cond(_erase)`` scatters it replaced. The ``p2l`` clear
    is a static unroll of masked ``dynamic_update_slice`` windows rather
    than one K*spb-index scatter: each victim's slots are contiguous, and
    on XLA:CPU a slice memcpy beats the general per-element scatter by ~4x
    (a masked-out lane writes its current window back, a no-op).

    With ``faults`` active (DESIGN.md §2D), each attempted erase draws a
    deterministic failure keyed on (block, P/E): a failed block is retired
    to ``BAD`` / ``block_bad`` instead of returning to the free pool — it
    never becomes an allocation hint, never counts toward ``free_count``,
    and ``alloc_free_block`` skips it forever (the scan only matches
    ``FREE``). The erase latency and P/E bump are still paid (the op was
    attempted) and the slot/metadata clears still run, so a retired block
    carries no mapped pages — exactly what ``check_invariants`` asserts.
    """
    spb = cfg.slots_per_block
    B = s.block_mode.shape[0]
    vb = jnp.maximum(victims, 0)
    bdrop = jnp.where(grp, vb, B)  # B = out of range -> dropped
    p2l = s.p2l
    neg = jnp.full((spb,), -1, jnp.int32)
    for i in range(victims.shape[0]):
        cur = lax.dynamic_slice(p2l, (vb[i] * spb,), (spb,))
        p2l = lax.dynamic_update_slice(
            p2l, jnp.where(grp[i], neg, cur), (vb[i] * spb,)
        )
    die = cfg.die_of_block(vb)
    erase_ms = jnp.where(grp, modes.ERASE_LATENCY_US[s.block_mode[vb]] / 1000.0, 0.0)
    if cfg.chan_model == "lattice" and cfg.planes_per_lun > 1:
        # multi-plane erase overlap: co-scheduled plane erases on one die
        # pay the max of the per-plane times, not their sum
        per_plane = jax.ops.segment_sum(
            erase_ms, cfg.plane_slot_of_block(vb),
            num_segments=cfg.n_dies * cfg.planes_per_die,
        )
        die_erase = per_plane.reshape(cfg.n_dies, cfg.planes_per_die).max(1)
    else:
        die_erase = jax.ops.segment_sum(erase_ms, die, num_segments=cfg.n_dies)
    if faults is not None:
        fail = grp & flt.erase_fails(
            faults, flt.block_entity(vb, cfg.n_dies, cfg.planes_per_die),
            s.block_pe[vb], modes.PE_LIMIT[s.block_mode[vb]],
        )
    else:
        fail = jnp.zeros_like(grp)
    freed = grp & ~fail
    # any *freed* block on the die is a valid allocation hint; take the max
    # id (retired blocks must never become hints)
    hint_cand = jax.ops.segment_max(
        jnp.where(freed, vb, -1), die, num_segments=cfg.n_dies
    )
    n_free = freed.sum().astype(jnp.int32)
    n_fail = fail.sum().astype(jnp.int32)
    src_mode = s.block_mode[vb]
    s = s._replace(
        p2l=p2l,
        block_pe=s.block_pe.at[bdrop].add(1, mode="drop"),
        block_reads=s.block_reads.at[bdrop].set(0, mode="drop"),
        block_state=s.block_state.at[bdrop].set(
            jnp.where(fail, st.BAD, st.FREE).astype(s.block_state.dtype),
            mode="drop",
        ),
        block_next=s.block_next.at[bdrop].set(0, mode="drop"),
        block_valid=s.block_valid.at[bdrop].set(0, mode="drop"),
        block_cold_age=s.block_cold_age.at[bdrop].set(0, mode="drop"),
        block_bad=s.block_bad.at[jnp.where(fail, vb, B)].set(True, mode="drop"),
        bad_count=s.bad_count + n_fail,
        # each retirement consumes an over-provisioning spare until the pool
        # runs dry (invariant: spare_count == max(total - bad, 0))
        spare_count=jnp.maximum(s.spare_count - n_fail, 0),
        free_count=s.free_count + n_free,
        free_hint=jnp.where(hint_cand >= 0, hint_cand.astype(jnp.int32), s.free_hint),
        die_busy_ms=s.die_busy_ms + die_erase,
        n_erases=s.n_erases + grp.sum().astype(jnp.float32),
        n_erase_fails=s.n_erase_fails + n_fail.astype(jnp.float32),
    )
    if faults is not None and obs.full(cfg):
        zeros = jnp.zeros(vb.shape, jnp.float32)
        s = obs.record_events(
            s, cfg,
            mask=fail,
            block=vb,
            from_mode=src_mode,
            to_mode=src_mode,
            reason=obs.REASON_BAD_BLOCK,
            retry_est=zeros,
            pages=zeros,
        )
    return s


def _erase(s: st.SSDState, blk, cfg: geometry.SimConfig):
    """Erase ``blk``: invalidate slots, bump P/E, return to free pool.

    Reference-only (the sequential half of ``_migrate_block_reference``);
    production relocation erases through :func:`_erase_many`.
    """
    spb = cfg.slots_per_block
    mode = s.block_mode[blk]
    p2l = lax.dynamic_update_slice(s.p2l, jnp.full((spb,), -1, jnp.int32), (blk * spb,))
    die = cfg.die_of_block(blk)
    erase_ms = modes.ERASE_LATENCY_US[mode] / 1000.0
    return s._replace(
        p2l=p2l,
        block_pe=s.block_pe.at[blk].add(1),
        block_reads=s.block_reads.at[blk].set(0),
        block_state=s.block_state.at[blk].set(st.FREE),
        block_next=s.block_next.at[blk].set(0),
        block_valid=s.block_valid.at[blk].set(0),
        block_cold_age=s.block_cold_age.at[blk].set(0),
        free_count=s.free_count + 1,
        free_hint=s.free_hint.at[die].set(blk.astype(jnp.int32)),
        die_busy_ms=s.die_busy_ms.at[die].add(erase_ms),
        n_erases=s.n_erases + 1.0,
    )


def _place_pages(s: st.SSDState, lpns, valid, tgt_mode, cfg: geometry.SimConfig,
                 n_dest: int, wide: bool = False):
    """Append the ``valid``-masked ``lpns`` into open migration block(s) of
    ``tgt_mode``, opening up to ``n_dest`` fresh blocks from the free pool.

    Shared placement core of page migration and the fused relocation kernel
    — besides the engine write path this is the only place FREE blocks are
    consumed, so the free-pool bookkeeping lives here once. Callers
    invalidate (or erase) the source slots themselves.

    The ``n_dest`` unroll carries only scalar per-block bookkeeping
    (allocation, block_next/valid/state, busy time) and accumulates each
    lane's destination slot; every lane is placed in exactly one iteration,
    so the expensive full-array scatters (l2p/p2l/page timestamps) happen
    once after the loop instead of once per destination — the unroll cost
    no longer scales with the lane count. ``wide`` does those scatters
    through the 128-wide views (:func:`state.put`).
    """
    spb = cfg.slots_per_block
    ppb = geometry.pages_per_block(cfg)
    S, L = cfg.n_slots, cfg.n_logical

    lp_safe = jnp.maximum(lpns, 0)
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1  # rank of each valid page
    n_valid = valid.sum()
    consumed = jnp.int32(0)
    dest_slot = jnp.full(lpns.shape, S, jnp.int32)  # S = dropped
    # lattice multi-plane overlap: defer the program charges and fold
    # co-scheduled plane programs on one die to their max after the unroll
    # (legacy — and any single-plane geometry — keeps the sequential
    # per-iteration adds, preserving float association bit for bit)
    overlap = cfg.chan_model == "lattice" and cfg.planes_per_lun > 1
    prog_blocks: list = []
    prog_ms: list = []
    for _ in range(n_dest):
        cur = s.open_mig[tgt_mode]
        fresh = cur < 0
        a = alloc_free_block(s, cfg=cfg)
        d = jnp.where(fresh, a, cur)
        dd = jnp.maximum(d, 0)  # safe index; all writes masked when d < 0
        start = s.block_next[dd]
        usable = jnp.where(d >= 0, ppb[tgt_mode] - start, 0)
        take = jnp.clip(n_valid - consumed, 0, usable)
        opened = (take > 0) & (d >= 0)
        sel = valid & (pos >= consumed) & (pos < consumed + take) & opened
        dest_slot = jnp.where(sel, dd * spb + start + (pos - consumed), dest_slot)

        write_ms = take * modes.WRITE_LATENCY_US[tgt_mode] / 1000.0
        is_full = start + take >= ppb[tgt_mode]
        if overlap:
            prog_blocks.append(dd)
            prog_ms.append(write_ms)
            busy = s.die_busy_ms
        else:
            busy = s.die_busy_ms.at[cfg.die_of_block(dd)].add(write_ms)
        s = s._replace(
            block_mode=s.block_mode.at[dd].set(
                jnp.where(opened, tgt_mode, s.block_mode[dd])
            ),
            block_state=s.block_state.at[dd].set(
                jnp.where(opened, jnp.where(is_full, st.FULL, st.OPEN),
                          s.block_state[dd])
            ),
            free_count=s.free_count - jnp.where(opened & fresh, 1, 0),
            block_next=s.block_next.at[dd].add(jnp.where(opened, take, 0)),
            block_valid=s.block_valid.at[dd].add(jnp.where(opened, take, 0)),
            open_mig=s.open_mig.at[tgt_mode].set(
                jnp.where(opened, jnp.where(is_full, -1, d), s.open_mig[tgt_mode])
            ),
            die_busy_ms=busy,
        )
        consumed = consumed + take
    if overlap and prog_blocks:
        per_plane = jax.ops.segment_sum(
            jnp.stack(prog_ms), cfg.plane_slot_of_block(jnp.stack(prog_blocks)),
            num_segments=cfg.n_dies * cfg.planes_per_die,
        )
        die_prog = per_plane.reshape(cfg.n_dies, cfg.planes_per_die).max(1)
        s = s._replace(die_busy_ms=s.die_busy_ms + die_prog)
    placed = dest_slot < S
    lp_idx = jnp.where(placed, lpns, L)  # L = dropped
    return s._replace(
        l2p=st.put(s.l2p, lp_idx, dest_slot, wide),
        p2l=st.put(s.p2l, dest_slot, lp_safe, wide),
        page_write_ms=st.put(s.page_write_ms, dest_slot, s.clock_ms, wide),
        # every physical relocation program is a page of write amplification;
        # counting here (the single placement core) covers GC, reclaim,
        # conversion AND prog-fail re-placement with one counter
        n_reloc_pages=s.n_reloc_pages + placed.sum().astype(jnp.float32),
    )


def migrate_block(s: st.SSDState, src, tgt_mode, cfg: geometry.SimConfig,
                  faults: flt.FaultParams | None = None):
    """Move all valid pages of ``src`` into open migration block(s) of
    ``tgt_mode``, then erase ``src``. This is both mode conversion
    (tgt != src mode) and GC relocation (tgt == src mode) — a K=1 call into
    the fused :func:`relocate_group` kernel.

    Latency accounting: each valid page costs one source-mode read (with its
    Eq.-3 retry count) plus one target-mode program; the erase costs the
    source-mode erase latency. Requires up to MAX_DEST destination blocks;
    the caller guards on free_block_count.
    """
    victims = jnp.asarray(src, jnp.int32).reshape((1,))
    return relocate_group(s, victims, jnp.ones((1,), bool), tgt_mode, cfg,
                          MAX_DEST, reason=obs.REASON_CONV_BLOCK,
                          faults=faults)


def _migrate_block_reference(s: st.SSDState, src, tgt_mode, cfg: geometry.SimConfig):
    """The original sequential block migration — retained purely as the
    behavioral reference for the fused-kernel bit-identity tests
    (``gc_pass_reference`` routes through it); production code uses
    :func:`migrate_block` / :func:`relocate_group`.
    """
    spb = cfg.slots_per_block

    src_mode = s.block_mode[src]
    slots = src * spb + jnp.arange(spb, dtype=jnp.int32)
    lpns = lax.dynamic_slice(s.p2l, (src * spb,), (spb,))
    valid = lpns >= 0
    n_valid = valid.sum()

    # -- read cost of the source pages (Eq. 1 -> Eq. 3 per page) --
    age_h = (
        cfg.device_age_h
        + (s.clock_ms - lax.dynamic_slice(s.page_write_ms, (src * spb,), (spb,))) / 3.6e6
    )
    retries = retry.page_retries(src_mode, s.block_pe[src], age_h, s.block_reads[src], slots)
    read_ms = jnp.where(valid, retry.read_latency_us(src_mode, retries), 0.0).sum() / 1000.0
    src_die = cfg.die_of_block(src)
    s = s._replace(die_busy_ms=s.die_busy_ms.at[src_die].add(read_ms))

    # source slots die with the erase below; no explicit invalidation needed
    s = _place_pages(s, lpns, valid, tgt_mode, cfg, MAX_DEST)

    s = s._replace(
        n_migrated_pages=s.n_migrated_pages + n_valid,
        n_conversions=s.n_conversions.at[src_mode, tgt_mode].add(1.0),
    )
    return _erase(s, src, cfg)


def _dest_unroll(cfg: geometry.SimConfig, n_pages: int) -> int:
    """Destination blocks needed to place ``n_pages`` into the smallest-
    capacity mode (SLC), plus one partially-filled open block."""
    slc_ppb = max(cfg.slots_per_block // 4, 1)
    return -(-n_pages // slc_ppb) + 1


def migrate_pages(s: st.SSDState, lpns, tgt_mode, cfg: geometry.SimConfig,
                  faults: flt.FaultParams | None = None, wide: bool = False):
    """Page-granular conversion migration (paper Fig. 9/10): move the given
    logical pages into open block(s) programmed in ``tgt_mode``, invalidating
    their old slots. The destination block is the unit of mode uniformity
    ("flash type alignment"); source blocks are compacted later by GC.

    ``lpns``: (M,) int32, -1-padded. M is static (cfg.migrate_pages_per_chunk).
    With ``faults`` active, over-budget migration reads pay the ECC recovery
    penalty and count as uncorrectable (same model as :func:`relocate_group`).
    ``wide`` indexes the mapping and timestamps through their 128-wide views
    (:func:`state.put`). With no valid page the call leaves ``s`` exactly as it
    was: every scatter drops and every add is of zero to a non-negative sum.
    """
    spb = cfg.slots_per_block
    S = cfg.n_slots
    M = lpns.shape[0]

    lp_safe = jnp.maximum(lpns, 0)
    old_slot = st.take(s.l2p, lp_safe, wide)
    valid = (lpns >= 0) & (old_slot >= 0)
    old_slot = jnp.where(valid, old_slot, 0)
    src_blk = old_slot // spb
    src_mode = s.block_mode[src_blk]
    # don't "migrate" pages already in the target mode
    valid &= src_mode != tgt_mode
    n_valid = valid.sum()

    # -- read cost of sources (each page is re-read to migrate) --
    age_h = cfg.device_age_h + (
        s.clock_ms - st.take(s.page_write_ms, old_slot, wide)) / 3.6e6
    retries = retry.page_retries(src_mode, s.block_pe[src_blk], age_h, s.block_reads[src_blk], old_slot)
    lat_us = retry.read_latency_us(src_mode, retries)
    if faults is not None:
        mrr = faults.max_read_retries
        rated = modes.PE_LIMIT[src_mode]
        pe = s.block_pe[src_blk]
        over = valid & (mrr >= 0) & (retries > mrr)
        uncorr = over | (valid & flt.read_fails(faults, old_slot, pe, rated))
        rec_us = flt.recovery_us(faults, src_mode, cfg)
        lat_us = retry.read_latency_us(
            src_mode, jnp.where(over, jnp.maximum(mrr, 0), retries)
        ) + jnp.where(uncorr, rec_us, 0.0)
        s = _book_rebuilds(s, faults, uncorr, old_slot, pe, rated, cfg)
    rd_ms = jnp.where(valid, lat_us, 0.0) / 1000.0
    die_rd = jax.ops.segment_sum(rd_ms, cfg.die_of_block(src_blk),
                                 num_segments=cfg.n_dies)
    s = s._replace(die_busy_ms=s.die_busy_ms + die_rd)

    # -- invalidate old slots --
    drop_slot = jnp.where(valid, old_slot, S)
    p2l = st.put(s.p2l, drop_slot, -1, wide)
    bv = s.block_valid - jax.ops.segment_sum(valid.astype(jnp.int32), src_blk, num_segments=s.block_valid.shape[0])
    s = s._replace(p2l=p2l, block_valid=bv)

    s = _place_pages(s, lpns, valid, tgt_mode, cfg, _dest_unroll(cfg, M), wide)

    conv = jax.ops.segment_sum(valid.astype(jnp.float32), src_mode, num_segments=3)
    s = s._replace(
        n_migrated_pages=s.n_migrated_pages + n_valid,
        n_conversions=s.n_conversions.at[:, tgt_mode].add(conv),
    )
    if obs.full(cfg):
        # one event per source mode with pages moved this call: block -1
        # (page-granular — pages come from many blocks), trigger = the
        # policy's per-read conversion pipeline, conversion weight = pages
        retry_sum = jax.ops.segment_sum(
            jnp.where(valid, retries.astype(jnp.float32), 0.0), src_mode,
            num_segments=modes.N_MODES,
        )
        s = obs.record_events(
            s, cfg,
            mask=conv > 0,
            block=jnp.full((modes.N_MODES,), -1, jnp.int32),
            from_mode=jnp.arange(modes.N_MODES, dtype=jnp.int32),
            to_mode=jnp.full((modes.N_MODES,), tgt_mode, jnp.int32),
            reason=obs.REASON_CONV_PAGE,
            retry_est=retry_sum / jnp.maximum(conv, 1.0),
            pages=conv,
        )
    return s


def maybe_migrate_pages(s: st.SSDState, lpns, tgt_mode, cfg: geometry.SimConfig,
                        faults: flt.FaultParams | None = None,
                        lane_axis: str | None = None):
    """:func:`migrate_pages` when some page is given and the free pool has
    room.

    Under a vmap (``lane_axis`` named) a cond on a batched predicate is a
    select of the whole state, and the select pins the large leaves to a
    layout the migration's scatters must copy them out of. So the lanes that
    may not migrate pass no page instead, which leaves their large leaves
    exactly as they were (every scatter drops), and the migration indexes
    them 128 wide. The small leaves keep the select: it costs nothing and
    keeps their float sums in the order the unbatched cond gives them
    (without it XLA adds the two migrations' read costs into the die clocks
    in different orders, an ulp off the lane's own run)."""
    any_valid = (lpns >= 0).any()
    ok = any_valid & (free_block_count(s) >= _dest_unroll(cfg, lpns.shape[0]) + 2)
    if lane_axis is not None:
        m = migrate_pages(s, jnp.where(ok, lpns, -1), tgt_mode, cfg, faults,
                          wide=True)
        return jax.tree.map(
            lambda new, old: new if st.foldable(new) else jnp.where(ok, new, old),
            m, s)
    return lax.cond(
        ok,
        lambda s_: migrate_pages(s_, lpns, tgt_mode, cfg, faults),
        lambda s_: s_,
        s,
    )


def _demote_dest_unroll(cfg: geometry.SimConfig, tgt_mode: int, n_victims: int) -> int:
    """Destination blocks needed by one fused demotion pass into ``tgt_mode``:
    up to ``n_victims`` source blocks one density level below the target,
    plus one partially-filled open block."""
    ppb = geometry.pages_per_block_host(cfg)
    src_pages = n_victims * int(ppb[tgt_mode - 1])
    return -(-src_pages // int(ppb[tgt_mode])) + 1


def relocate_group(s: st.SSDState, victims, grp, tgt_mode,
                   cfg: geometry.SimConfig, n_dest: int,
                   reason: int = obs.REASON_CONV_BLOCK,
                   faults: flt.FaultParams | None = None):
    """The fused relocation kernel (DESIGN.md §2A): migrate every
    ``grp``-masked victim block into ``tgt_mode`` in one placement pass,
    then erase all victims in one vectorized :func:`_erase_many`.

    GC relocation (tgt == victim mode), reclaim demotion (one call per
    demotion target) and block conversion (:func:`migrate_block`, K=1) are
    all this kernel with different victim sets; ``n_dest`` is the caller's
    static bound on destination blocks one pass can open. ``reason`` tags
    the per-victim observability events (DESIGN.md §7.4) with the trigger
    that fired the pass; the scalar reference paths do not record events,
    so the fused-vs-reference bit-identity tests run at ``obs_level="off"``.

    With ``faults`` active, migration reads whose Eq.-3 retry count exceeds
    the retry budget are uncorrectable: they burn the budget, pay the ECC
    recovery penalty and count into ``n_uncorrectable`` (the relocated copy
    is the soft-decoded data — migration itself still succeeds), and the
    victim erases can retire blocks (see :func:`_erase_many`). Migration
    programs are modeled as verified-good: re-placing a failed migration
    program would recurse into placement, and the recovery path it would
    exercise is already covered by the user write path's re-placement.
    """
    spb = cfg.slots_per_block

    vb = jnp.maximum(victims, 0)
    slots = vb[:, None] * spb + jnp.arange(spb, dtype=jnp.int32)[None, :]  # (K, spb)
    lpns = jnp.where(grp[:, None], s.p2l[slots], -1)
    valid = lpns >= 0
    src_mode = s.block_mode[vb]  # (K,)

    # -- read cost of all victim pages, one vectorized Eq.-3 pass --
    age_h = cfg.device_age_h + (s.clock_ms - s.page_write_ms[slots]) / 3.6e6
    retries = retry.page_retries(
        src_mode[:, None], s.block_pe[vb][:, None], age_h, s.block_reads[vb][:, None], slots
    )
    lat_us = retry.read_latency_us(src_mode[:, None], retries)
    if faults is not None:
        mrr = faults.max_read_retries
        rated = modes.PE_LIMIT[src_mode][:, None]
        pe = s.block_pe[vb][:, None]
        over = valid & (mrr >= 0) & (retries > mrr)
        uncorr = over | (valid & flt.read_fails(faults, slots, pe, rated))
        rec_us = flt.recovery_us(faults, src_mode[:, None], cfg)
        lat_us = retry.read_latency_us(
            src_mode[:, None], jnp.where(over, jnp.maximum(mrr, 0), retries)
        ) + jnp.where(uncorr, rec_us, 0.0)
        s = _book_rebuilds(s, faults, uncorr, slots, pe, rated, cfg)
    rd_ms = jnp.where(valid, lat_us, 0.0).sum(1) / 1000.0
    rd_w = jnp.where(grp, rd_ms, 0.0)
    if cfg.chan_model == "lattice" and cfg.planes_per_lun > 1:
        # multi-plane relocation reads on one die overlap (optimistic
        # cache-read model): co-scheduled plane victims pay the max of the
        # per-plane read times, matching the erase/program overlap charges
        per_plane = jax.ops.segment_sum(
            rd_w, cfg.plane_slot_of_block(vb),
            num_segments=cfg.n_dies * cfg.planes_per_die,
        )
        die_rd = per_plane.reshape(cfg.n_dies, cfg.planes_per_die).max(1)
    else:
        die_rd = jax.ops.segment_sum(rd_w, cfg.die_of_block(vb),
                                     num_segments=cfg.n_dies)
    s = s._replace(die_busy_ms=s.die_busy_ms + die_rd)

    s = _place_pages(s, lpns.reshape(-1), valid.reshape(-1), tgt_mode, cfg, n_dest)

    conv_src = jnp.where(grp, src_mode, modes.N_MODES)  # N_MODES = dropped
    s = s._replace(
        n_migrated_pages=s.n_migrated_pages + valid.sum(),
        n_conversions=s.n_conversions.at[conv_src, tgt_mode].add(1.0, mode="drop"),
    )
    if obs.full(cfg):
        pages = valid.sum(1).astype(jnp.float32)
        retry_mean = jnp.where(valid, retries.astype(jnp.float32), 0.0).sum(
            1
        ) / jnp.maximum(pages, 1.0)
        s = obs.record_events(
            s, cfg,
            mask=grp,
            block=vb,
            from_mode=src_mode,
            to_mode=jnp.broadcast_to(jnp.asarray(tgt_mode, jnp.int32),
                                     vb.shape),
            reason=reason,
            retry_est=retry_mean,
            pages=pages,
        )
    return _erase_many(s, victims, grp, cfg, faults=faults)


def reclaim_victims(s: st.SSDState, victims, v_ok, v_tgt, cfg: geometry.SimConfig,
                    faults: flt.FaultParams | None = None):
    """Fused reclaim demotion (paper §IV-E): the top-k victims selected by
    ``reclaim.select_demotion_victims`` are migrated in at most two masked
    passes (one per demotion target, SLC->TLC and TLC->QLC) instead of K
    sequential block migrations. Each pass is cond-gated on having victims
    and enough free destination blocks."""
    K = victims.shape[0]
    for tgt in (modes.TLC, modes.QLC):
        grp = v_ok & (v_tgt == tgt) & (s.block_state[jnp.maximum(victims, 0)] == st.FULL)
        ok = grp.any() & (free_block_count(s) >= _demote_dest_unroll(cfg, tgt, K) + 2)
        s = lax.cond(
            ok,
            lambda s_, grp=grp, tgt=tgt: relocate_group(
                s_, victims, grp, tgt, cfg, _demote_dest_unroll(cfg, tgt, K),
                reason=obs.REASON_RECLAIM, faults=faults,
            ),
            lambda s_: s_,
            s,
        )
    return s


def _gc_dest_need(cfg: geometry.SimConfig, k: int) -> int:
    """Free-pool guard headroom for a fused GC pass of up to ``k`` victims.

    One same-mode victim needs at most MAX_DEST destinations (the scalar
    reference's guard, kept so ``gc_victims_per_pass=1`` is bit-identical to
    it); every further victim fills at most one more fresh block.
    """
    return MAX_DEST + (k - 1)


def select_gc_victims(s: st.SSDState, cfg: geometry.SimConfig, k: int,
                      knobs=None):
    """Top-k GC victim selection via the unified scorer
    (``reclaim.score_victims``): among reclaimable FULL blocks — at least
    one invalid page at their current mode — the ``k`` best under
    ``cfg.gc_objective``, ties to the lowest block id. The default
    ``"min_valid"`` objective (fewest valid pages first) equals ``k``
    sequential greedy argmin picks because relocation never creates a new
    reclaimable block (placed blocks fill completely valid). A traced
    ``knobs.gc_objective`` code overrides the static objective per run."""
    code = None if knobs is None else getattr(knobs, "gc_objective", None)
    victims, ok, _ = reclaim.score_victims(s, cfg, cfg.gc_objective, k=k,
                                           objective_code=code)
    return victims, ok


def gated(pred, fn, s, lane_axis: str | None = None):
    """``lax.cond(pred, fn, identity, s)`` that stays a branch under vmap.

    Under ``jax.vmap`` a cond on a batched predicate lowers to a select, so
    ``fn`` would run for every lane on every call. Given the vmap's
    ``lane_axis`` name, the pass sits behind one scalar cond on whether any
    lane of the batch needs it; inside, the per-lane cond (that same
    select) keeps the state of lanes that do not, so each lane's result is
    bit-identical to its own unbatched run. ``lane_axis=None`` is the plain
    cond. The batch cond's operands and results carry the state 128 wide
    (``state.fold_lanes``), as the sweep's scan carry does, so no relayout
    copy sits around it. Returns ``(state, opened)``: ``opened`` is the
    scalar predicate of the branch taken, for :func:`obs.record_gate`."""
    keep = lambda s_: s_  # noqa: E731
    if lane_axis is None:
        return lax.cond(pred, fn, keep, s), pred
    opened = lax.psum(pred.astype(jnp.int32), lane_axis) > 0

    def run(f):
        return st.fold_lanes(lax.cond(pred, fn, keep, st.unfold_lanes(f, s)))

    f = lax.cond(opened, run, keep, st.fold_lanes(s))
    return st.unfold_lanes(f, s), opened


def gc_step(s: st.SSDState, cfg: geometry.SimConfig,
            faults: flt.FaultParams | None = None, knobs=None,
            lane_axis: str | None = None):
    """Fused greedy GC, cond-gated on the free-pool watermark: with a
    healthy pool the victim scan is skipped entirely, so GC can never fire
    above ``cfg.gc_free_threshold``. Under pressure one firing relocates up
    to ``cfg.gc_victims_per_pass`` victims through :func:`relocate_group`,
    amortizing the full-device top-k, the placement unroll and the per-chunk
    dispatch over k blocks. ``lane_axis`` names the vmap axis of a batched
    caller (see :func:`gated`)."""
    need = free_block_count(s) < cfg.gc_free_threshold
    s, opened = gated(need, lambda s_: _gc_pass(s_, cfg, faults, knobs), s,
                      lane_axis)
    return obs.record_gate(s, cfg, obs.GATE_GC, opened)


def _gc_pass(s: st.SSDState, cfg: geometry.SimConfig,
             faults: flt.FaultParams | None = None, knobs=None):
    """One fused GC firing: top-k min-valid victims relocated in a single
    masked :func:`relocate_group` pass over the batch's dominant source
    mode (GC keeps each block's mode), cond-gated on having victims and
    free headroom.

    The batch is deficit-aware (per-victim projected net reclaim
    ``1 - valid/pages`` from the selection-time counts, prefix-summed
    best-first): victims are *forced* while the projection is still needed
    to lift the pool back to ``gc_free_threshold``, and taken
    *opportunistically* beyond that — up to ``k - 1`` blocks of hysteresis
    headroom — only when they offer at least half the batch's best
    projected harvest (i.e. comparably cheap to the victim GC would have
    picked anyway). One firing then builds enough slack that the following chunks
    skip GC entirely, amortizing the full-device top-k, the placement
    unroll and the cond/dispatch overhead over the batch, while valid-heavy
    victims deep in the ranking are never relocated early (they decay to
    cheap victims by the time they are actually needed — relocating them
    now would multiply write amplification, and with a thin invalid
    inventory the pass degrades gracefully to the reference's
    one-victim-per-firing behavior). With ``k = 1`` the mask is always
    true, keeping the pass bit-identical to ``gc_pass_reference``. ``k``
    victims each with >= 1 invalid page place into at most ``k`` fresh
    blocks plus the open migration block, so the placement unroll is
    ``k + 1``.

    Under the ``"lifespan"`` objective the lanes arrive ordered by *score*
    (wear-discounted), not by projected harvest, so lane 0's ``net`` is the
    preferred victim's harvest rather than the maximum — the deficit
    batching then forces however many score-ordered victims the projection
    needs, which is exactly the wear-levelled trade the objective asks
    for."""
    k = min(max(int(cfg.gc_victims_per_pass), 1), cfg.n_blocks)
    victims, ok = select_gc_victims(s, cfg, k, knobs)
    vb = jnp.maximum(victims, 0)
    ppb = geometry.pages_per_block(cfg)
    vmode = s.block_mode[vb]
    net = jnp.where(ok, 1.0 - s.block_valid[vb] / ppb[vmode].astype(jnp.float32), 0.0)
    cum_before = jnp.cumsum(net) - net  # projected reclaim of better victims
    deficit = (cfg.gc_free_threshold - free_block_count(s)).astype(jnp.float32)
    forced = cum_before < deficit
    # opportunistic batching: only victims offering at least half the best
    # victim's harvest ride along (victims are ordered best-first, so lane 0
    # holds the batch's best projected net reclaim)
    cheap = net >= 0.5 * net[0]
    ok &= forced | (cheap & (cum_before < deficit + (k - 1)))
    # one relocation pass per firing, on the dominant source mode's victims
    # (a GC batch is virtually always single-mode — user data lives in QLC;
    # minority-mode victims simply wait for a later firing)
    cnt = jax.ops.segment_sum(ok.astype(jnp.int32), vmode, num_segments=modes.N_MODES)
    tgt = jnp.argmax(cnt).astype(jnp.int32)
    grp = ok & (vmode == tgt) & (s.block_state[vb] == st.FULL)
    go = grp.any() & (free_block_count(s) >= _gc_dest_need(cfg, k) + 2)
    return lax.cond(
        go,
        lambda s_: relocate_group(s_, victims, grp, tgt, cfg, k + 1,
                                  reason=obs.REASON_GC, faults=faults),
        lambda s_: s_,
        s,
    )


def gc_step_reference(s: st.SSDState, cfg: geometry.SimConfig):
    """Watermark-gated wrapper over :func:`gc_pass_reference` (mirrors
    :func:`gc_step`); reference-only, for the bit-identity tests."""
    need = free_block_count(s) < cfg.gc_free_threshold
    return lax.cond(need, lambda s_: gc_pass_reference(s_, cfg), lambda s_: s_, s)


def gc_pass_reference(s: st.SSDState, cfg: geometry.SimConfig):
    """The original scalar single-victim GC pass — argmin victim scan plus
    one sequential block migration — retained purely as the behavioral
    reference: the fused :func:`_gc_pass` with ``gc_victims_per_pass=1``
    must be bit-identical to it (asserted in tier-1)."""
    ppb = geometry.pages_per_block(cfg)
    full = s.block_state == st.FULL
    reclaimable = full & (s.block_valid < ppb[s.block_mode])
    score = jnp.where(reclaimable, s.block_valid, jnp.iinfo(jnp.int32).max)
    victim = jnp.argmin(score).astype(jnp.int32)
    src = jnp.where(reclaimable[victim], victim, -1)
    tgt_mode = s.block_mode[victim]
    ok = (src >= 0) & (free_block_count(s) >= MAX_DEST + 2)
    ok &= s.block_state[jnp.maximum(src, 0)] == st.FULL
    return lax.cond(
        ok,
        lambda s_: _migrate_block_reference(s_, jnp.maximum(src, 0), tgt_mode, cfg),
        lambda s_: s_,
        s,
    )
