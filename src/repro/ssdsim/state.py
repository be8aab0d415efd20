"""Simulator state — a flat pytree of arrays so the whole engine jits/scans.

Physical page addressing: slot = block * slots_per_block + offset. A block
programmed in TLC/SLC mode only uses the first pages_per_block(mode) offsets.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import modes
from repro.ssdsim import geometry, obs, telemetry

FREE = 0
OPEN = 1
FULL = 2
# Retired: the block failed an erase and left service permanently
# (DESIGN.md §2D). Never FREE again, never allocated, zero capacity.
BAD = 3

# Sentinel pool size for cfg.spare_blocks < 0: an unbounded spare pool
# (int32 max — no realizable retirement count reaches it), which keeps the
# degraded-mode predicate traced-False and the PR 7 accounting bit-exact.
SPARE_UNLIMITED = 2**31 - 1


class SSDState(NamedTuple):
    # mapping
    l2p: jnp.ndarray  # (L,) int32 logical -> physical slot (-1 unmapped)
    p2l: jnp.ndarray  # (S,) int32 physical slot -> logical (-1 invalid)
    page_write_ms: jnp.ndarray  # (S,) float32 sim-clock time of program

    # per-block
    block_mode: jnp.ndarray  # (B,) int32 SLC/TLC/QLC
    block_state: jnp.ndarray  # (B,) int32 FREE/OPEN/FULL
    block_pe: jnp.ndarray  # (B,) int32 P/E cycles
    block_reads: jnp.ndarray  # (B,) int32 reads since program (disturb)
    block_next: jnp.ndarray  # (B,) int32 next free offset
    block_valid: jnp.ndarray  # (B,) int32 valid page count
    block_cold_age: jnp.ndarray  # (B,) int32 epochs since any hot/warm access
    # grown bad-block map (DESIGN.md §2D): True iff the block failed an
    # erase and was retired (block_state == BAD, by invariant). A separate
    # leaf so factory bad blocks / host-visible retirement lists have a
    # stable home independent of the state machine.
    block_bad: jnp.ndarray  # (B,) bool

    # retirement accounting (exact, maintained by ftl._erase_many like
    # free_count; invariant: bad_count == (block_state == BAD).sum())
    bad_count: jnp.ndarray  # int32 scalar — retired blocks

    # over-provisioning spare pool (DESIGN.md §2D): every retirement
    # consumes one spare until the pool runs dry; an exhausted pool flips
    # the engine into read-only degraded mode (writes dropped + counted).
    # spare_total is a constant leaf (SPARE_UNLIMITED for the unbounded
    # PR 7 accounting); invariant: spare_count == max(total - bad, 0).
    spare_total: jnp.ndarray  # int32 scalar — configured pool size
    spare_count: jnp.ndarray  # int32 scalar — spares remaining

    # heat (logical)
    heat: jnp.ndarray  # (L,) float32

    # allocation cursors
    open_user: jnp.ndarray  # (n_dies,) int32 open block per die (-1 none)
    open_mig: jnp.ndarray  # (3,) int32 open migration block per mode (-1)

    # free-pool bookkeeping (maintained incrementally by erase/alloc so the
    # hot path never rescans block_state; invariant checked by the tests:
    # free_count == (block_state == FREE).sum())
    free_count: jnp.ndarray  # int32 scalar — exact number of FREE blocks
    free_hint: jnp.ndarray  # (n_dies,) int32 — a (possibly stale) free block
    #   per die, refreshed on erase; consumers verify against block_state and
    #   fall back to a full scan only when the hint is dead

    # timing — the (channel, die, plane) resource lattice (DESIGN.md §2C).
    # A die owns sense/program/erase occupancy; the channel bus serializes
    # page transfers across its dies (chan_model="lattice"; under "legacy"
    # the channel clocks stay 0 and a die is the historical one-clock LUN).
    clock_ms: jnp.ndarray  # f32 scalar — simulated time
    die_busy_ms: jnp.ndarray  # (n_dies,) f32 — cumulative busy time
    chan_busy_ms: jnp.ndarray  # (n_channels,) f32 — cumulative transfer time
    # open-loop arrival model (DESIGN.md §2C): absolute sim time at which
    # each die next becomes available. Requests arriving earlier queue
    # (FCFS per die); background work (migrations/GC/erase) pushes it
    # forward too, so reads block behind FTL tasks. Stays 0 in closed loop.
    die_avail_ms: jnp.ndarray  # (n_dies,) f32 — busy_until clock per die
    # absolute sim time each channel bus next becomes free for a transfer
    # (lattice open loop only; stays 0 under chan_model="legacy")
    chan_avail_ms: jnp.ndarray  # (n_channels,) f32 — busy_until per channel

    # telemetry
    lat_hist: jnp.ndarray  # (telemetry.N_LAT_BINS,) f32 read-latency histogram
    w_lat_hist: jnp.ndarray  # (telemetry.N_LAT_BINS,) f32 write-latency histogram

    # observability accumulators (DESIGN.md §7.4; shapes collapse to
    # zero-length when the instrument is off, so obs_level="off" carries
    # nothing extra through the scan)
    obs_lat_mode: jnp.ndarray  # (3|0, N_LAT_BINS) per-mode read counts
    obs_lat_comp: jnp.ndarray  # (3|0, N_COMPONENTS, N_LAT_BINS) µs sums
    obs_events: jnp.ndarray  # (capacity|0, N_EV_FIELDS) f32 event ring
    obs_ev_count: jnp.ndarray  # i32 scalar — true total events emitted
    obs_ts: jnp.ndarray  # (windows|0, N_SERIES) windowed time series
    obs_gated_chunks: jnp.ndarray  # (N_GATES|0,) i32 chunks each gated pass ran

    # counters (f32 scalars; summed per-chunk so precision is fine)
    svc_sum_ms: jnp.ndarray  # total recorded user-read latency (queueing
    #   delay when open-loop, + sense/retry + xfer)
    q_sum_ms: jnp.ndarray  # total read queueing delay (0 in closed loop)
    chanq_sum_ms: jnp.ndarray  # total read channel-wait (transfer queueing
    #   behind the bus; nonzero only under the lattice open-loop model)
    n_reads: jnp.ndarray
    n_writes: jnp.ndarray
    n_retries: jnp.ndarray
    n_migrated_pages: jnp.ndarray
    # physical relocation programs, counted at the single placement core
    # (ftl._place_pages) so GC / reclaim / conversion / prog-fail
    # re-placement all land in one WAF denominator-exact counter:
    # WAF = (n_writes + n_reloc_pages) / n_writes (DESIGN.md §2E)
    n_reloc_pages: jnp.ndarray
    n_erases: jnp.ndarray
    n_conversions: jnp.ndarray  # (3,3) from-mode x to-mode counts
    # fault/recovery counters (DESIGN.md §2D; all stay exactly 0.0 on the
    # fault-free path, which the zero-fault equivalence test pins)
    n_uncorrectable: jnp.ndarray  # reads past the retry budget (ECC recovery)
    n_prog_fails: jnp.ndarray  # failed page programs (re-placed)
    n_erase_fails: jnp.ndarray  # failed erases (block retired)
    n_dropped_writes: jnp.ndarray  # writes/re-placements lost to allocation
    #   exhaustion under retirement pressure (the stalled-queue path)
    n_rebuilds: jnp.ndarray  # die-parity stripe reconstructions (uncorrectable
    #   reads recovered via peers; only with parity_rebuild armed)
    n_data_loss: jnp.ndarray  # rebuilds hit by a second uncorrectable among
    #   the peer reads — the stripe is unreconstructable (true data loss)
    n_degraded_writes: jnp.ndarray  # writes refused in read-only degraded
    #   mode (spare pool exhausted; mapping untouched)


def init_state(cfg: geometry.SimConfig, initial_pe=None,
               spare_blocks=None) -> SSDState:
    """Pre-filled device: L logical pages written sequentially into QLC
    blocks (LUN-striped by block id), remaining blocks free. Matches the
    paper's setup: 'Initially, the block types of the hybrid SSD are set to
    the QLC mode'.

    ``initial_pe`` optionally overrides ``cfg.initial_pe`` with a traced
    scalar so a batch of wear stages can share one jitted sweep (vmap over
    the run axis — see repro.experiments.sweep); ``spare_blocks`` does the
    same for ``cfg.spare_blocks`` (negative = unbounded pool).
    """
    B, S, L = cfg.n_blocks, cfg.n_slots, cfg.n_logical
    spb = cfg.slots_per_block
    assert L <= S, "working set must fit the device"
    n_full = L // spb  # fully used blocks
    rem = L - n_full * spb

    l2p = jnp.arange(L, dtype=jnp.int32)  # block i//spb, offset i%spb -> slot == lpn
    # the inverse of that identity map, built without a scatter: the TPU
    # compiler aborts on a scatter whose indices are an iota
    slot = jnp.arange(S, dtype=jnp.int32)
    p2l = jnp.where(slot < L, slot, -1)

    blk = jnp.arange(B, dtype=jnp.int32)
    used_full = blk < n_full
    part = (blk == n_full) & (rem > 0)
    block_state = jnp.where(used_full, FULL, jnp.where(part, OPEN, FREE)).astype(jnp.int32)
    block_next = jnp.where(used_full, spb, jnp.where(part, rem, 0)).astype(jnp.int32)
    block_valid = block_next

    free = block_state == FREE
    # lowest-numbered free block per LUN seeds the allocation hints
    hint = jax.ops.segment_min(
        jnp.where(free, blk, B), cfg.die_of_block(blk), num_segments=cfg.n_dies
    )
    free_hint = jnp.where(hint < B, hint, -1).astype(jnp.int32)

    # negative = unbounded pool; works for both the static int and a traced
    # per-run knob (the where stays shape-() either way)
    sb = jnp.asarray(
        cfg.spare_blocks if spare_blocks is None else spare_blocks, jnp.int32)
    spare_total = jnp.where(sb < 0, jnp.int32(SPARE_UNLIMITED), sb)

    return SSDState(
        l2p=l2p,
        p2l=p2l,
        page_write_ms=jnp.zeros((S,), jnp.float32),
        block_mode=jnp.full((B,), modes.QLC, jnp.int32),
        block_state=block_state,
        block_pe=jnp.full((B,), jnp.int32(cfg.initial_pe if initial_pe is None else initial_pe)),
        block_reads=jnp.zeros((B,), jnp.int32),
        block_next=block_next,
        block_valid=block_valid,
        block_cold_age=jnp.zeros((B,), jnp.int32),
        block_bad=jnp.zeros((B,), bool),
        bad_count=jnp.int32(0),
        spare_total=spare_total,
        spare_count=spare_total,
        heat=jnp.zeros((L,), jnp.float32),
        open_user=jnp.full((cfg.n_dies,), -1, jnp.int32),
        open_mig=jnp.full((3,), -1, jnp.int32),
        free_count=free.sum().astype(jnp.int32),
        free_hint=free_hint,
        lat_hist=jnp.zeros((telemetry.N_LAT_BINS,), jnp.float32),
        w_lat_hist=jnp.zeros((telemetry.N_LAT_BINS,), jnp.float32),
        **obs.init_leaves(cfg),
        clock_ms=jnp.float32(0.0),
        die_busy_ms=jnp.zeros((cfg.n_dies,), jnp.float32),
        chan_busy_ms=jnp.zeros((cfg.n_channels,), jnp.float32),
        die_avail_ms=jnp.zeros((cfg.n_dies,), jnp.float32),
        chan_avail_ms=jnp.zeros((cfg.n_channels,), jnp.float32),
        svc_sum_ms=jnp.float32(0.0),
        q_sum_ms=jnp.float32(0.0),
        chanq_sum_ms=jnp.float32(0.0),
        n_reads=jnp.float32(0.0),
        n_writes=jnp.float32(0.0),
        n_retries=jnp.float32(0.0),
        n_migrated_pages=jnp.float32(0.0),
        n_reloc_pages=jnp.float32(0.0),
        n_erases=jnp.float32(0.0),
        n_conversions=jnp.zeros((3, 3), jnp.float32),
        n_uncorrectable=jnp.float32(0.0),
        n_prog_fails=jnp.float32(0.0),
        n_erase_fails=jnp.float32(0.0),
        n_dropped_writes=jnp.float32(0.0),
        n_rebuilds=jnp.float32(0.0),
        n_data_loss=jnp.float32(0.0),
        n_degraded_writes=jnp.float32(0.0),
    )


# Under a vmapped lane axis XLA tiles a (lanes, n) leaf (8, 128) where it
# picks the layout (scan carry, cond operands), but a batched scatter
# linearises (lane, index) into one flat index and wants the leaf flat, so
# every such scatter copies the whole leaf out and back. A (n/128, 128) leaf
# tiled (8, 128) holds the bytes of a flat (n,) leaf tiled (1024,): carried
# 128 wide, the scatter's flat operand is a bitcast, not a copy.
FOLD = 128


def foldable(x) -> bool:
    """A rank-1 leaf of a whole number of (8, 128) tiles, more than one: the
    per-slot and per-logical leaves, not the one-tile per-block leaves of a
    1,024-block drive, whose copies cost nothing."""
    n = x.shape[0] if x.ndim == 1 else 0
    return n > 8 * FOLD and n % (8 * FOLD) == 0


def fold_lanes(s):
    """Every :func:`foldable` leaf of ``s`` reshaped to ``(-1, FOLD)``."""
    with jax.named_scope("lane_fold"):
        return jax.tree.map(
            lambda x: x.reshape(-1, FOLD) if foldable(x) else x, s)


def unfold_lanes(s, like):
    """Inverse of :func:`fold_lanes`: each leaf back to its leaf of ``like``'s
    shape (only the shapes of ``like`` are read)."""
    with jax.named_scope("lane_fold"):
        return jax.tree.map(lambda x, ref: x.reshape(ref.shape), s, like)


def take(x, i, wide: bool):
    """``x[i]``; ``wide`` reads a :func:`foldable` leaf through its 128-wide
    view (see :func:`put`)."""
    if wide and foldable(x):
        return x.reshape(-1, FOLD)[i // FOLD, i % FOLD]
    return x[i]


def put(x, i, v, wide: bool):
    """``x.at[i].set(v, mode="drop")``; ``wide`` scatters into a
    :func:`foldable` leaf through its 128-wide view, whose layout under a
    vmapped lane axis is the one the scatter wants, so XLA adds no relayout
    copy around it. An index past the end stays dropped."""
    if wide and foldable(x):
        w = x.reshape(-1, FOLD).at[i // FOLD, i % FOLD].set(v, mode="drop")
        return w.reshape(x.shape)
    return x.at[i].set(v, mode="drop")


def check_invariants(s: SSDState, cfg: geometry.SimConfig, where: str = "") -> None:
    """Assert full-state FTL consistency (host-side numpy; test helper).

    Checks the invariants every engine step and every relocation pass must
    preserve: l2p/p2l mutual consistency (a bijection on mapped pages),
    ``block_valid`` equal to the per-block recount of valid slots, valid
    slots confined to each block's programmed window, block metadata in
    range, exact incremental ``free_count``, free hints on their own LUN
    (stale hints are legal by design — consumers re-validate against
    ``block_state`` — but a hint never strays off its LUN or out of range),
    and open user/migration cursors pointing at OPEN blocks.
    """
    import numpy as np

    tag = f" [{where}]" if where else ""
    spb = cfg.slots_per_block
    B, L = cfg.n_blocks, cfg.n_logical
    l2p = np.asarray(s.l2p)
    p2l = np.asarray(s.p2l)

    # -- mapping bijection --
    mapped = l2p >= 0
    assert (l2p[mapped] < cfg.n_slots).all(), f"l2p out of range{tag}"
    assert (p2l[l2p[mapped]] == np.arange(L)[mapped]).all(), \
        f"l2p -> p2l mismatch{tag}"
    vslots = np.nonzero(p2l >= 0)[0]
    assert (p2l[vslots] < L).all(), f"p2l out of range{tag}"
    assert (l2p[p2l[vslots]] == vslots).all(), f"p2l -> l2p mismatch{tag}"

    # -- per-block accounting --
    bv = np.asarray(s.block_valid)
    counts = np.bincount(vslots // spb, minlength=B)
    assert (bv == counts).all(), \
        f"block_valid recount mismatch at {np.nonzero(bv != counts)[0][:8]}{tag}"
    bm = np.asarray(s.block_mode)
    bs = np.asarray(s.block_state)
    bn = np.asarray(s.block_next)
    assert ((bm >= 0) & (bm < modes.N_MODES)).all(), f"block_mode range{tag}"
    assert ((bs >= FREE) & (bs <= BAD)).all(), f"block_state range{tag}"
    ppb = geometry.pages_per_block_host(cfg)
    nonfree = bs != FREE
    assert (bn[nonfree] <= ppb[bm[nonfree]]).all(), f"block_next > pages{tag}"
    assert (bn >= bv).all(), f"valid pages exceed programmed pages{tag}"
    assert (bn[bs == FREE] == 0).all() and (bv[bs == FREE] == 0).all(), \
        f"FREE block with programmed/valid pages{tag}"

    # -- bad-block accounting (DESIGN.md §2D) --
    bad = np.asarray(s.block_bad)
    assert (bad == (bs == BAD)).all(), f"block_bad / block_state BAD mismatch{tag}"
    assert int(s.bad_count) == int(bad.sum()), \
        f"bad_count {int(s.bad_count)} != recount {int(bad.sum())}{tag}"
    assert (bn[bad] == 0).all() and (bv[bad] == 0).all(), \
        f"retired block with programmed/valid pages{tag}"
    # spare-pool accounting: every retirement consumed a spare until dry
    total, remaining = int(s.spare_total), int(s.spare_count)
    assert total >= 0, f"negative spare_total{tag}"
    assert remaining == max(total - int(bad.sum()), 0), \
        f"spare_count {remaining} != max({total} - {int(bad.sum())}, 0){tag}"
    # valid slots sit inside the programmed window of their block
    assert (vslots % spb < bn[vslots // spb]).all(), \
        f"valid slot past block_next{tag}"

    # -- free-pool bookkeeping --
    assert int(s.free_count) == int((bs == FREE).sum()), \
        f"free_count {int(s.free_count)} != recount {int((bs == FREE).sum())}{tag}"
    hint = np.asarray(s.free_hint)
    assert ((hint >= -1) & (hint < B)).all(), f"free_hint range{tag}"
    live = hint >= 0
    assert (hint[live] % cfg.n_dies == np.arange(cfg.n_dies)[live]).all(), \
        f"free_hint off its die{tag}"

    # -- allocation cursors --
    for name, cur in (("open_user", np.asarray(s.open_user)),
                      ("open_mig", np.asarray(s.open_mig))):
        openc = cur >= 0
        assert ((cur >= -1) & (cur < B)).all(), f"{name} range{tag}"
        assert (bs[cur[openc]] == OPEN).all(), f"{name} -> non-OPEN block{tag}"
    om = np.asarray(s.open_mig)
    assert (bm[om[om >= 0]] == np.arange(3)[om >= 0]).all(), \
        f"open_mig block mode mismatch{tag}"


def usable_capacity_pages(state: SSDState, cfg: geometry.SimConfig, xp=jnp):
    """Usable capacity in pages: non-free blocks count at their current
    mode's page count; free blocks count at QLC density (they can be opened
    in any mode, so their capacity potential is the dense one).

    ``xp=numpy`` computes on the host (``pages_per_block_host`` rounds
    identically) so ``engine.summarize`` can run on device_get'ed numpy
    leaves without enqueueing device work (DESIGN.md §7.3); the default
    stays traceable for the in-jit ChunkMetrics use."""
    ppb = (geometry.pages_per_block(cfg) if xp is jnp
           else geometry.pages_per_block_host(cfg))
    per_block = xp.where(
        state.block_state == FREE,
        ppb[modes.QLC],
        ppb[state.block_mode],
    )
    # retired blocks (erase failure, DESIGN.md §2D) left service for good
    per_block = xp.where(state.block_state == BAD, 0, per_block)
    return per_block.sum()


def capacity_gib(state: SSDState, cfg: geometry.SimConfig, xp=jnp):
    # float cast first: pages * page_bytes overflows int32 at real geometry
    return (usable_capacity_pages(state, cfg, xp).astype(xp.float32)
            * cfg.page_bytes / 2**30)
