"""Plain reference of the simulated drive, in numpy on the host.

It follows the same model as the program under test: the paper's Eq. 1
raw bit error rate with per-page process variation, the Eq. 3 retry count,
Table IV latencies, the heat classifier, the RARO Table II conversion
triggers with the stage-adaptive R2 schedule, page-granular conversion into
open migration blocks, elastic capacity recovery (demotion of cold SLC/TLC
blocks under free-space pressure), watermark-gated greedy GC and the user
write path, one chunk of requests per step, in the legacy closed-loop
timing model. It imports nothing of the program and is written as plainly
as the model allows: the user write path and block allocation run request
by request, relocation block by block.

Every float statistic is computed in ``dtype`` (float32, as the program
states its arithmetic). The benchmark's control runs the same code in
bfloat16, the next precision down.
"""

from __future__ import annotations

import numpy as np

SLC, TLC, QLC = 0, 1, 2
COLD, WARM, HOT = 0, 1, 2
FREE, OPEN, FULL = 0, 1, 2

# Table III/IV of the paper: pages per block, latencies (us), senses per
# read and the retry-table limit of each mode.
PAGES_PER_BLOCK = np.array([256, 768, 1024])
READ_US = (20.0, 66.0, 140.0)
WRITE_US = (160.0, 730.0, 3102.0)
ERASE_US = (2000.0, 3000.0, 10000.0)
N_SENSE = (1.0, 4.0, 8.0)
MAX_RETRIES = (8, 16, 16)
# Eq. 1 constants per mode: eps, alpha, k, beta, m, n, gamma, p, q
RBER = (
    (1e-5, 2e-9, 1.0, 1e-11, 1.0, 0.5, 1e-12, 1.0, 0.5),
    (6e-4, 7e-7, 1.0, 3.0e-10, 1.6, 0.7, 4.3e-10, 1.0, 1.1),
    (1.3e-3, 3.2e-6, 1.0, 3.25e-9, 1.6, 0.7, 3.0e-9, 1.0, 1.1),
)
PAGE_SIGMA = 0.40
# Eq. 3: each retry keeps (1 - delta) of the errors; LDPC corrects 72 bits
# of an 8,192-bit codeword
DELTA = 0.2
E_LDPC = 72.0 / 8192.0
R2_BY_STAGE = (5, 7, 11)  # young (P/E <= 333) / middle (<= 666) / old
HEAT_DECAY, HOT_AT, WARM_AT = 0.95, 2.0, 0.5
# elastic capacity recovery: demote below this free-block share, blocks
# cold for at least this many chunks
RECLAIM_LOW, COLD_EPOCHS = 0.15, 4
MAX_DEST = 5  # destination blocks one block relocation may need
# read-latency histogram: 16 log bins per decade from 8 us
LAT_MIN_US, BINS_PER_DECADE, N_BINS = 8.0, 16, 64

SUPPORTED = dict(chan_model="legacy", obs_level="off", max_read_retries=-1,
                 prog_fail_rate=0.0, erase_fail_rate=0.0, read_fail_rate=0.0,
                 gc_objective="min_valid", alloc_policy="lowest_id",
                 planes_per_lun=1, policy=2, r2_override=-1,
                 reclaim_enabled=True, spare_blocks=-1)


def _hash_normal(slots):
    """Per-page standard normal from the physical page id (xorshift hash,
    then Box-Muller on the two 16-bit halves)."""
    h = np.asarray(slots).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = h * np.uint32(0x9E3779B9)
        h ^= h >> np.uint32(16)
        h = h * np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h = h * np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


class Drive:
    """One simulated drive. ``cfg`` is the benchmark configuration's dict
    of drive settings (the ``sim`` group of its file)."""

    def __init__(self, cfg: dict, initial_pe: int | None = None,
                 dtype=np.float32):
        for key, want in SUPPORTED.items():
            if cfg[key] != want:
                raise ValueError(f"reference models {key}={want!r} only, "
                                 f"got {cfg[key]!r}")
        self.f = dtype
        self.c = cfg
        self.nd = cfg["n_channels"] * cfg["luns_per_channel"]
        self.nb = self.nd * cfg["blocks_per_plane"]
        self.spb = cfg["slots_per_block"]
        self.ns = self.nb * self.spb
        self.nl = cfg["n_logical"]
        ratio = (PAGES_PER_BLOCK.astype(np.float32)
                 / np.float32(PAGES_PER_BLOCK[QLC]))
        self.ppb = np.maximum((ratio * self.spb).astype(np.int64), 1)
        self.xfer_us = cfg["page_kib"] * 1024 / (cfg["channel_mb_s"] * 1e6) * 1e6
        self._tables()

        L, S, B, spb = self.nl, self.ns, self.nb, self.spb
        n_full, rem = divmod(L, spb)
        # the working set written sequentially into QLC blocks
        self.l2p = np.arange(L, dtype=np.int64)
        self.p2l = np.where(np.arange(S) < L, np.arange(S), -1)
        self.pwm = np.zeros(S, self.f)  # program time of each slot (ms)
        self.mode = np.full(B, QLC)
        self.state = np.full(B, FREE)
        self.state[:n_full] = FULL
        self.next = np.zeros(B, np.int64)
        self.next[:n_full] = spb
        if rem:
            self.state[n_full] = OPEN
            self.next[n_full] = rem
        self.valid = self.next.copy()
        pe = cfg["initial_pe"] if initial_pe is None else initial_pe
        self.pe = np.full(B, pe, np.int64)
        self.reads = np.zeros(B, np.int64)
        self.cold_age = np.zeros(B, np.int64)
        self.heat = np.zeros(L, self.f)
        self.open_user = [-1] * self.nd
        self.open_mig = [-1, -1, -1]
        self.free_count = int((self.state == FREE).sum())
        self.hint = [-1] * self.nd  # a free block per die, maybe stale
        for b in range(B - 1, -1, -1):
            if self.state[b] == FREE:
                self.hint[b % self.nd] = b
        self.clock = self.f(0.0)
        self.die_busy = np.zeros(self.nd, self.f)
        self.chan_busy = np.zeros(cfg["n_channels"], self.f)
        self.lat_hist = np.zeros(N_BINS, np.float64)
        self.w_hist = np.zeros(N_BINS, np.float64)
        self.svc_sum = self.f(0.0)
        self.n_reads = self.n_writes = self.n_retries = 0
        self.n_migrated = self.n_reloc = self.n_erases = 0
        self.conversions = np.zeros((3, 3), np.int64)

    # ---------------- the model's arithmetic, in self.f ----------------

    def _tables(self):
        f = self.f
        self.rber_t = np.array(RBER, np.float32).astype(f)
        self.read_us = np.array(READ_US, np.float32).astype(f)
        self.n_sense = np.array(N_SENSE, np.float32).astype(f)
        self.log_keep = np.log(f(1.0 - DELTA))
        self.e_ldpc = f(E_LDPC)

    def retries(self, mode, pe, age_h, reads, slots):
        """Eq. 1 raw bit error rate -> ceil of Eq. 3, clipped to the
        mode's retry table."""
        f = self.f
        eps, al, k, be, m, n, ga, p, q = (self.rber_t[mode, i] for i in range(9))
        c = np.maximum(np.asarray(pe).astype(f), f(0))
        t = np.maximum(np.asarray(age_h).astype(f), f(0))
        r = np.maximum(np.asarray(reads).astype(f), f(0))
        rber = eps + al * c**k + be * c**m * t**n + ga * c**p * r**q
        h = _hash_normal(slots)
        u1 = ((h & np.uint32(0xFFFF)).astype(f) + f(0.5)) / f(65536.0)
        u2 = (((h >> np.uint32(16)) & np.uint32(0xFFFF)).astype(f) + f(0.5)) / f(65536.0)
        z = np.sqrt(f(-2.0) * np.log(u1)) * np.cos(f(2.0 * np.pi) * u2)
        rber = rber * np.exp(f(PAGE_SIGMA) * z)
        raw = np.log(self.e_ldpc / np.maximum(rber * self.n_sense[mode], f(1e-30)))
        n = np.ceil(np.maximum(raw / self.log_keep, f(0)))
        return np.clip(n.astype(np.int64), 0, np.array(MAX_RETRIES)[mode])

    def read_latency_us(self, mode, retries):
        return self.read_us[mode] * (self.f(1.0) + retries.astype(self.f))

    def age_h(self, slots):
        return self.f(self.c["device_age_h"]) + (self.clock - self.pwm[slots]) / self.f(3.6e6)

    def classify(self, heat):
        return np.where(heat >= HOT_AT, HOT, np.where(heat >= WARM_AT, WARM, COLD))

    @staticmethod
    def latency_bin(lat_us):
        lat = np.maximum(lat_us.astype(np.float32), np.float32(LAT_MIN_US))
        idx = np.floor(np.log10(lat / np.float32(LAT_MIN_US)) * np.float32(BINS_PER_DECADE))
        return np.clip(idx.astype(np.int64), 0, N_BINS - 1)

    def busy(self, die, ms):
        self.die_busy[die] = self.die_busy[die] + self.f(ms)

    # ---------------- allocation and relocation ----------------

    def alloc(self, die=None):
        """A free block: the die's hint when it is still free, else the
        lowest free id (on ``die`` when one is asked for), else -1."""
        dies = [die] if die is not None else range(self.nd)
        for d in dies:
            h = self.hint[d]
            if h >= 0 and self.state[h] == FREE:
                return h
        free = np.nonzero(self.state == FREE)[0]
        if die is not None:
            own = free[free % self.nd == die]
            if len(own):
                return int(own[0])
        return int(free[0]) if len(free) else -1

    def place(self, lpns, tgt, n_dest):
        """Append ``lpns`` in order into open migration blocks of mode
        ``tgt``, opening at most ``n_dest`` blocks (counting the open one)."""
        done = 0
        for _ in range(n_dest):
            cur = self.open_mig[tgt]
            d = cur if cur >= 0 else self.alloc()
            if d < 0:
                continue
            start = int(self.next[d])
            take = min(len(lpns) - done, int(self.ppb[tgt]) - start)
            if take <= 0:
                continue
            if cur < 0:
                self.free_count -= 1
            slots = d * self.spb + start + np.arange(take)
            moved = lpns[done:done + take]
            self.l2p[moved] = slots
            self.p2l[slots] = moved
            self.pwm[slots] = self.clock
            self.n_reloc += take
            self.busy(d % self.nd, self.f(take) * self.f(WRITE_US[tgt]) / self.f(1000.0))
            self.mode[d] = tgt
            self.next[d] += take
            self.valid[d] += take
            full = self.next[d] >= self.ppb[tgt]
            self.state[d] = FULL if full else OPEN
            self.open_mig[tgt] = -1 if full else d
            done += take

    def migrate_pages(self, lpns, tgt):
        """Page-granular conversion of ``lpns`` (read, invalidate, place)."""
        lpns = lpns[lpns >= 0]
        old = self.l2p[lpns]
        keep = (old >= 0) & (self.mode[np.maximum(old, 0) // self.spb] != tgt)
        lpns, old = lpns[keep], old[keep]
        blk = old // self.spb
        src = self.mode[blk]
        lat = self.read_latency_us(
            src, self.retries(src, self.pe[blk], self.age_h(old), self.reads[blk], old))
        for i in range(len(lpns)):
            self.busy(blk[i] % self.nd, lat[i] / self.f(1000.0))
            self.p2l[old[i]] = -1
            self.valid[blk[i]] -= 1
            self.conversions[src[i], tgt] += 1
        self.n_migrated += len(lpns)
        slc_ppb = max(self.spb // 4, 1)
        m = self.c["migrate_pages_per_chunk"]
        self.place(lpns, tgt, -(-m // slc_ppb) + 1)

    def relocate(self, victims, tgt, n_dest):
        """Move every valid page of each victim block into mode ``tgt``,
        then erase the victims."""
        spb = self.spb
        moved = []
        for v in victims:
            slots = v * spb + np.arange(spb)
            lp = self.p2l[slots]
            ok = lp >= 0
            lat = self.read_latency_us(
                self.mode[v], self.retries(self.mode[v], self.pe[v],
                                           self.age_h(slots), self.reads[v], slots))
            self.busy(v % self.nd, np.where(ok, lat, self.f(0)).sum(dtype=self.f)
                      / self.f(1000.0))
            moved.append(lp[ok])
            self.n_migrated += int(ok.sum())
            self.conversions[self.mode[v], tgt] += 1
        self.place(np.concatenate(moved), tgt, n_dest)
        for v in victims:
            self.p2l[v * spb:(v + 1) * spb] = -1
            self.busy(v % self.nd, self.f(ERASE_US[self.mode[v]]) / self.f(1000.0))
            self.pe[v] += 1
            self.reads[v] = self.next[v] = self.valid[v] = self.cold_age[v] = 0
            self.state[v] = FREE
            self.free_count += 1
            self.n_erases += 1
        for d in range(self.nd):
            on_die = [v for v in victims if v % self.nd == d]
            if on_die:
                self.hint[d] = max(on_die)

    # ---------------- one chunk ----------------

    def step(self, lpn, op):
        """Serve one chunk: reads, heat, user writes, conversion triggers,
        capacity recovery, GC, clock."""
        f, c = self.f, self.c
        lpn = np.asarray(lpn, np.int64)
        op = np.asarray(op, np.int64)
        slot = np.where(lpn >= 0, self.l2p[np.maximum(lpn, 0)], -1)
        rd = (op == 0) & (slot >= 0)
        s_rd = slot[rd]
        blk_rd = s_rd // self.spb
        mode_rd = self.mode[blk_rd]
        retr = self.retries(mode_rd, self.pe[blk_rd], self.age_h(s_rd),
                            self.reads[blk_rd], s_rd)
        svc = self.read_latency_us(mode_rd, retr)
        lat = svc + f(self.xfer_us)
        np.add.at(self.lat_hist, self.latency_bin(lat), 1.0)
        self.svc_sum = self.svc_sum + lat.sum(dtype=f) / f(1000.0)
        for d in range(self.nd):
            self.busy(d, svc[blk_rd % self.nd == d].sum(dtype=f) / f(1000.0))
        for ch in range(len(self.chan_busy)):
            n_ch = ((blk_rd % self.nd) % len(self.chan_busy) == ch).sum()
            self.chan_busy[ch] += f(n_ch) * f(self.xfer_us) / f(1000.0)
        np.add.at(self.reads, blk_rd, 1)
        self.n_reads += int(rd.sum())
        self.n_retries += int(retr.sum())

        self.heat = self.heat * f(HEAT_DECAY)
        touched = lpn[rd | (op == 1)]
        np.add.at(self.heat, touched, f(1.0))

        for i in np.nonzero((op == 1) & (lpn >= 0))[0]:
            self.write(int(lpn[i]))

        # RARO (Table II) on the chunk's distinct read pages, hottest first
        uniq = np.unique(lpn[rd])
        us = self.l2p[uniq]
        ub = us // self.spb
        um = self.mode[ub]
        ur = self.retries(um, self.pe[ub], self.age_h(us), self.reads[ub], us)
        uh = self.heat[uniq]
        cls = self.classify(uh)
        stage = np.where(self.pe[ub] <= 333, 0, np.where(self.pe[ub] <= 666, 1, 2))
        r2 = np.array(R2_BY_STAGE)[stage]
        r1 = c["r1"]
        target = um.copy()
        target[(um == QLC) & (cls == WARM) & (ur >= r2)] = TLC
        target[(um == QLC) & (cls == HOT) & (ur >= r1)] = SLC
        target[(um == TLC) & (cls == HOT) & (ur >= r1)] = SLC
        m = c["migrate_pages_per_chunk"]
        slc_ppb = max(self.spb // 4, 1)
        for tgt in (SLC, TLC):
            cand = np.nonzero((target == tgt) & (um > tgt))[0]
            order = cand[np.argsort(-uh[cand].astype(np.float64), kind="stable")][:m]
            if len(order) and self.free_count >= -(-m // slc_ppb) + 3:
                self.migrate_pages(uniq[order], tgt)

        # elastic capacity recovery: demote long-cold SLC/TLC blocks
        hw = rd & (self.classify(self.heat[np.maximum(lpn, 0)]) >= WARM)
        warm_blk = np.zeros(self.nb, bool)
        warm_blk[slot[hw] // self.spb] = True
        self.cold_age = np.where(warm_blk, 0, self.cold_age + 1)
        free_frac = self.free_count / self.nb
        if free_frac < RECLAIM_LOW:
            self.reclaim()
        if self.free_count < c["gc_free_threshold"]:
            self.gc()
        self.clock = max(self.clock, self.die_busy.max())

    def write(self, lpn):
        """One user write onto the open block of die ``lpn % n_dies``."""
        die = lpn % self.nd
        d = self.open_user[die]
        if d < 0 or self.next[d] >= self.ppb[QLC]:
            d = self.alloc(die)
            if d < 0:
                return
            self.mode[d] = QLC
            self.state[d] = OPEN
            self.free_count -= 1
        old = self.l2p[lpn]
        if old >= 0:
            self.p2l[old] = -1
            self.valid[old // self.spb] -= 1
        s = d * self.spb + self.next[d]
        self.l2p[lpn] = s
        self.p2l[s] = lpn
        self.pwm[s] = self.clock
        self.next[d] += 1
        self.valid[d] += 1
        full = self.next[d] >= self.ppb[QLC]
        if full:
            self.state[d] = FULL
        self.open_user[die] = -1 if full else d
        self.busy(die, self.f(WRITE_US[QLC]) / self.f(1000.0))
        self.n_writes += 1
        w = np.array([WRITE_US[QLC] + self.xfer_us], np.float32)
        self.w_hist[self.latency_bin(w)[0]] += 1.0

    def reclaim(self):
        """Demote the coldest full SLC/TLC blocks one density level."""
        f = self.f
        k = self.c["max_conversions_per_chunk"]
        page_heat = np.where(self.p2l >= 0, self.heat[np.maximum(self.p2l, 0)], f(0))
        block_heat = np.maximum(page_heat.reshape(self.nb, self.spb).max(1), f(0))
        ok = (self.state == FULL) & (self.mode < QLC) & (self.cold_age >= COLD_EPOCHS)
        score = self.cold_age.astype(f) - f(1e-3) * block_heat
        cand = np.nonzero(ok)[0]
        top = cand[np.argsort(-score[cand].astype(np.float64), kind="stable")][:k]
        tgt_of = self.mode[top] + 1
        for tgt in (TLC, QLC):
            group = [int(v) for v, t in zip(top, tgt_of)
                     if t == tgt and self.state[v] == FULL]
            need = -(-k * int(self.ppb[tgt - 1]) // int(self.ppb[tgt])) + 1
            if group and self.free_count >= need + 2:
                self.relocate(group, tgt, need)

    def gc(self):
        """Greedy GC: the full blocks with the fewest valid pages."""
        f = self.f
        k = min(max(self.c["gc_victims_per_pass"], 1), self.nb)
        reclaimable = (self.state == FULL) & (self.valid < self.ppb[self.mode])
        cand = np.nonzero(reclaimable)[0]
        top = cand[np.argsort(self.valid[cand], kind="stable")][:k]
        if not len(top):
            return
        net = (f(1.0) - self.valid[top].astype(f) / self.ppb[self.mode[top]].astype(f))
        before = np.concatenate([[f(0)], np.cumsum(net, dtype=f)[:-1]]).astype(f)
        deficit = f(self.c["gc_free_threshold"] - self.free_count)
        take = (before < deficit) | ((net >= f(0.5) * net[0]) & (before < deficit + f(k - 1)))
        vm = self.mode[top]
        tgt = int(np.argmax(np.bincount(vm[take], minlength=3)))
        group = [int(v) for v, t, m in zip(top, take, vm)
                 if t and m == tgt and self.state[v] == FULL]
        if group and self.free_count >= MAX_DEST + (k - 1) + 2:
            self.relocate(group, tgt, k + 1)

    # ---------------- summary ----------------

    def summary(self) -> dict:
        ppb = np.where(self.state == FREE, self.ppb[QLC], self.ppb[self.mode])
        cap = float(np.float32(ppb.sum()) * np.float32(self.c["page_kib"] * 1024)
                    / np.float32(2**30))
        reads = float(self.n_reads)
        makespan = float(max(self.die_busy.max(), self.chan_busy.max()))
        mean_ms = float(self.svc_sum) / max(reads, 1.0)
        p = percentiles(self.lat_hist)
        return dict(
            reads=reads,
            writes=float(self.n_writes),
            retries_per_read=self.n_retries / max(reads, 1.0),
            mean_read_latency_us=mean_ms * 1000.0,
            read_lat_p50_us=p[0.5],
            read_lat_p95_us=p[0.95],
            read_lat_p99_us=p[0.99],
            iops=reads / max(makespan / 1000.0, 1e-9),
            migrated_pages=float(self.n_migrated),
            reloc_pages=float(self.n_reloc),
            erases=float(self.n_erases),
            conversions=self.conversions.astype(float).tolist(),
            capacity_gib=cap,
        )


def percentiles(hist, qs=(0.5, 0.95, 0.99, 0.999)) -> dict:
    """Quantiles (us) of a latency count histogram, interpolated
    geometrically inside the bin that holds each one."""
    h = np.asarray(hist, np.float64)
    total = h.sum()
    if total <= 0:
        return {q: 0.0 for q in qs}
    edges = LAT_MIN_US * 10.0 ** (np.arange(N_BINS + 1) / BINS_PER_DECADE)
    cum = np.cumsum(h)
    nonempty = np.nonzero(h > 0)[0]
    out = {}
    for q in qs:
        b = int(np.searchsorted(cum, q * total, side="left"))
        if b >= N_BINS or h[b] <= 0:
            later = nonempty[nonempty > b] if b < N_BINS else nonempty[:0]
            b = int(later[0]) if len(later) else int(nonempty[-1])
        prev = cum[b - 1] if b > 0 else 0.0
        frac = min(max((q * total - prev) / h[b], 0.0), 1.0)
        lo, hi = edges[b], edges[b + 1]
        out[q] = float(lo * (hi / lo) ** frac)
    return out


def simulate(cfg: dict, lpn, op, initial_pe: int | None = None,
             dtype=np.float32) -> dict:
    """Run one drive over a packed trace ``(n_chunks, chunk)`` and return
    its summary."""
    drive = Drive(cfg, initial_pe=initial_pe, dtype=dtype)
    for i in range(lpn.shape[0]):
        drive.step(lpn[i], op[i])
    return drive.summary()
