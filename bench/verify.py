"""The comparison that decides ``correct``.

Two numbers compare a simulated run's summary with the plain reference's
summary of the same trace:

- ``stats_gap``: the widest gap over every simulated statistic the run
  reports. The read path: retries per read, mean and p50/p95/p99 read
  latency and the simulated IOPS, each as a share of the reference's
  value. The write and relocation path: migrated and relocated pages,
  erases and usable capacity, each against the reference's value (or 1, if
  larger), and each entry of the from-mode x to-mode conversions matrix
  against the reference's total of conversions.
- ``count_gap``: reads and user writes served, an exact difference.

``invariant_faults`` counts the FTL consistency rules that a final drive
state breaks (the mapping a bijection, per-block counts equal to a
recount, the free pool exact, cursors on open blocks).
"""

from __future__ import annotations

import numpy as np

READ_KEYS = ("retries_per_read", "mean_read_latency_us", "read_lat_p50_us",
             "read_lat_p95_us", "read_lat_p99_us", "iops")
RELOC_KEYS = ("migrated_pages", "reloc_pages", "erases", "capacity_gib")
COUNT_KEYS = ("reads", "writes")


def _rel(a, b, floor: float) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), floor)))


def gaps(program: dict, ref: dict) -> dict:
    """The compared numbers for one run: ``program``'s summary against the
    reference's ``ref``."""
    conv_p = np.asarray(program["conversions"], np.float64)
    conv_r = np.asarray(ref["conversions"], np.float64)
    conv = float(np.abs(conv_p - conv_r).max() / max(conv_r.sum(), 1.0))
    return {
        "stats_gap": max([_rel(program[k], ref[k], 1e-30) for k in READ_KEYS]
                         + [_rel(program[k], ref[k], 1.0) for k in RELOC_KEYS] + [conv]),
        "count_gap": max(abs(float(program[k]) - float(ref[k])) for k in COUNT_KEYS),
    }


def detail(program: dict, ref: dict) -> dict:
    """Every compared statistic of one run, the program's beside the
    reference's."""
    keys = READ_KEYS + RELOC_KEYS + COUNT_KEYS + ("conversions",)
    return {k: [program[k], ref[k]] for k in keys}


def passes(values: dict, limits: dict) -> bool:
    return all(v <= limits[k] for k, v in values.items())


FIELDS = ("l2p", "p2l", "block_mode", "block_state", "block_next", "block_valid",
          "free_count", "free_hint", "open_user", "open_mig")


def host_state(state) -> dict:
    """The drive-state fields the consistency rules read, on the host."""
    import jax

    return {k: np.asarray(v) for k, v in
            zip(FIELDS, jax.device_get([getattr(state, k) for k in FIELDS]))}


def invariant_faults(s: dict, sim: dict) -> list[str]:
    """Names of the FTL consistency rules the state ``s`` breaks."""
    free, open_ = 0, 1
    spb = sim["slots_per_block"]
    n_dies = sim["n_channels"] * sim["luns_per_channel"]
    n_blocks = n_dies * sim["planes_per_lun"] * sim["blocks_per_plane"]
    n_slots, n_logical = n_blocks * spb, sim["n_logical"]
    ppb = np.maximum((np.array([256, 768, 1024], np.float32) / np.float32(1024)
                      * spb).astype(np.int64), 1)
    l2p, p2l = s["l2p"].astype(np.int64), s["p2l"].astype(np.int64)
    bm, bs = s["block_mode"], s["block_state"]
    bn, bv = s["block_next"], s["block_valid"]
    mapped = np.nonzero(l2p >= 0)[0]
    vslots = np.nonzero(p2l >= 0)[0]
    rules = {
        "l2p in range": (l2p[mapped] < n_slots).all(),
        "p2l in range": (p2l[vslots] < n_logical).all(),
        "l2p -> p2l": (p2l[np.minimum(l2p[mapped], n_slots - 1)] == mapped).all(),
        "p2l -> l2p": (l2p[np.minimum(p2l[vslots], n_logical - 1)] == vslots).all(),
        "block_valid recount": (bv == np.bincount(vslots // spb, minlength=n_blocks)).all(),
        "block_mode range": ((bm >= 0) & (bm < 3)).all(),
        "block_state range": ((bs >= free) & (bs <= 3)).all(),
        "block_next within pages": (bn[bs != free] <= ppb[np.clip(bm[bs != free], 0, 2)]).all(),
        "valid <= programmed": (bn >= bv).all(),
        "free blocks empty": (bn[bs == free] == 0).all() and (bv[bs == free] == 0).all(),
        "valid slot programmed": (vslots % spb < bn[vslots // spb]).all(),
        "free_count exact": int(s["free_count"]) == int((bs == free).sum()),
        "free_hint on its die": all(h == -1 or (0 <= h < n_blocks and h % n_dies == d)
                                    for d, h in enumerate(s["free_hint"])),
    }
    for name in ("open_user", "open_mig"):
        cur = s[name]
        live = cur[cur >= 0]
        rules[f"{name} on open blocks"] = (
            ((cur >= -1) & (cur < n_blocks)).all() and (bs[live] == open_).all())
    om = s["open_mig"]
    rules["open_mig mode"] = (bm[om[om >= 0]] == np.arange(3)[om >= 0]).all()
    return [name for name, ok in rules.items() if not ok]
