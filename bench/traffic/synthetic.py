"""The benchmark's general synthetic traffic generator.

A traffic mix is a JSON file beside this one, ``<traffic>.json``, that
names its generator module under ``"generator"`` and gives its parameters.
This generator covers the FIO-like Zipf read and mixed read/write traces of
the simulator's workload module (paper section V-A), copied here so that a
change to the program cannot move the traffic the benchmark offers:

- ``theta``: Zipf popularity of reads, hot ranks scattered over the logical
  space by a permutation, as FIO's zipf decouples rank from LBA;
- ``read_frac`` (default 1): share of reads; the rest are overwrites;
- ``write_theta`` (default none: uniform): Zipf popularity of overwrites
  over an independent permutation, as YCSB's Zipfian updates.

Output is the engine's packed trace: ``lpn`` and ``op`` arrays of shape
``(n_chunks, chunk)``, padded with inactive reads (``lpn`` -1).
"""

from __future__ import annotations

import numpy as np

OP_READ, OP_WRITE = 0, 1


def _pack(chunk: int, lpn: np.ndarray, op: np.ndarray) -> dict:
    n_chunks = -(-len(lpn) // chunk)
    pad = n_chunks * chunk - len(lpn)
    lpn = np.concatenate([lpn, np.full(pad, -1)]).astype(np.int32)
    op = np.concatenate([op, np.full(pad, OP_READ)]).astype(np.int32)
    return {"lpn": lpn.reshape(n_chunks, chunk), "op": op.reshape(n_chunks, chunk)}


def _zipf_probs(n: int, theta: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -theta
    return w / w.sum()


def _zipf(rng, n: int, size: int, theta: float) -> np.ndarray:
    ranks = rng.choice(n, size=size, p=_zipf_probs(n, theta))
    return rng.permutation(n)[ranks]


def build(params: dict, n_logical: int, chunk: int, n_requests: int,
          seed: int) -> dict:
    """The packed trace of one simulated run of the mix ``params``."""
    rng = np.random.default_rng(seed)
    r_lpn = _zipf(rng, n_logical, n_requests, params["theta"])
    read_frac = params.get("read_frac", 1.0)
    if read_frac >= 1.0:
        return _pack(chunk, r_lpn, np.full(n_requests, OP_READ))
    if params.get("write_theta") is None:
        w_lpn = rng.integers(0, n_logical, size=n_requests)
    else:
        w_lpn = _zipf(rng, n_logical, n_requests, params["write_theta"])
    is_read = rng.random(n_requests) < read_frac
    return _pack(chunk, np.where(is_read, r_lpn, w_lpn),
                 np.where(is_read, OP_READ, OP_WRITE))
