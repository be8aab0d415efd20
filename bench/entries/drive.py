"""Entry of one simulated drive at a time: the window calls ``engine.run``
on a trace from a pool built from the seed, waits, then calls
``engine.summarize``, as a user runs one drive."""

from __future__ import annotations

import numpy as np

from bench import harness


class Runner:
    def __init__(self, cell: harness.Cell, seed: int):
        self.cfg = harness.sim_config(cell.config)
        self.n = cell.config["requests_per_run"]
        self.pe = cell.config["sim"]["initial_pe"]
        self.pool = []
        for s in np.random.default_rng(seed).integers(0, 2**62, size=harness.POOL):
            tr = cell.trace(self.cfg.n_logical, self.cfg.chunk, self.n, int(s))
            self.pool.append((int(s), tr, harness.reads_of(tr)))
        self.i = 0
        self.times = {}

    def warm(self):
        self.one()
        self.i = 0

    def served(self, k: int) -> list[harness.Sim]:
        """The first ``k`` runs a window serves, not yet run."""
        return [harness.Sim(seed, self.pe, self.n, reads, tr["lpn"].shape[0], {})
                for seed, tr, reads in (self.pool[i % len(self.pool)]
                                        for i in range(k))]

    def one(self) -> list[harness.Sim]:
        import jax
        from repro.ssdsim import engine

        seed, tr, reads = self.pool[self.i % len(self.pool)]
        self.i += 1
        self.times = {}
        with harness.span("bench.dispatch", self.times):
            s, _ = engine.run(self.cfg, tr)
        with harness.span("bench.wait", self.times):
            s = jax.block_until_ready(s)
        with harness.span("bench.summarize", self.times):
            summary = engine.summarize(s, self.cfg)
        return [harness.Sim(seed, self.pe, self.n, reads, tr["lpn"].shape[0],
                            summary, s)]

    def trace_of(self, sim: harness.Sim) -> dict:
        return next(tr for s, tr, _ in self.pool if s == sim.seed)
