"""Entry of a grid of drives: the window calls ``sweep.run_sweep`` on wear
stages x seeds, its traces built through a scenario that the benchmark
registers from the cell's own traffic generator. A cell on more than one
chip shards the grid's run axis over its chips."""

from __future__ import annotations

import numpy as np

from bench import harness


class Runner:
    def __init__(self, cell: harness.Cell, seed: int):
        from repro.experiments import registry, sweep

        self.cell = cell
        self.cfg = harness.sim_config(cell.config)
        self.n = cell.config["requests_per_run"]
        self.devices = cell.chips if cell.chips > 1 else None
        name = f"bench.{cell.name}"

        def build(cfg, n_requests, seed=0):
            return cell.trace(cfg.n_logical, cfg.chunk, n_requests, seed)

        if name not in registry.SCENARIOS:
            registry.register(name)(build)
        k = cell.config["seeds_per_stage"]
        rng = np.random.default_rng(seed)
        self.specs = [
            sweep.SweepSpec(
                scenario=name, n_requests=self.n, policies=(self.cfg.policy,),
                initial_pe=tuple(cell.config["initial_pe"]),
                seeds=tuple(int(s) for s in rng.integers(0, 2**62, size=k)),
                base=self.cfg,
            )
            for _ in range(harness.POOL)
        ]
        self.chunks = -(-self.n // self.cfg.chunk)
        # reads each lane's trace holds, for the check of every lane
        self.reads = {s: harness.reads_of(self._trace(s))
                      for spec in self.specs for s in spec.seeds}
        self.i = 0
        self.times = {}

    def _trace(self, seed: int) -> dict:
        return self.cell.trace(self.cfg.n_logical, self.cfg.chunk, self.n, seed)

    def warm(self):
        self.one()
        self.i = 0

    def served(self, k: int) -> list[harness.Sim]:
        """The first ``k`` lanes a window serves, not yet run."""
        lanes = [harness.Sim(s, pe, self.n, self.reads[s], self.chunks, {})
                 for spec in self.specs for s in spec.seeds for pe in spec.initial_pe]
        return lanes[:k]

    def one(self) -> list[harness.Sim]:
        from repro.experiments import sweep

        spec = self.specs[self.i % len(self.specs)]
        self.i += 1
        self.times = {}
        with harness.span("bench.sweep", self.times):
            results = sweep.run_sweep(spec, devices=self.devices)
        return [harness.Sim(r["run"]["seed"], r["run"]["initial_pe"], self.n,
                            self.reads[r["run"]["seed"]], self.chunks, r)
                for r in results]

    def trace_of(self, sim: harness.Sim) -> dict:
        return self._trace(sim.seed)
