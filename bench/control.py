"""The control of the benchmark's ``correct``, at the cells' own sizes.

    python3 bench/control.py --seeds 3 [--cells CELL ...]

For each cell and seed it builds the cell as a run with that seed does
(``harness.find_cell`` and ``harness.make``), takes the runs its window
serves first, as many as the check samples, and runs the plain reference
on their very traces and wear in float32 and again in bfloat16 (the
control, one precision below the float32 the simulator states, put in the
program's place). It prints the compared numbers of the control against
the reference: the upper readings from which the limits in
``bench/limits`` were set. It runs on the host alone, one process per
(cell, seed), and touches no accelerator.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _one(job):
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import ml_dtypes

    from bench import harness, reference, verify

    root, name, seed = job
    cell = harness.find_cell(name, root)
    runner = harness.make(cell, seed)
    out = []
    for sim in runner.served(cell.sample):
        tr = runner.trace_of(sim)
        ref = reference.simulate(cell.config["sim"], tr["lpn"], tr["op"],
                                 initial_pe=sim.initial_pe)
        ctl = reference.simulate(cell.config["sim"], tr["lpn"], tr["op"],
                                 initial_pe=sim.initial_pe, dtype=ml_dtypes.bfloat16)
        out.append({"trace_seed": sim.seed, "initial_pe": sim.initial_pe,
                    "control": verify.gaps(ctl, ref), "detail": verify.detail(ctl, ref)})
    return name, seed, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--cells", nargs="*")
    ap.add_argument("--root", type=Path, default=ROOT, help="benchmark root")
    ap.add_argument("--procs", type=int, default=4)
    args = ap.parse_args(argv)
    bench = json.loads((args.root / "BENCHMARK.json").read_text())
    cells = args.cells or [w["name"] for w in bench["workloads"]]
    jobs = [(args.root, c, args.first_seed + i) for c in cells for i in range(args.seeds)]
    with mp.get_context("spawn").Pool(min(len(jobs), args.procs)) as pool:
        for name, seed, runs in pool.imap(_one, jobs):
            least = min(r["control"]["stats_gap"] for r in runs)
            print(json.dumps({"cell": name, "seed": seed, "least_stats_gap": least,
                              "runs": runs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
