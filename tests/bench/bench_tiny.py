"""A benchmark root at unit-test size: the repository's BENCHMARK.json and
benchmark files, with every configuration cut to the simulator's tiny test
geometry, written to a temporary directory."""

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
TINY_SIM = dict(blocks_per_plane=16, slots_per_block=64, n_logical=1536, chunk=128,
                migrate_pages_per_chunk=16, max_conversions_per_chunk=2,
                gc_free_threshold=2, gc_victims_per_pass=2)
TINY_REQUESTS = 12 * 128


def tiny_root(dst: Path) -> Path:
    shutil.copytree(REPO / "bench", dst / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        conf["sim"].update(TINY_SIM)
        conf["requests_per_run"] = TINY_REQUESTS
        (dst / c["file"]).write_text(json.dumps(conf))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def cell_names() -> list[str]:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]
