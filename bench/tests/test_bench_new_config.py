"""A new configuration and cell enter the benchmark as new files and
``BENCHMARK.json`` entries alone.

In a copy of ``bench/`` and ``BENCHMARK.json``, the test adds a
configuration (``graph500-kron-s15`` under a new name, with a ``small`` of
its own), the new cell's limits, and a cell that pairs the configuration
with the ``pagerank`` traffic, listed in the ``workloads`` of every metric
that the cell reports.  It then runs the cell on the CPU as the other tests
run every cell.  No file of the real tree may change.
"""
import hashlib
import json
import shutil

from bench.harness import manifest as M
from bench.harness import runner

from .small import run_small

REAL_ROOT = M.ROOT
BASE_CONFIG, BASE_CELL, TRAFFIC = "graph500-kron-s15", "kron15.pagerank", \
    "pagerank"
CONFIG, CELL = "graph500-kron-copy", "kroncopy.pagerank"
SMALL = {"scale": 7}


def tree_digest(root) -> dict:
    files = [root / "BENCHMARK.json"] + sorted(
        p for p in (root / "bench").rglob("*")
        if p.is_file() and not {"__pycache__", ".out"} & set(p.parts))
    return {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in files}


def write_json(path, obj):
    path.write_text(json.dumps(obj, indent=2))


def add_config_and_cell(root):
    bench_dir = root / "bench"
    cfg = M.load_json(bench_dir / "configs" / f"{BASE_CONFIG}.json")
    write_json(bench_dir / "configs" / f"{CONFIG}.json",
               dict(cfg, name=CONFIG, small=SMALL))
    shutil.copy(bench_dir / "cells" / f"{BASE_CELL}.json",
                bench_dir / "cells" / f"{CELL}.json")

    bench = M.load_json(root / "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == BASE_CONFIG)
    bench["configs"].append(dict(entry, name=CONFIG,
                                 file=f"bench/configs/{CONFIG}.json"))
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "a copy of the Kronecker configuration under a new name"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if BASE_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    write_json(root / "BENCHMARK.json", bench)


def test_new_config_and_cell_run_from_new_files_alone(tmp_path, monkeypatch):
    before = tree_digest(REAL_ROOT)
    shutil.copy(REAL_ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(REAL_ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out",
                                                  "tests"))
    add_config_and_cell(tmp_path)
    monkeypatch.setattr(M, "ROOT", tmp_path)
    monkeypatch.setattr(M, "BENCH", tmp_path / "bench")

    cell, base = M.resolve(CELL), M.resolve(BASE_CELL)
    assert cell.config["small"] == SMALL
    assert cell.end_to_end == base.end_to_end
    assert cell.per_layer == base.per_layer

    sizes = []
    run = runner.run

    def spy(*args, **kwargs):
        sizes.append(kwargs["config_params"])
        return run(*args, **kwargs)

    monkeypatch.setattr(runner, "run", spy)
    res = run_small(CELL)
    assert sizes == [SMALL]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert tree_digest(REAL_ROOT) == before
