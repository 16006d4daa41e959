"""Each cell shrunk to run in a second on the CPU through the whole
harness: its configuration and mix files are copied under a directory
of their own with a tiny record count, short warm-ups and a low rate,
and ``run.run_cell`` reads them from there.  On the CPU the data plane
runs on the host, so ``not_compiled`` counts every decision."""
from __future__ import annotations

import json
import time
from pathlib import Path

from lsmbench import run, spec, store

SEED = 2 ** 33 + 17          # larger than 32 signed bits hold

#: a test-only cell that drives the scan path of the front door and the
#: check: YCSB E's scans beside reads, updates and inserts
SCAN_CELL = {"name": "tiering.scan", "config": "paper-tiering",
             "traffic": "scan", "chips": 1, "why": "scans, tests only"}
SCAN_MIX = {"name": "scan", "source": "tests", "rate_ops_per_s": 1000,
            "ops": {"scan": 0.4, "read": 0.3, "update": 0.2,
                    "insert": 0.1},
            "key_dist": "scrambled_zipfian", "zipf_theta": 0.99,
            "scan_length": {"min": 1, "max": 100}, "max_batch": 4096,
            "warmup_s": 1}


def config(cfg: dict) -> dict:
    """A configuration cut to a tiny size, its shape kept."""
    pa = dict(cfg["policy_args"])
    if "file_entries" in pa:
        pa.update(file_entries=256, l1_capacity=2048)
    return {**cfg, "records": 1 << 14, "memtable_entries": 512,
            "policy_args": pa, "io_budget_bytes_per_s": 1024 * 200_000,
            "wal": {"group_commit_entries": 256, "segment_entries": 4096}}


def mix(m: dict) -> dict:
    return {**m, "rate_ops_per_s": min(m["rate_ops_per_s"], 2000),
            "warmup_s": 0.3, "warmup_burst_ops": 2048}


def make_root(root: Path) -> dict:
    """Writes the tiny cells' files under ``root``; returns their
    ``BENCHMARK.json``."""
    b = spec.load()
    b["workloads"] = b["workloads"] + [SCAN_CELL]
    for entry in b["configs"]:
        cfg = json.loads((spec.ROOT / entry["file"]).read_text())
        dst = root / entry["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(json.dumps(config(cfg)))
    mixes = root / spec.HERE.name / "mixes"
    mixes.mkdir(parents=True, exist_ok=True)
    for src in (spec.HERE / "mixes").glob("*.json"):
        (mixes / src.name).write_text(
            json.dumps(mix(json.loads(src.read_text()))))
    (mixes / "scan.json").write_text(json.dumps(mix(SCAN_MIX)))
    return b


class Tiny:
    def __init__(self, root: Path):
        self.root = root
        self.bench = make_root(root)
        self.cells = [w["name"] for w in self.bench["workloads"]]

    def cell(self, name: str):
        return spec.cell(self.bench, name, self.root)

    def run(self, name: str, seed: int = SEED, seconds: float = 0.5,
            traced: bool = False, build=store.build) -> dict:
        return run.run_cell(self.bench, name, seed, seconds, traced,
                            time.perf_counter(), self.root, build)
