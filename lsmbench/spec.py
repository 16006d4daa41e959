"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs`` entry, whose ``file`` holds
the deployment) and a traffic mix (``mixes/<traffic>.json``).  Metrics
are read by ``metrics/<family>.py``, the family being the metric's name
up to its first dot.
"""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


def load(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str, root: Path = ROOT):
    """``(workload, config, mix)`` of the cell called ``name``, its files
    read from under ``root``."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = json.loads((Path(root) / entry["file"]).read_text())
    mix = json.loads((Path(root) / HERE.name / "mixes"
                      / f"{wl['traffic']}.json").read_text())
    return wl, cfg, mix


def metrics_for(bench: dict, name: str, traced: bool) -> list[dict]:
    """The metrics cell ``name`` reports: its end-to-end metrics in an
    untraced run, its per-layer metrics in a traced one."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
