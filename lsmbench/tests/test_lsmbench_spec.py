"""``BENCHMARK.json`` keeps to its contract: names, units and keys, and
every cell's files and readers are where the harness looks."""
import json
import re

import pytest

from lsmbench import spec
from lsmbench.metrics import reader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
BENCH = spec.load()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(LINE.match(w) for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert (spec.ROOT / p).is_dir()


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_configs(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert LINE.match(entry["source"]) and LINE.match(entry["why"])
    cfg = json.loads((spec.ROOT / entry["file"]).read_text())
    assert entry["file"].startswith(tuple(BENCH["paths"]))
    assert cfg["name"] == entry["name"]
    assert all(NAME.match(k) for k in entry["reduced"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert cfg["source"] and cfg["assumed"]


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workloads(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(wl["name"]) and NAME.match(wl["traffic"])
    assert wl["chips"] in (1, 4)
    assert LINE.match(wl["why"])
    spec.cell(BENCH, wl["name"])              # its files exist
    e2e = spec.metrics_for(BENCH, wl["name"], False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert spec.metrics_for(BENCH, wl["name"], True)


def test_names_are_unique():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(m):
    e2e = m in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if e2e else {"layer", "moves"}
    assert keys <= set(m) <= keys | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    reader(m["name"])                         # its reader exists
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        moved = next(x for x in BENCH["end_to_end"]
                     if x["name"] == m["moves"])
        for cell in m.get("workloads", []):
            assert cell in moved.get("workloads", [cell])
    for cell in m.get("workloads", []):
        assert cell in {w["name"] for w in BENCH["workloads"]}


def test_files_under_paths_are_named_from_name_characters():
    root = spec.ROOT / "lsmbench"
    for p in root.rglob("*"):
        if "__pycache__" in p.parts or not p.is_file():
            continue
        rel = p.relative_to(spec.ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_file_is_small():
    assert (spec.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
