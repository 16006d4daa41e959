"""The restored starting state of each configuration at a tiny record
count: the shape the policy holds, accepted by the policy, and still
answering like the reference after a few pumps of merges."""
import json
from pathlib import Path

import numpy as np
import pytest

from lsmbench import check, store
from lsmbench.keys import record_keys
from lsmbench.shapes import partitioned_leveling, tiering
from lsmbench.tests.tiny import config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    return config(cfg)


def test_leveling_shape_at_paper_size():
    cfg = json.loads((CONFIGS / "paper-partitioned-leveling.json")
                     .read_text())
    runs = partitioned_leveling.layout(cfg)
    assert [(r["level"], r["files"]) for r in runs] == \
        [(3, 1308), (2, 199), (1, 19)]
    assert runs[-1]["hi"] == cfg["records"]
    assert sum(r["files"] for r in runs) == 1526


def test_tiering_shape_is_the_base_t_digits_of_the_flushes():
    cfg = json.loads((CONFIGS / "paper-tiering.json").read_text())
    runs = tiering.layout(cfg)
    mem = cfg["memtable_entries"]
    # 711 whole flushes = 2*243 + 2*81 + 2*27 + 1*9, no partial memtable
    assert [(r["level"], (r["hi"] - r["lo"]) // mem) for r in runs] == \
        [(5, 243), (5, 243), (4, 81), (4, 81), (3, 27), (3, 27), (2, 9)]
    assert runs[-1]["hi"] == cfg["records"] == 711 * mem
    assert [r["stamp"] for r in runs] == [1, 2, 3, 4, 5, 6, 7]
    # a load reaches a run of 3**6 memtables only at 729 flushes
    assert 729 * mem > cfg["records"]


@pytest.mark.parametrize("name", ["paper-partitioned-leveling",
                                  "paper-tiering"])
def test_restored_state_is_at_rest_and_matches_the_reference(name,
                                                             tmp_path):
    cfg = _cfg(name)
    be = store.counting_backend("host")
    group, _ = store.build(cfg, 7, be, tmp_path)
    n = cfg["records"]
    assert group.total_entries() == n
    # the policy accepts the shape: no merge is due at rest
    group.pump(1 << 20)
    assert not group.running and group.stats["merges"] == 0
    keys = record_keys(np.arange(n))
    vals = store.record_values(7, n)
    hist = check.history(keys, vals, np.empty(0, np.uint32),
                         np.empty(0, np.int32))
    f, v = group.get_batch(keys[::7])
    assert check.read_mismatches(hist, [(0, keys[::7], f, v)]) == 0
    group.close()


@pytest.mark.parametrize("name", ["paper-partitioned-leveling",
                                  "paper-tiering"])
def test_merges_after_writes_still_match_the_reference(name, tmp_path):
    cfg = _cfg(name)
    be = store.counting_backend("host")
    group, _ = store.build(cfg, 11, be, tmp_path)
    n = cfg["records"]
    rng = np.random.default_rng(3)
    wk, wv = [], []
    # updates and inserts, several memtables' worth, pumped in small
    # quanta so merges stream across pumps
    for step in range(24):
        k = record_keys(np.concatenate([rng.integers(0, n, 200),
                                        n + step * 100 + np.arange(100)]))
        v = rng.integers(-2 ** 31 + 1, 2 ** 31, len(k), dtype=np.int32)
        done = 0
        while done < len(k):
            a = group.put_batch(k[done:], v[done:])
            wk.append(k[done:done + a])
            wv.append(v[done:done + a])
            done += a
            group.pump(700)
        group.pump(700)
    assert group.stats["merges"] > 0
    wk, wv = np.concatenate(wk), np.concatenate(wv)
    hist = check.history(record_keys(np.arange(n)),
                         store.record_values(11, n), wk, wv)
    q = np.concatenate([wk, record_keys(rng.integers(0, n, 2000))])
    f, v = group.get_batch(q)
    assert check.read_mismatches(hist, [(len(wk), q, f, v)]) == 0
    lo = int(record_keys(np.array([5]))[0])
    ks, vs = group.scan_range(lo, lo + 2 ** 26)
    assert check.scan_mismatches(
        hist, [(len(wk), lo, lo + 2 ** 26, ks, vs)]) == 0
    group.close()


def test_drop_oldest_loses_the_oldest_run(tmp_path):
    cfg = _cfg("paper-tiering")
    group, _ = store.build(cfg, 7, store.counting_backend("host"),
                           tmp_path, drop_oldest=True)
    oldest = tiering.layout(cfg)[0]
    assert group.total_entries() == cfg["records"] - (oldest["hi"]
                                                      - oldest["lo"])
    group.close()
