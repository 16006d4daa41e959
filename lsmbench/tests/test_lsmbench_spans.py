"""The reduction of the program's own spans on a hand-made trace, the
metrics read from them, and the whole tool on a tiny cell on the CPU."""
import types

import pytest

from lsmbench import spans
from lsmbench import trace as tr
from lsmbench.metrics import reader

MS = 1e6   # ns

CLIENT = [
    ("lsmbench.window", 0 * MS, 100 * MS),
    ("lsmbench.get", 10 * MS, 30 * MS),
    ("lsm.get", 10.5 * MS, 29 * MS),
    ("lsm.lock", 10.5 * MS, 0.5 * MS),
    ("lsm.get.memtables", 11 * MS, 1 * MS),
    ("lsm.get.filters", 12 * MS, 1 * MS),
    ("lsm.get.probe", 13 * MS, 7 * MS),
    ("lsm.get.search", 20 * MS, 19 * MS),
    ("lsmbench.scan", 60 * MS, 20 * MS),
    ("lsm.scan", 60 * MS, 20 * MS),
    ("lsm.lock", 60 * MS, 0.5 * MS),
    ("lsm.scan.runs", 60.5 * MS, 1.5 * MS),
    ("lsm.scan.merge", 62 * MS, 17 * MS)]
PUMP = [
    ("lsm.pump", -5 * MS, 3 * MS),                  # before the window
    ("lsmbench.pump", 36 * MS, 5 * MS),
    ("lsm.pump", 36 * MS, 5 * MS),
    ("lsm.wal.sync", 37 * MS, 3 * MS),
    ("lsmbench.pump", 44 * MS, 12 * MS),
    ("lsm.pump", 44 * MS, 12 * MS),
    ("lsm.lock", 44 * MS, 0.1 * MS),
    ("lsm.wal.sync", 44.1 * MS, 0.9 * MS),
    ("lsm.pump.merge", 45 * MS, 10 * MS),
    ("lsmbench.pump", 85 * MS, 10 * MS),
    ("lsm.pump", 85 * MS, 10 * MS),
    ("lsm.pump.flush", 86 * MS, 8 * MS)]
DEVICE = [
    ("jit__set_row_donated(7)", 5 * MS, 1 * MS),
    ("jit_bloom_probe_multi_kernel(1)", 14 * MS, 5 * MS),
    ("jit_other(9)", 30 * MS, 1 * MS),
    ("jit__round(2)", 46 * MS, 4 * MS),
    ("jit__round(3)", 63 * MS, 7 * MS)]


def _planes(program_spans=True):
    def keep(evs):
        return [e for e in evs
                if program_spans or not e[0].startswith("lsm.")]
    return [{"name": "/host:CPU", "lines": [
                {"name": "client", "events": keep(CLIENT)},
                {"name": "pump", "events": keep(PUMP)}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": DEVICE},
                {"name": "XLA Ops", "events": [("fusion", 0, 99 * MS)]}]}]


def _row(sp, name):
    r = sp["spans"][name]
    return r["count"], r["total_s"] * 1e3, r["self_s"] * 1e3


def test_self_time_leaves_out_nested_children():
    sp = spans.reduce(_planes())
    assert _row(sp, "lsm.get") == (1, pytest.approx(29), pytest.approx(0.5))
    assert _row(sp, "lsm.get.search") == \
        (1, pytest.approx(19), pytest.approx(19))
    assert _row(sp, "lsm.scan") == (1, pytest.approx(20), pytest.approx(1))
    assert ("lsm.lock", "lsm.get", pytest.approx(0.0005)) in sp["calls"]
    assert ("lsm.get", None, pytest.approx(0.029)) in sp["calls"]


def test_two_threads_and_spans_that_start_before_the_window():
    sp = spans.reduce(_planes())
    # three pump calls in the window, one before it that does not count
    assert _row(sp, "lsm.pump") == (3, pytest.approx(27), pytest.approx(5))
    assert _row(sp, "lsm.lock") == (3, pytest.approx(1.1),
                                    pytest.approx(1.1))
    assert _row(sp, "lsm.wal.sync") == (2, pytest.approx(3.9),
                                        pytest.approx(3.9))
    parents = {(n, p) for n, p, _ in sp["calls"]}
    assert ("lsm.wal.sync", "lsm.pump") in parents
    assert ("lsm.pump.flush", "lsm.pump") in parents


def test_gaps_named_by_the_innermost_span():
    sp = spans.reduce(_planes())
    got = [(b, o, pytest.approx(s)) for b, o, s in sp["gaps"]]
    assert got == [("idle", None, 0.005),
                   ("get", None, 0.008),             # before lsm.get
                   ("get", "lsm.get.search", 0.011),
                   # both threads hold 38.5 ms: the pump started last
                   ("pump", "lsm.wal.sync", 0.015),
                   ("idle", None, 0.013),
                   ("pump", "lsm.pump", 0.030)]       # before the flush
    assert spans.named_share(sp) == pytest.approx(100 * 56 / 64)


def test_programs_go_to_the_span_that_launched_them():
    sp = spans.reduce(_planes())
    progs = sp["programs"]
    assert progs[("lsm.pump.merge", "jit__round")] == pytest.approx(0.004)
    assert progs[("lsm.scan.merge", "jit__round")] == pytest.approx(0.007)
    assert progs[("lsm.get.probe", "jit_bloom_probe_multi_kernel")] == \
        pytest.approx(0.005)
    assert progs[(None, "jit__set_row_donated")] == pytest.approx(0.001)


def test_breakdown_labels_name_the_program_span():
    sp = spans.reduce(_planes())
    assert spans.idle_gaps(sp, n=3) == [
        ["host:pump/lsm.pump", pytest.approx(0.030)],
        ["host:pump/lsm.wal.sync", pytest.approx(0.015)],
        ["host:idle", pytest.approx(0.013)]]
    labels = [lab for lab, _ in spans.idle_gaps(sp)]
    assert "host:get/lsm.get.search" in labels and "host:get" in labels


def test_trace_reduce_ignores_program_spans():
    with_ours = tr.reduce(_planes(program_spans=True))
    without = tr.reduce(_planes(program_spans=False))
    assert with_ours == without
    sp = spans.reduce(_planes())
    assert [(b, s) for b, _, s in sp["gaps"]] == with_ours["gaps"]


def test_span_metrics():
    run = types.SimpleNamespace(spans=spans.reduce(_planes()))
    assert reader("search_ms.read")(run) == pytest.approx(19.0)
    # the scan's and the pump's lock spans are not a foreground wait
    assert reader("lock_wait_p99_ms.mixed")(run) == pytest.approx(0.5)
    assert reader("wal_sync_ms.mixed")(run) == pytest.approx(3.9)
    assert reader("flush_ms.mixed")(run) == pytest.approx(8.0)


def test_span_metrics_without_program_spans():
    # a program that opens no span reads nothing; a span that never ran
    # reads 0
    bare = types.SimpleNamespace(spans=spans.reduce(_planes(False)))
    for m in spans.METRICS:
        assert reader(m)(bare) is None
        assert reader(m)(types.SimpleNamespace()) is None
    planes = _planes()
    planes[0]["lines"][1]["events"] = []
    run = types.SimpleNamespace(spans=spans.reduce(planes))
    assert reader("wal_sync_ms.mixed")(run) == 0.0
    assert reader("flush_ms.mixed")(run) == 0.0


def test_no_window_is_an_error():
    planes = _planes()
    planes[0]["lines"][0]["events"] = CLIENT[1:]
    with pytest.raises(ValueError):
        spans.reduce(planes)


def test_probe_live_share():
    run = types.SimpleNamespace(stats_delta={"probe_cells": 4096,
                                             "probe_live_cells": 1024})
    assert reader("probe_live_share.read")(run) == pytest.approx(25.0)
    for delta in ({}, {"probe_cells": 0, "probe_live_cells": 0}):
        run = types.SimpleNamespace(stats_delta=delta)
        assert reader("probe_live_share.mixed")(run) is None


def test_report_on_a_tiny_traced_run(tiny):
    load = tr.load
    with spans.kept_planes() as kept:
        out = tiny.run("tiering.ycsb-a", traced=True)
    assert tr.load is load
    assert "probe_live_share.mixed" in out["metrics"]
    rep = spans.report(kept[-1])
    assert {"lsm.get", "lsm.get.probe", "lsm.get.search", "lsm.put",
            "lsm.put.wal", "lsm.pump", "lsm.lock"} <= set(rep["spans"])
    m = rep["span_metrics"]
    assert set(m) == set(spans.METRICS)
    assert m["search_ms"] > 0 and m["lock_wait_p99_ms"] >= 0
    assert m["wal_sync_ms"] >= 0 and m["flush_ms"] >= 0
    # the CPU has no device plane: no gap to name
    assert rep["span_idle_gaps"] == [] and rep["named_idle_share"] is None
