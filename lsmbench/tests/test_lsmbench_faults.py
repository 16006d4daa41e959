"""Each cell, run through the whole harness at a tiny size, answers
like the reference when the store is sound, and not when its timed path
is broken underneath or its control breaks a stated guarantee.  On the
CPU every decision runs on the host, so ``not_compiled`` is never 0 and
``correct`` is false: the numbers are checked one by one."""
import pytest

from lsmbench import spec
from lsmbench.control import broken_build
from lsmbench.faults import FAULTS
from lsmbench.tests.tiny import SCAN_CELL

CELLS = [w["name"] for w in spec.load()["workloads"]] + [SCAN_CELL["name"]]
WRITES = {c for c in CELLS if c != "leveling.ycsb-c"}

# a store that keeps nothing of its writes is a fault only where the
# cell writes
BROKEN = [(c, f) for c in CELLS for f in sorted(FAULTS)
          if f != "unchanged_state" or c in WRITES]


def _answers(out) -> dict:
    return {k: c["value"] for k, c in out["checks"].items()
            if k != "not_compiled"}


def test_cells_cover_the_benchmark(tiny):
    assert set(tiny.cells) == set(CELLS)
    for c in CELLS:
        _, _, mix = tiny.cell(c)
        assert (c in WRITES) == bool({"update", "insert"} & set(mix["ops"]))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_store_answers_like_the_reference(tiny, cell):
    out = tiny.run(cell)
    assert not any(_answers(out).values()), out["checks"]
    assert out["failed"] == 0
    # the host path is never compiled, and the check always says so
    assert out["checks"]["not_compiled"]["value"] > 0
    assert not out["correct"]
    assert set(out["metrics"]) == {m["name"] for m in
                                   spec.metrics_for(tiny.bench, cell, False)}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_its_per_layer_metrics(tiny, cell):
    out = tiny.run(cell, traced=True)
    assert not any(_answers(out).values()), out["checks"]
    assert out["device"]["window_s"] > 0
    # the CPU has no device trace: what is read from it stays out
    want = {m["name"] for m in spec.metrics_for(tiny.bench, cell, True)
            if m["source"] != "device_trace"}
    assert want <= set(out["metrics"])


@pytest.mark.parametrize("cell,fault", BROKEN)
def test_broken_store_is_caught(tiny, cell, fault):
    out = tiny.run(cell, build=broken_build(fault))
    assert any(_answers(out).values()), out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_caught(tiny, cell):
    out = tiny.run(cell, build=broken_build(drop_oldest=True))
    assert out["checks"]["readback_mismatches"]["value"] > 0
