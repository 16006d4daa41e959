"""``chip_smoke.py`` at a tiny size on the CPU: its numpy reference and the
engine agree through every phase, and without a TPU it refuses to run."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core.backend import ExecBackend

_PATH = Path(__file__).resolve().parent.parent / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("mode", ["host", "interpret"])
def test_smoke_run_matches_reference(smoke, mode):
    """Load, reads, scans and the served phase at 2**11 keys: zero
    mismatches, every key loaded, and the backend's kernels (or host
    twins) took every merge and probe."""
    backend = smoke._counting_backend(ExecBackend, mode=mode)
    out = smoke.run(3, backend, log2_keys=11, log2_reads=9, memtable=128,
                    file_entries=512, log=lambda *a, **k: None)
    assert out["mismatches"] == 0, out
    assert out["loaded"] == 1 << 11 and out["deletes"] > 0
    assert out["tables"] > 1 and out["scanned_keys"] > 0
    ops = {op for op, _ in backend.decisions}
    assert {"merge_kway_window", "probe_multi", "scan_merge"} <= ops
    assert {m for _, m in backend.decisions} == {mode}


def test_reference_is_newest_wins(smoke):
    ref = smoke.Reference()
    ref.record([5, 1, 9], [50, 10, 90])
    ref.record([1, 9], [11, smoke.TOMBSTONE])
    ref.settle()
    ref.record([5, 7], [smoke.TOMBSTONE, 70])
    ref.settle_recent()
    found, vals = ref.get(np.array([1, 5, 7, 9, 3], np.uint32))
    assert found.tolist() == [True, False, True, False, False]
    assert vals.tolist() == [11, 0, 70, 0, 0]
    ks, vs = ref.scan(0, 8)
    assert ks.tolist() == [1, 7] and vs.tolist() == [11, 70]
