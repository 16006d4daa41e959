"""Unified execution-backend layer (PR 8): measured host/kernel dispatch
+ device-resident merge→flush→probe data plane.

What is pinned here:

* All execution modes — host packed-sort, interpret Pallas, compiled
  Pallas (skipped where the XLA backend cannot lower it) — produce
  BIT-IDENTICAL merge/probe/scan results, for every merge policy and for
  the streaming ``merge_kway_window`` path.
* Dispatch decisions come from the measured crossover table: nearest
  size class at or below, forced modes win, compiled verdicts degrade
  when unsupported, and a missing/corrupt calibration artifact falls
  back to the built-in default without failing construction.
* ``ExecBackend.from_legacy`` reproduces the three historical engine
  booleans bit-for-bit as forced per-op modes.
* A fleet built with a forced backend actually routes every shard's
  launches through it (spy-counted).
* ``_finish_merge`` binds the finished table as VIEWS into the
  preallocated streaming output buffer — no O(merge-size) host
  concatenate+rebuild (``np.shares_memory``), the buffer is allocated
  once per merge, and kernel-mode merges hand the finished table a
  device-resident copy with no re-upload.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import backend as backend_mod
from repro.core.backend import (COMPILED, HOST, INTERPRET, OPS, ExecBackend,
                                compiled_supported, device_kind,
                                load_calibration, merge_kway_host,
                                write_calibration)
from repro.core.constraints import NoConstraint
from repro.core.engine import LSMEngine
from repro.core.fleet import LSMFleet
from repro.core.memtable import TOMBSTONE
from repro.core.policies import (LevelingPolicy, PartitionedLevelingPolicy,
                                 TieringPolicy)
from repro.core.scheduler import FairScheduler
from repro.core.sstable import SSTable

MODES = [HOST, INTERPRET] + ([COMPILED] if compiled_supported() else [])

needs_compiled = pytest.mark.skipif(
    not compiled_supported(),
    reason="compiled Pallas unsupported on this XLA backend")

ALL_MODES = [HOST, INTERPRET,
             pytest.param(COMPILED, marks=needs_compiled)]


def _mk_engine(policy: str, backend, memtable: int = 64,
               unique: int = 2048) -> LSMEngine:
    pol = {
        "tiering": lambda: TieringPolicy(3, memtable, unique),
        "leveling": lambda: LevelingPolicy(3, memtable, unique),
        "partitioned": lambda: PartitionedLevelingPolicy(
            4, memtable, unique, file_entries=64, l1_capacity=256),
    }[policy]()
    return LSMEngine(pol, FairScheduler(), NoConstraint(),
                     memtable_entries=memtable, unique_keys=unique,
                     merge_block=64, backend=backend)


def _runs(rng, k: int, n: int, space: int = 3000):
    """k newest-first sorted-unique runs, heavily overlapping."""
    runs = []
    for _ in range(k):
        keys = np.unique(rng.integers(0, space, n, dtype=np.uint32))
        vals = rng.integers(0, 1 << 30, len(keys)).astype(np.int32)
        runs.append((keys, vals))
    return runs


# ------------------------------------------------ cross-mode differential
@pytest.mark.parametrize("policy", ["tiering", "leveling", "partitioned"])
def test_engine_modes_bit_identical(policy):
    """The same workload (puts, deletes, odd streaming quanta) on one
    engine per execution mode: point reads and scans must agree bit for
    bit across every mode, and with the dict oracle."""
    engines = {m: _mk_engine(policy, m) for m in MODES}
    oracle = {}
    rng = np.random.default_rng(9)
    for step in range(6):
        ks = rng.integers(0, 2000, 150, dtype=np.uint32)
        vs = rng.integers(0, 1 << 30, 150).astype(np.int32)
        dels = rng.integers(0, 2000, 20, dtype=np.uint32)
        # admission is prefix-shaped and must not depend on dispatch
        # mode: every engine admits the same counts, the oracle follows
        # the admitted prefixes
        ns = {m: e.put_batch(ks, vs) for m, e in engines.items()}
        nds = {m: e.delete_batch(dels) for m, e in engines.items()}
        assert len(set(ns.values())) == 1, "admission depends on backend"
        assert len(set(nds.values())) == 1
        for eng in engines.values():
            eng.pump(97)            # odd quantum: windows never align
        n, nd = ns[HOST], nds[HOST]
        for k, v in zip(ks[:n].tolist(), vs[:n].tolist()):
            oracle[k] = v
        for k in dels[:nd].tolist():
            oracle.pop(k, None)
    for eng in engines.values():
        eng.drain(budget_entries=53)
    qs = np.arange(0, 2000, dtype=np.uint32)
    ref_f, ref_v = engines[HOST].get_batch(qs)
    ref_sk, ref_sv = engines[HOST].scan_range(0, 2000)
    assert dict(zip(ref_sk.tolist(), ref_sv.tolist())) == oracle
    got = {int(k): int(v) for k, v in zip(qs[ref_f], ref_v[ref_f])}
    assert got == oracle
    for m, eng in engines.items():
        if m == HOST:
            continue
        f, v = eng.get_batch(qs)
        assert np.array_equal(f, ref_f), (policy, m, "found mask")
        assert np.array_equal(v, ref_v), (policy, m, "values")
        sk, sv = eng.scan_range(0, 2000)
        assert np.array_equal(sk, ref_sk), (policy, m, "scan keys")
        assert np.array_equal(sv, ref_sv), (policy, m, "scan vals")


@pytest.mark.parametrize("mode", ALL_MODES)
def test_window_merge_composes_and_matches_host(mode):
    """``merge_kway_window`` under key-boundary cuts: the concatenated
    window outputs must equal the one-shot merge, in every mode, and
    every mode must equal the host reference."""
    rng = np.random.default_rng(4)
    runs = _runs(rng, k=4, n=700)
    be = ExecBackend(mode=mode, merge_block=64)
    want_k, want_v, _ = be.merge_kway(runs)
    host_k, host_v = merge_kway_host(runs)
    assert np.array_equal(want_k, host_k), mode
    assert np.array_equal(want_v, host_v), mode
    # cut at global key boundaries (the engine's merge-path pivot rule)
    cuts = [0, 400, 1100, 1900, 3000]
    got_k, got_v = [], []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        starts = [int(np.searchsorted(k, np.uint32(lo))) for k, _ in runs]
        stops = [int(np.searchsorted(k, np.uint32(hi))) for k, _ in runs]
        wk, wv, _ = be.merge_kway_window(runs, starts, stops)
        got_k.append(wk)
        got_v.append(wv)
    assert np.array_equal(np.concatenate(got_k), want_k), mode
    assert np.array_equal(np.concatenate(got_v), want_v), mode


@pytest.mark.parametrize("mode", ALL_MODES)
def test_scan_merge_drops_tombstones_identically(mode):
    rng = np.random.default_rng(6)
    runs = _runs(rng, k=3, n=300)
    # newest run tombstones a slice of the key space
    tk = np.unique(rng.integers(0, 3000, 100, dtype=np.uint32))
    runs.insert(0, (tk, np.full(len(tk), TOMBSTONE, np.int32)))
    be = ExecBackend(mode=mode, merge_block=64)
    mk, mv = be.scan_merge(runs, drop_value=int(TOMBSTONE))
    ref = {}
    for k, v in reversed([(rk.tolist(), rv.tolist())
                          for rk, rv in runs]):
        ref.update(zip(k, v))
    ref = {k: v for k, v in ref.items() if v != TOMBSTONE}
    assert dict(zip(mk.tolist(), mv.tolist())) == ref, mode
    assert (mv != TOMBSTONE).all()


# ----------------------------------------------------- dispatch decisions
def _cal_table(kind=None):
    return {"device_kind": kind or device_kind(), "ops": {
        "merge_kway": {"sizes": [1000, 100000],
                       "best": [HOST, COMPILED],
                       "ms": {HOST: [0.1, 50.0],
                              INTERPRET: [5.0, 40.0],
                              COMPILED: [1.0, 2.0]}},
        "probe_multi": {"sizes": [4096], "best": [HOST],
                        "ms": {HOST: [0.2]}},
    }}


def test_decide_uses_size_classes():
    be = ExecBackend(mode="auto", calibration=_cal_table())
    assert be.decide("merge_kway", 500) == HOST       # below first class
    assert be.decide("merge_kway", 50_000) == HOST    # nearest at-or-below
    # window op aliases to merge_kway's calibration entry
    assert be.decide("merge_kway_window", 500) == HOST
    if compiled_supported():
        assert be.decide("merge_kway", 200_000) == COMPILED
    else:
        # compiled verdict degrades to the next measured best (interpret
        # beats host at this size class in the table above)
        assert be.decide("merge_kway", 200_000) == INTERPRET
    # unknown op: built-in default, never the interpreter
    assert be.decide("scan_merge", 10) in (HOST, COMPILED)


def test_decide_forced_wins_over_calibration():
    be = ExecBackend(mode="auto", calibration=_cal_table(),
                     forced={"merge_kway": INTERPRET})
    assert be.decide("merge_kway", 500) == INTERPRET
    assert be.decide("merge_kway", 10 ** 9) == INTERPRET


def test_calibration_absent_or_corrupt_falls_back(tmp_path):
    missing = tmp_path / "nope.json"
    assert load_calibration(missing) is None
    corrupt = tmp_path / "bad.json"
    corrupt.write_text("{not json")
    assert load_calibration(corrupt) is None
    be = ExecBackend(mode="auto", calibration=missing)
    assert be.calibration is None
    want = COMPILED if compiled_supported() else HOST
    for op in ("merge_kway", "probe_multi", "scan_merge"):
        got = be.decide(op, 1 << 20)
        assert got == (want if compiled_supported() else HOST)
        assert got != INTERPRET, "interpreter must never win by default"


def test_calibration_roundtrip(tmp_path):
    p = write_calibration(_cal_table(), tmp_path / "cal.json")
    loaded = load_calibration(p)
    assert loaded is not None and "ops" in loaded
    be = ExecBackend(mode="auto", calibration=p)
    assert be.calibration is not None
    assert be.decide("merge_kway", 500) == HOST


def test_committed_calibration_artifact_loads():
    """The committed artifact (acceptance criterion: dispatch is loaded
    from a MEASURED table, not guessed) must parse and drive decisions
    for every engine op."""
    cal = load_calibration()
    assert cal is not None, "artifacts/bench/backend_calibration.json " \
        "missing or unreadable (regenerate via benchmarks.kernels_bench)"
    be = ExecBackend(mode="auto", calibration=cal)
    for op in ("probe_multi", "merge_kway", "merge_kway_window",
               "scan_merge"):
        assert be.decide(op, 4096) in (HOST, INTERPRET, COMPILED)


def test_calibration_from_another_device_is_not_applied():
    """A table measured on another kind of device never drives dispatch:
    the backend drops it and decides by its built-in default."""
    be = ExecBackend(mode="auto", calibration=_cal_table("TPU v5 lite"))
    assert be.calibration is None
    default = COMPILED if compiled_supported() else HOST
    assert be.decide("merge_kway", 500) == default
    assert be.decide("merge_kway", 200_000) == default
    assert ExecBackend(mode="auto",
                       calibration=_cal_table()).calibration is not None


@pytest.fixture
def reported_tpu(monkeypatch):
    """Make JAX report a TPU default backend (the compiled-support probe
    cache is cleared around it, so no other test sees the verdict)."""
    monkeypatch.setattr(backend_mod.jax, "default_backend", lambda: "tpu")
    compiled_supported.cache_clear()
    yield
    compiled_supported.cache_clear()


@pytest.mark.parametrize("size", [1, 500, 50_000, 10 ** 9])
def test_decide_on_tpu_is_always_compiled(reported_tpu, size):
    """On a TPU every data-plane decision of an unforced backend is
    compiled, whatever the calibration table says — including the
    engine's default backend."""
    cal = _cal_table()
    for op in OPS:
        cal["ops"].setdefault(op, {"sizes": [1], "best": [HOST],
                                   "ms": {HOST: [0.1]}})
    eng = _mk_engine("tiering", None)
    for be in (ExecBackend(mode="auto", calibration=cal), eng.backend):
        for op in OPS:
            assert be.decide(op, size) == COMPILED, (op, size)


def test_compiled_supported_propagates_lowering_errors(reported_tpu):
    """Where a chip is reported, a kernel the backend cannot lower must
    raise out of ``compiled_supported`` — never read as 'unsupported'
    and quietly move the data plane to the host."""
    with pytest.raises(Exception) as err:
        compiled_supported()
    assert not isinstance(err.value, AssertionError)


def test_compiled_mode_raises_when_unsupported():
    if compiled_supported():
        pytest.skip("compiled Pallas available here")
    with pytest.raises(ValueError):
        ExecBackend(mode="compiled")


# ------------------------------------------------------- legacy mapping
def test_from_legacy_reproduces_old_dispatch():
    # use_kernels=True, interpret=True: merges+probe interpret, scan host
    be = ExecBackend.from_legacy(use_kernels=True, interpret=True)
    assert be.decide("merge_kway", 1) == INTERPRET
    assert be.decide("merge_kway_window", 10 ** 9) == INTERPRET
    assert be.decide("probe_multi", 1) == INTERPRET
    assert be.decide("scan_merge", 1) == HOST
    # use_kernels=False: merges+scan host; probe stays the fused kernel
    be = ExecBackend.from_legacy(use_kernels=False, interpret=True)
    assert be.decide("merge_kway", 1) == HOST
    assert be.decide("scan_merge", 1) == HOST
    assert be.decide("probe_multi", 1) == INTERPRET
    # explicit scan override forces the kernel side
    be = ExecBackend.from_legacy(use_kernels=False, interpret=True,
                                 scan_use_kernels=True)
    assert be.decide("scan_merge", 1) == INTERPRET
    assert be.decide("merge_kway", 1) == HOST


def test_engine_legacy_flags_are_backend_views():
    eng = _mk_engine("tiering", None)     # default: auto dispatch
    assert eng.backend.mode == "auto" and not eng.backend._forced
    assert eng.use_kernels is (eng.backend.decide("merge_kway", 1 << 20)
                               != HOST)
    legacy = LSMEngine(TieringPolicy(3, 64, 2048), FairScheduler(),
                       NoConstraint(), memtable_entries=64,
                       unique_keys=2048, use_kernels=True, interpret=True)
    assert legacy.use_kernels is True
    assert legacy.backend.decide("probe_multi", 1) == INTERPRET
    assert legacy.scan_use_kernels is False  # auto: kernel only if compiled
    eng2 = LSMEngine(TieringPolicy(3, 64, 2048), FairScheduler(),
                     NoConstraint(), memtable_entries=64,
                     unique_keys=2048, use_kernels=False)
    assert eng2.use_kernels is False
    assert eng2.backend.decide("merge_kway", 1) == HOST


# ------------------------------------------------------------ fleet pin
class _SpyBackend(ExecBackend):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.calls = {"probe_multi": 0, "merge_kway": 0,
                      "merge_kway_window": 0, "scan_merge": 0}

    def probe_multi(self, *a, **kw):
        self.calls["probe_multi"] += 1
        return super().probe_multi(*a, **kw)

    def merge_kway(self, *a, **kw):
        self.calls["merge_kway"] += 1
        return super().merge_kway(*a, **kw)

    def merge_kway_window(self, *a, **kw):
        self.calls["merge_kway_window"] += 1
        return super().merge_kway_window(*a, **kw)

    def scan_merge(self, *a, **kw):
        self.calls["scan_merge"] += 1
        return super().scan_merge(*a, **kw)


def test_fleet_forced_backend_reaches_every_shard():
    """A fleet built with one forced backend must plumb THAT object to
    every shard and actually route shard launches through it."""
    spy = _SpyBackend(mode=HOST, merge_block=64)

    def factory(i):
        return _mk_engine("tiering", "interpret", memtable=32,
                          unique=1 << 14)

    with LSMFleet(3, factory, parallel=False, backend=spy) as fleet:
        assert fleet.backend is spy
        for e in fleet.engines:
            assert e.backend is spy, "shard kept its factory backend"
        rng = np.random.default_rng(2)
        for _ in range(6):
            ks = rng.integers(0, 1 << 14, 200, dtype=np.uint32)
            fleet.put_batch(ks, np.ones(200, np.int32))
            fleet.pump(300)
        fleet.drain()
        fleet.get_batch(rng.integers(0, 1 << 14, 64, dtype=np.uint32))
        fleet.scan_range(0, 1 << 14)
    assert spy.calls["merge_kway_window"] > 0, "merges bypassed backend"
    assert spy.calls["probe_multi"] > 0, "probes bypassed the backend"
    assert spy.calls["scan_merge"] > 0, "scans bypassed the backend"


# ------------------------------------- device residency / no-concat pins
def _spy_merge_outputs(eng):
    """Wrap ``_finish_merge`` to record, per finished merge, the
    ``_RunningMerge`` and the output tables it bound (the diff of
    ``eng.tables`` across the finish call)."""
    seen = []
    orig_finish = eng._finish_merge

    def spying_finish(rm):
        before = set(eng.tables)
        orig_finish(rm)
        outs = [t for c, t in eng.tables.items() if c not in before]
        seen.append((rm, outs))

    eng._finish_merge = spying_finish
    return seen


def _drive_merge(eng, rng, rounds=6, n=64):
    for _ in range(rounds):
        keys = rng.choice(1 << 16, n, replace=False).astype(np.uint32)
        eng.put_batch(keys, np.ones(n, np.int32))
        if len(eng.active):
            eng.seal_active()
        eng.pump(n)                      # flush; merges collect
    eng.drain(37)                        # odd quanta stream the merges
    assert eng.stats["merges"] > 0, "workload produced no merges"


def test_finish_merge_binds_buffer_views_no_concat():
    """Acceptance pin: the finished table's host mirrors are VIEWS into
    the streaming output buffer (no concatenate+rebuild), and the buffer
    is allocated exactly once per merge (same object every quantum)."""
    eng = _mk_engine("tiering", HOST, memtable=64, unique=1 << 16)
    seen = _spy_merge_outputs(eng)
    orig_advance = eng._advance_merge
    bufs = {}

    def spying_advance(rm, q):
        before = bufs.get(id(rm))
        out = orig_advance(rm, q)
        if rm.buf_keys is not None:
            if before is not None:
                assert rm.buf_keys is before, \
                    "output buffer was reallocated mid-merge"
            bufs[id(rm)] = rm.buf_keys
        return out

    eng._advance_merge = spying_advance
    _drive_merge(eng, np.random.default_rng(1))
    checked = 0
    for rm, outs in seen:
        if rm.emitted == 0 or rm.buf_keys is None:
            continue
        for t in outs:
            assert np.shares_memory(t.keys_np, rm.buf_keys), \
                "finished merge output is not a view into its buffer"
            assert np.shares_memory(t.vals_np, rm.buf_vals)
            checked += 1
    assert checked > 0, "no streamed merge output to pin view-binding on"


def test_partitioned_outputs_are_buffer_views():
    """Partitioned merges split the output into several files — each
    must still be a contiguous VIEW into the streaming buffer, and the
    concatenation of the views must reproduce the emitted stream."""
    eng = _mk_engine("partitioned", HOST, memtable=64, unique=1 << 16)
    seen = _spy_merge_outputs(eng)
    _drive_merge(eng, np.random.default_rng(8))
    split = 0
    for rm, outs in seen:
        if rm.emitted == 0 or rm.buf_keys is None:
            continue
        for t in outs:
            if len(t):
                assert np.shares_memory(t.keys_np, rm.buf_keys)
        if len(outs) > 1:
            glued = np.concatenate([t.keys_np for t in outs])
            assert np.array_equal(glued, rm.buf_keys[:rm.emitted])
            split += 1
    assert split > 0, "no partitioned (multi-file) merge ran"


@pytest.mark.parametrize("mode", ALL_MODES[1:])   # kernel modes only
def test_kernel_merge_output_is_device_resident(mode):
    """A merge whose every window ran on a kernel path hands the
    finished table an ADOPTED device array (no lazy re-upload), and the
    device copy equals the host mirror."""
    eng = _mk_engine("tiering", mode, memtable=64, unique=1 << 16)
    seen = _spy_merge_outputs(eng)
    _drive_merge(eng, np.random.default_rng(5), rounds=4)
    checked = 0
    for rm, outs in seen:
        for t in outs:
            if not len(t):
                continue
            assert t.device_resident, \
                "kernel-merged table did not adopt the device buffer"
            assert np.array_equal(np.asarray(t.keys), t.keys_np)
            assert np.array_equal(np.asarray(t.vals), t.vals_np)
            checked += 1
    assert checked > 0, "no kernel-merged output table to check"


def test_host_merge_output_stays_host_only():
    eng = _mk_engine("tiering", HOST, memtable=64, unique=1 << 16)
    _drive_merge(eng, np.random.default_rng(5), rounds=4)
    for t in eng.tables.values():
        assert not t.device_resident, \
            "host-mode merge paid for a device upload"


def test_sstable_build_lazy_and_adopted_device():
    keys = np.arange(10, dtype=np.uint32)
    vals = np.arange(10, dtype=np.int32)
    t = SSTable.build(keys, vals)
    assert not t.device_resident
    _ = t.keys                            # first kernel use materializes
    assert t._keys_dev is not None
    import jax.numpy as jnp
    dk, dv = jnp.asarray(keys), jnp.asarray(vals)
    t2 = SSTable.build(keys, vals, dev=(dk, dv))
    assert t2.device_resident
    assert t2.keys is dk and t2.vals is dv
