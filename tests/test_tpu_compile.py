"""Compile the data plane's device programs for a described TPU v5e.

Nothing runs: each test lowers and compiles one program at the sizes
``chip_smoke.py`` drives, for a chip that is described, not attached, so
a construct the chip's compiler refuses (an unaligned slice, a gather it
cannot lower, too much VMEM) fails here at no chip time.  The topology is
described inside a module fixture, never at import: only one process at
a time may load the TPU library, and the test runner's workers all
import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import merge_kway_host
from repro.kernels.bloom.bloom import (_VMEM_CAP, bloom_probe_multi_kernel,
                                      stack_width)
from repro.kernels.bloom.ops import filter_params
from repro.kernels.merge import ops as merge_ops
from repro.kernels.merge.merge import AGE_PAD, KEY_PAD, LANES

#: chip_smoke.py sizes: load windows reach 8 puts of 2**16 per pump, a
#: scan window spans ~4096 keys over ~32 runs, and point reads come in
#: batches of 2**14 against ~32 tables of 2**20 keys.
LOAD_WINDOW = 1 << 19
SCAN_WINDOW, SCAN_RUNS = 4096, 32
PROBE_TABLES, TABLE_KEYS, PROBE_BATCH = 32, 1 << 20, 1 << 14
BLOCK = merge_ops.kernel_block(256)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_rounds(one_chip, window_lens):
    """Compile every tournament round ``merge_dedup_kway`` launches for
    windows of these lengths (empty windows are not staged; the rest
    follow the padding rule of ``ops._stage``)."""
    window_lens = [n for n in window_lens if n]
    runs = merge_ops._next_pow2(max(len(window_lens), 2))
    cap = max(BLOCK, merge_ops._next_pow2(max(window_lens)))
    compiled = []
    while runs > 1:
        rows = (cap + 2 * BLOCK) // LANES
        stack = _spec(one_chip, (runs, rows, LANES))
        lowered = merge_ops._round.lower(
            stack, stack, stack, _spec(one_chip, (runs,)),
            block=BLOCK, interpret=False)
        compiled.append(lowered.compile())
        runs, cap = runs // 2, 2 * cap
    return compiled


@pytest.mark.parametrize("runs", [2, 4])
def test_merge_round_compiles_at_load_window(one_chip, runs):
    """The largest merge quantum of the smoke load: every run's window
    at the full pump budget."""
    rounds = _compile_rounds(one_chip, [LOAD_WINDOW] * runs)
    assert len(rounds) == max(runs.bit_length() - 1, 1)
    assert "tpu_custom_call" in rounds[0].as_text()


def test_merge_round_compiles_at_scan_size(one_chip):
    """A range scan's k-way merge across every run of the tree."""
    rounds = _compile_rounds(one_chip, [SCAN_WINDOW] * SCAN_RUNS)
    assert len(rounds) == 5


def _host_round(keys, ages, vals, lens, block):
    """A tournament round by numpy, in the kernel's output layout: each
    pair's entries in (key, age) order, then the sentinel tail."""
    keys, ages, vals, lens = (np.asarray(x) for x in (keys, ages, vals, lens))
    runs, rows, _ = keys.shape
    width = (2 * rows * LANES // block - 2) * block
    out = [np.full((runs // 2, width), pad, np.int32)
           for pad in (KEY_PAD, AGE_PAD, 0)]
    for p in range(runs // 2):
        pair = [np.concatenate([x[2 * p + s].reshape(-1)[:lens[2 * p + s]]
                                for s in (0, 1)]) for x in (keys, ages, vals)]
        order = np.lexsort((pair[1], pair[0]))
        for o, x in zip(out, pair):
            o[p, :len(order)] = x[order]
    return (*(o.reshape(runs // 2, -1, LANES) for o in out),
            lens[0::2] + lens[1::2])


def test_merge_dedup_kway_window_compiles(one_chip, monkeypatch):
    """One streaming window call with uneven windows (a level-0 run's
    slice against partitioned files, one window empty): every round it
    launches compiles for the chip at the shapes its own staging made.
    The rounds then run on the host so the call completes, and its
    result must equal the host merge of the same windows."""
    real_round = merge_ops._round
    compiled = []

    def compile_then_host(keys, ages, vals, lens, block, interpret):
        assert not interpret
        specs = [_spec(one_chip, x.shape) for x in (keys, ages, vals, lens)]
        compiled.append(real_round.lower(*specs, block=block,
                                         interpret=False).compile())
        return _host_round(keys, ages, vals, lens, block)

    monkeypatch.setattr(merge_ops, "_round", compile_then_host)
    rng = np.random.default_rng(0)
    runs = [(np.unique(rng.integers(0, 1 << 22, n, dtype=np.uint32)),)
            for n in (60_000, 260_000, 10, 120_000, 30, 5_000)]
    runs = [(k, rng.integers(-9, 9, len(k), dtype=np.int32))
            for (k,) in runs]
    lo, hi = np.uint32(1 << 20), np.uint32(3 << 20)
    starts = [int(np.searchsorted(k, lo)) for k, _ in runs]
    stops = [int(np.searchsorted(k, hi)) for k, _ in runs]
    stops[2] = starts[2]                           # one empty window
    mk, mv = merge_ops.merge_dedup_kway_window(runs, starts, stops,
                                               block=256, drop_value=0)
    assert len(compiled) == 3                      # 5 windows -> 8 runs
    assert all("tpu_custom_call" in c.as_text() for c in compiled)
    wk, wv = merge_kway_host([(k[s:e], v[s:e])
                              for (k, v), s, e in zip(runs, starts, stops)])
    live = wv != 0
    np.testing.assert_array_equal(mk, wk[live])
    np.testing.assert_array_equal(mv, wv[live])


def test_bloom_probe_compiles_for_table_stack(one_chip):
    n_bits, _ = filter_params(TABLE_KEYS)
    words = n_bits // 32
    compiled = bloom_probe_multi_kernel.lower(
        _spec(one_chip, (PROBE_TABLES, words), jnp.uint32),
        _spec(one_chip, (PROBE_TABLES, 5), jnp.uint32),
        _spec(one_chip, (PROBE_BATCH,), jnp.uint32),
        k_max=7, block=1024, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the benchmark's filter stacks: partitioned leveling's 1,526 files of
#: 65,536 records in 3,052 rows, tiering's 7 runs, the widest of 243
#: memtables of 131,072, in 14 rows; reads of up to 1,024 and 4,096 keys
@pytest.mark.parametrize("rows,table_keys,batch", [
    (3052, 1 << 16, 1024),
    (14, 243 << 17, 4096),
])
def test_pruned_probe_compiles_for_benchmark_stacks(one_chip, rows,
                                                    table_keys, batch):
    """The probe as the engine launches it: the device stack read in
    place, per-row windows, one double-buffered filter row within the
    kernel's VMEM cap, and no copy of the stack beside it."""
    n_bits, k = filter_params(table_keys)
    width = stack_width(n_bits // 32)
    assert 2 * width * 4 + (8 << 20) <= _VMEM_CAP
    compiled = bloom_probe_multi_kernel.lower(
        _spec(one_chip, (rows, width // LANES, LANES)),
        _spec(one_chip, (rows, 5), jnp.uint32),
        _spec(one_chip, (batch,), jnp.uint32),
        k_max=k, block=1024, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    stack_bytes = rows * width * 4
    assert compiled.memory_analysis().temp_size_in_bytes < stack_bytes // 8
