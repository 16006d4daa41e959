"""Per-kernel microbenchmarks (interpret mode on CPU — wall numbers are
for regression tracking, not TPU projections) + oracle agreement + the
execution-backend calibration sweep.

The sweep times every AVAILABLE execution mode (host numpy / interpret
Pallas / compiled Pallas when the XLA backend can lower it) for each
backend op at a grid of sizes, checks the modes agree bit-for-bit, and
persists the fastest-mode-per-(op, size) crossover table to
``artifacts/bench/backend_calibration.json`` — the table
``core.backend.ExecBackend`` loads at engine construction."""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from .common import save


def _time(fn, *args, reps=3):
    fn(*args)                      # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e3   # ms


# ------------------------------------------------- backend calibration
def _sweep_runs(rng, total: int, k: int):
    """k sorted-unique runs totaling ~``total`` entries (newest first)."""
    per = max(total // k, 1)
    runs = []
    for _ in range(k):
        keys = np.unique(rng.integers(0, 4 * per * k, per,
                                      dtype=np.uint32))
        vals = rng.integers(0, 1 << 30, len(keys)).astype(np.int32)
        runs.append((keys, vals))
    return runs


def calibration_sweep(quick: bool = False) -> dict:
    """Time host/interpret/compiled for every backend op at a size grid;
    returns ``{"table": <crossover table>, "agree": bool, "path": str}``
    after persisting the table to the calibration artifact."""
    from repro.core.backend import (compiled_supported, merge_kway_host,
                                    write_calibration)
    from repro.kernels.bloom.ops import (bloom_build, bloom_probe_multi,
                                         bloom_probe_multi_host,
                                         filter_params, stack_filters)
    from repro.kernels.merge.ops import merge_dedup_kway

    rng = np.random.default_rng(7)
    sizes = [512, 2048] if quick else [512, 4096, 16384]
    has_compiled = compiled_supported()
    modes = ["host", "interpret"] + (["compiled"] if has_compiled else [])
    table: dict = {"ops": {}}
    agree = True

    def record(op: str, timers: dict, checks: dict) -> None:
        nonlocal agree
        ms = {m: [] for m in modes}
        best = []
        for s in sizes:
            outs = {}
            for m in modes:
                ms[m].append(timers[m](s))
                outs[m] = checks[m](s)
            ref = outs["host"]
            for m in modes[1:]:
                same = all(np.array_equal(np.asarray(a), np.asarray(b))
                           for a, b in zip(ref, outs[m]))
                agree = agree and same
            best.append(min(modes, key=lambda m: ms[m][-1]))
        table["ops"][op] = {"sizes": sizes, "best": best, "ms": ms}

    # -- merges (merge_kway; merge_kway_window aliases to it) ----------
    run_cache: dict = {}

    def merge_runs(s, k):
        # operands are generated ONCE per (size, k) and reused by every
        # timed mode, so the sweep compares merge cost, not data gen
        if (s, k) not in run_cache:
            runs = _sweep_runs(np.random.default_rng(s), s, k)
            run_cache[(s, k)] = (runs,
                                 [(jnp.asarray(a), jnp.asarray(b))
                                  for a, b in runs])
        return run_cache[(s, k)]

    def m_host(s, k):
        return merge_kway_host(merge_runs(s, k)[0])

    def m_kern(s, interpret, k):
        mk, mv = merge_dedup_kway(merge_runs(s, k)[1], block=256,
                                  interpret=interpret)
        return np.asarray(mk), np.asarray(mv)

    for op, k in (("merge_kway", 4), ("scan_merge", 8)):
        record(op,
               timers={"host": lambda s, k=k: _time(m_host, s, k, reps=1),
                       "interpret": lambda s, k=k: _time(
                           m_kern, s, True, k, reps=1),
                       "compiled": lambda s, k=k: _time(
                           m_kern, s, False, k, reps=1)},
               checks={"host": lambda s, k=k: m_host(s, k),
                       "interpret": lambda s, k=k: m_kern(s, True, k),
                       "compiled": lambda s, k=k: m_kern(s, False, k)})

    # -- fused probe (size = tables * keys, 8 tables) ------------------
    def probe_operands(s, t=8):
        r = np.random.default_rng(s)
        filts, nb, kh = [], [], []
        for _ in range(t):
            keys = r.integers(0, 1 << 24, 512, dtype=np.uint32)
            n_bits, k_hashes = filter_params(len(keys), 0.01)
            filts.append(np.asarray(bloom_build(jnp.asarray(keys),
                                                n_bits, k_hashes)))
            nb.append(n_bits)
            kh.append(k_hashes)
        stk, meta = stack_filters(filts, nb, kh)
        q = r.integers(0, 1 << 24, max(s // t, 1), dtype=np.uint32)
        return stk, jnp.asarray(stk), meta, q

    def p_host(ops):
        return (bloom_probe_multi_host(ops[0], ops[2], ops[3]),)

    def p_kern(ops, interpret):
        return (np.asarray(bloom_probe_multi(ops[1], ops[2], ops[3],
                                             interpret=interpret)),)

    cache: dict = {}

    def probe_ops(s):
        if s not in cache:
            cache[s] = probe_operands(s)
        return cache[s]

    record("probe_multi",
           timers={"host": lambda s: _time(
                       lambda: p_host(probe_ops(s)), reps=1),
                   "interpret": lambda s: _time(
                       lambda: p_kern(probe_ops(s), True), reps=1),
                   "compiled": lambda s: _time(
                       lambda: p_kern(probe_ops(s), False), reps=1)},
           checks={"host": lambda s: p_host(probe_ops(s)),
                   "interpret": lambda s: p_kern(probe_ops(s), True),
                   "compiled": lambda s: p_kern(probe_ops(s), False)})

    table["compiled_supported"] = has_compiled
    table["quick"] = bool(quick)
    path = write_calibration(table)
    return {"table": table, "agree": bool(agree), "path": str(path)}


def run(quick: bool = False) -> dict:
    from repro.core.backend import compiled_supported
    out: dict = {"claims": {}}
    rng = np.random.default_rng(0)
    interp = not compiled_supported()     # the kernels' mode, reported
    out["kernel_mode"] = "interpret" if interp else "compiled"

    # merge kernel
    from repro.kernels.merge.ops import merge_dedup
    from repro.kernels.merge.ref import merge_dedup_ref
    n = 4096 if quick else 16384
    ka = np.sort(rng.choice(1 << 20, n, replace=False)).astype(np.uint32)
    kb = np.sort(rng.choice(1 << 20, n, replace=False)).astype(np.uint32)
    va = rng.integers(0, 1 << 30, n).astype(np.int32)
    vb = rng.integers(0, 1 << 30, n).astype(np.int32)
    mk, mv, keep, valid = merge_dedup(jnp.asarray(ka), jnp.asarray(va),
                                      jnp.asarray(kb), jnp.asarray(vb),
                                      block=256, interpret=interp)
    keep = np.array(keep)
    keep[valid:] = False
    rk, rv = merge_dedup_ref(ka, va, kb, vb)
    agree = np.array_equal(np.asarray(mk)[keep], rk) and \
        np.array_equal(np.asarray(mv)[keep], rv)
    out["merge"] = {
        "n": n,
        "ms": _time(lambda: merge_dedup(jnp.asarray(ka), jnp.asarray(va),
                                        jnp.asarray(kb), jnp.asarray(vb),
                                        block=256, interpret=interp)),
        "oracle_agree": bool(agree),
    }
    out["claims"]["merge_matches_oracle"] = bool(agree)

    # bloom kernel
    from repro.kernels.bloom.ops import bloom_build, bloom_probe, filter_params
    keys = rng.choice(1 << 24, n, replace=False).astype(np.uint32)
    n_bits, k_hashes = filter_params(n, 0.01)
    filt = bloom_build(jnp.asarray(keys), n_bits, k_hashes)
    present = bloom_probe(filt, jnp.asarray(keys), n_bits, k_hashes,
                          interpret=interp)
    absent_keys = rng.choice(1 << 24, 4 * n, replace=False).astype(np.uint32)
    absent_keys = np.setdiff1d(absent_keys, keys)[:n]
    fp = float(np.mean(np.asarray(
        bloom_probe(filt, jnp.asarray(absent_keys), n_bits, k_hashes,
                    interpret=interp))))
    out["bloom"] = {
        "n": n, "fp_rate": fp, "n_bits": n_bits, "k_hashes": k_hashes,
        "probe_ms": _time(lambda: bloom_probe(filt, jnp.asarray(keys),
                                              n_bits, k_hashes,
                                              interpret=interp)),
        "no_false_negatives": bool(np.asarray(present).all()),
    }
    out["claims"]["bloom_no_false_negatives"] = bool(
        np.asarray(present).all())
    out["claims"]["bloom_fp_near_target"] = fp < 0.03

    # attention kernel
    from repro.kernels.attention.ops import attention
    from repro.kernels.attention.ref import attention_ref
    B, H, Hkv, S, D = 1, 4, 2, 256, 32
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    o = attention(q, k, v, causal=True, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o - ref)))
    out["attention"] = {"max_err": err,
                        "ms": _time(lambda: attention(q, k, v, causal=True,
                                                      bq=64, bk=64))}
    out["claims"]["attention_matches_oracle"] = err < 2e-3

    # ssd kernel
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_scan_ref as ssd_ref
    BH, L, P, N = 2, 128, 16, 8
    x = jnp.asarray(rng.standard_normal((BH, L, P)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((BH, L, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((BH, L, N)), jnp.float32)
    alog = jnp.asarray(-np.abs(rng.standard_normal((BH, L))) * 0.1,
                       jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((BH, L))) * 0.1, jnp.float32)
    y = ssd(x, b, c, alog, dt, chunk=32)
    yr = ssd_ref(x, b, c, alog, dt)
    err = float(jnp.max(jnp.abs(y - yr)))
    out["ssd"] = {"max_err": err,
                  "ms": _time(lambda: ssd(x, b, c, alog, dt, chunk=32))}
    out["claims"]["ssd_matches_oracle"] = err < 2e-3

    # paged decode attention (block-table indirection)
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_kernel
    from repro.kernels.paged_attention.ref import paged_attention_ref
    B, Hkv, G, D, page, n_pages, mp = 4, 2, 4, 32, 16, 64, 8
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    tables = jnp.asarray(np.stack([
        rng.choice(n_pages, mp, replace=False) for _ in range(B)]),
        jnp.int32)
    lens = jnp.asarray(rng.integers(1, mp * page, B), jnp.int32)
    o = paged_attention_kernel(q, kp, vp, tables, lens)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    err = float(jnp.max(jnp.abs(o - ref)))
    out["paged_attention"] = {
        "max_err": err,
        "ms": _time(lambda: paged_attention_kernel(q, kp, vp, tables,
                                                   lens))}
    out["claims"]["paged_attention_matches_oracle"] = err < 2e-4

    # backend calibration sweep: time every available execution mode per
    # op per size, pin cross-mode agreement, persist the crossover table
    from pathlib import Path
    cal = calibration_sweep(quick=quick)
    out["backend_calibration"] = {
        "path": cal["path"],
        "compiled_supported": cal["table"]["compiled_supported"],
        "best": {op: t["best"] for op, t in cal["table"]["ops"].items()},
    }
    out["claims"]["backend_modes_agree"] = cal["agree"]
    out["claims"]["calibration_artifact_written"] = \
        Path(cal["path"]).exists()

    save("kernels_bench", out)
    return out
