"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(assignment deliverable c): every Pallas kernel is validated in
interpret mode over a grid of shapes and dtypes."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.attention.ops import attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.bloom.ops import bloom_build, bloom_probe, filter_params
from repro.kernels.bloom.ref import bloom_build_ref, bloom_probe_ref
from repro.kernels.merge.ops import (merge_dedup, merge_dedup_kway,
                                     merge_sorted)
from repro.kernels.merge.ref import (merge_dedup_kway_ref, merge_dedup_ref,
                                     merge_sorted_ref)
from repro.kernels.ssd.ops import ssd, ssd_decode_step
from repro.kernels.ssd.ref import ssd_scan_ref


# ---------------------------------------------------------------- merge
@pytest.mark.parametrize("na,nb,block", [
    (100, 100, 64), (1000, 37, 128), (0, 64, 64), (513, 511, 256),
    (2048, 2048, 256),
])
@pytest.mark.parametrize("dtype", [np.uint32, np.int32])
def test_merge_sorted_sweep(na, nb, block, dtype):
    rng = np.random.default_rng(na * 7919 + nb)
    hi = np.iinfo(dtype).max - 1
    ka = np.sort(rng.integers(0, hi, na)).astype(dtype)
    kb = np.sort(rng.integers(0, hi, nb)).astype(dtype)
    va = rng.integers(0, 1 << 30, na).astype(np.int32)
    vb = rng.integers(0, 1 << 30, nb).astype(np.int32)
    mk, mv, ms, valid = merge_sorted(jnp.asarray(ka), jnp.asarray(va),
                                     jnp.asarray(kb), jnp.asarray(vb),
                                     block=block, interpret=True)
    rk, rv, rs = merge_sorted_ref(jnp.asarray(ka), jnp.asarray(va),
                                  jnp.asarray(kb), jnp.asarray(vb))
    assert valid == na + nb
    np.testing.assert_array_equal(np.asarray(mk)[:valid], np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(mv)[:valid], np.asarray(rv))


@pytest.mark.parametrize("na,nb", [(128, 128), (1000, 333), (47, 2000)])
def test_merge_dedup_matches_dict_oracle(na, nb):
    rng = np.random.default_rng(na + nb)
    # force heavy key overlap so dedup matters
    ka = np.sort(rng.choice(max(na, nb) * 2, na, replace=False)).astype(
        np.uint32)
    kb = np.sort(rng.choice(max(na, nb) * 2, nb, replace=False)).astype(
        np.uint32)
    va = rng.integers(0, 1 << 30, na).astype(np.int32)
    vb = rng.integers(0, 1 << 30, nb).astype(np.int32)
    mk, mv, keep, valid = merge_dedup(jnp.asarray(ka), jnp.asarray(va),
                                      jnp.asarray(kb), jnp.asarray(vb),
                                      block=128, interpret=True)
    keep = np.array(keep)
    keep[valid:] = False
    rk, rv = merge_dedup_ref(ka, va, kb, vb)
    np.testing.assert_array_equal(np.asarray(mk)[keep], rk)
    np.testing.assert_array_equal(np.asarray(mv)[keep], rv)


def _mk_runs(rng, sizes, key_space):
    runs = []
    for n in sizes:
        ks = np.sort(rng.choice(key_space, n, replace=False)).astype(
            np.uint32)
        vs = rng.integers(0, 1 << 30, n).astype(np.int32)
        runs.append((ks, vs))
    return runs


@pytest.mark.parametrize("sizes,block", [
    ((100, 80), 64),                 # k=2: degenerates to the pairwise path
    ((64, 0, 200), 64),              # empty run dropped
    ((33, 128, 7, 255, 64), 128),    # odd k: carry-over leg
    ((100,) * 8, 64),                # balanced 3-round tournament
    ((50,), 64),                     # k=1 passthrough
])
def test_merge_dedup_kway_matches_dict_oracle(sizes, block):
    rng = np.random.default_rng(sum(sizes))
    runs = _mk_runs(rng, sizes, max(sizes) * 2 + 1)   # heavy key overlap
    mk, mv = merge_dedup_kway(runs, block=block, interpret=True)
    rk, rv = merge_dedup_kway_ref(runs)
    np.testing.assert_array_equal(np.asarray(mk), rk)
    np.testing.assert_array_equal(np.asarray(mv), rv)


def test_merge_dedup_kway_equals_pairwise_fold():
    """The balanced tournament must equal the sequential pairwise fold
    (oldest -> newest, newer run as A) it replaces in the engine."""
    rng = np.random.default_rng(9)
    runs = _mk_runs(rng, (120, 90, 255, 33, 64, 128), 400)
    mk, mv = merge_dedup_kway(runs, block=64, interpret=True)

    fk, fv = (jnp.asarray(runs[-1][0]), jnp.asarray(runs[-1][1]))
    for ks, vs in reversed(runs[:-1]):     # fold oldest->newest, newer = A
        k2, v2, keep, valid = merge_dedup(jnp.asarray(ks), jnp.asarray(vs),
                                          fk, fv, block=64, interpret=True)
        keep = np.array(keep)
        keep[valid:] = False
        fk, fv = jnp.asarray(np.asarray(k2)[keep]), \
            jnp.asarray(np.asarray(v2)[keep])
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(fk))
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(fv))


def test_merge_dedup_kway_duplicate_heavy():
    """Every run holds the SAME key set: output is run 0 verbatim (the
    newest version of every key), the hardest dedup case for the
    age-carrying tournament."""
    rng = np.random.default_rng(4)
    ks = np.sort(rng.choice(2048, 300, replace=False)).astype(np.uint32)
    runs = [(ks, rng.integers(0, 1 << 30, 300).astype(np.int32))
            for _ in range(5)]
    mk, mv = merge_dedup_kway(runs, block=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(mk), ks)
    np.testing.assert_array_equal(np.asarray(mv), runs[0][1])


# ---------------------------------------------------------------- bloom
@pytest.mark.parametrize("n,fpr", [(64, 0.01), (1000, 0.01), (5000, 0.05)])
def test_bloom_sweep(n, fpr):
    rng = np.random.default_rng(n)
    keys = rng.choice(1 << 24, n, replace=False).astype(np.uint32)
    n_bits, k = filter_params(n, fpr)
    filt = bloom_build(jnp.asarray(keys), n_bits, k)
    # kernel probe == numpy oracle on both present and absent keys
    absent = np.setdiff1d(
        rng.choice(1 << 24, 3 * n, replace=False).astype(np.uint32), keys)
    for qs in (keys, absent[:n]):
        got = np.asarray(bloom_probe(filt, jnp.asarray(qs), n_bits, k,
                                     interpret=True))
        bits = bloom_build_ref(keys, n_bits, k)
        want = bloom_probe_ref(bits, qs, n_bits, k)
        np.testing.assert_array_equal(got, want)
    # no false negatives; fp rate near target
    present = np.asarray(bloom_probe(filt, jnp.asarray(keys), n_bits, k,
                                     interpret=True))
    assert present.all()
    fp = np.mean(np.asarray(bloom_probe(filt, jnp.asarray(absent[:2000]),
                                        n_bits, k, interpret=True)))
    assert fp <= max(3 * fpr, 0.02)


def test_bloom_probe_multi_equals_per_table():
    """The fused stacked probe (heterogeneous filter geometry, zero-padded
    to a common word count) returns exactly the per-table probe rows, with
    no false negatives on each table's own keys."""
    from repro.kernels.bloom.ops import bloom_probe_multi, stack_filters
    rng = np.random.default_rng(0)
    tables = []
    for n, fpr in ((17, 0.01), (260, 0.05), (2048, 0.01), (900, 0.02)):
        keys = rng.choice(1 << 22, n, replace=False).astype(np.uint32)
        n_bits, k = filter_params(n, fpr)
        filt = bloom_build(jnp.asarray(keys), n_bits, k)
        tables.append((keys, filt, n_bits, k))
    filts, meta = stack_filters([t[1] for t in tables],
                                [t[2] for t in tables],
                                [t[3] for t in tables])
    assert filts.shape[1] == max(t[1].shape[0] for t in tables)
    qs = rng.integers(0, 1 << 22, 513, dtype=np.uint32)   # non-block-aligned
    multi = bloom_probe_multi(filts, meta, qs, interpret=True)
    assert multi.shape == (len(tables), len(qs))
    for i, (keys, filt, n_bits, k) in enumerate(tables):
        single = np.asarray(bloom_probe(filt, jnp.asarray(qs), n_bits, k,
                                        interpret=True))
        np.testing.assert_array_equal(multi[i], single)
        own = bloom_probe_multi(filts, meta, keys, interpret=True)
        assert own[i].all(), f"false negative in table {i}"
    # degenerate shapes
    assert bloom_probe_multi(filts[:0], meta[:0], qs,
                             interpret=True).shape == (0, len(qs))
    empty_q = np.empty(0, np.uint32)
    assert bloom_probe_multi(filts, meta, empty_q,
                             interpret=True).shape == (len(tables), 0)


#: a free stack row: its range holds no key, and its words are all ones,
#: so a probe that read it would answer "maybe" for every key
FREE = (None, 1, 0)
TOP = 2 ** 32 - 2                       # the largest key a store takes


def _table(rng, lo, hi, n):
    keys = np.unique(rng.integers(lo, hi + 1, n, dtype=np.uint64)
                     .astype(np.uint32))
    return keys, int(keys[0]), int(keys[-1])


def _pruning_case(case, rng):
    """Stack rows, each ``(keys, lo, hi)`` (``keys`` None for a free
    row), and a key batch for one pruning case."""
    a = _table(rng, 1000, 2000, 200)
    b = _table(rng, 3000, 9000, 300)
    if case == "empty_row":
        return [a, FREE, b], rng.integers(0, 10000, 300, dtype=np.uint32)
    if case == "keys_at_lo_and_hi":
        c = _table(rng, 2000, 3000, 100)   # may share a bound with a or b
        q = np.array([a[1], a[2], b[1], b[2], c[1], c[2], a[1] - 1,
                      a[2] + 1, b[2] + 1, *a[0][:40]], np.uint32)
        return [a, b, c], q
    if case == "keys_above_2_31":
        hi1 = _table(rng, 2 ** 31, 2 ** 31 + 5000, 300)
        hi2 = _table(rng, TOP - 4000, TOP, 200)
        q = np.concatenate([hi1[0][::3], hi2[0][::2],
                            rng.integers(2 ** 31 - 100, 2 ** 31 + 100, 50,
                                         dtype=np.uint32),
                            np.array([TOP, 2 ** 31 - 1, 0], np.uint32)])
        return [a, hi1, hi2], q
    if case == "row_holding_every_key":
        run = (_table(rng, 0, 20000, 500)[0], 0, TOP)
        return [run, a, run], rng.integers(0, 30000, 400, dtype=np.uint32)
    if case == "no_live_row":
        return [a, FREE, b], rng.integers(10000, 20000, 200,
                                          dtype=np.uint32)
    if case == "batch_off_128_over_two_blocks":
        # rows whose windows lie in the first, both and the second
        # 1,024-key block of the sorted batch
        wide = _table(rng, 0, 6000, 400)
        late = _table(rng, 10000, 11500, 300)
        return [FREE, late, a, b, wide, FREE, late], \
            rng.integers(0, 12000, 1500, dtype=np.uint32)
    if case == "duplicate_keys":
        q = np.repeat(np.concatenate([a[0][:50], b[0][:50],
                                      rng.integers(0, 10000, 50,
                                                   dtype=np.uint32)]), 3)
        return [a, b], rng.permutation(q)
    assert case == "free_rows_between_live_ones"
    c = _table(rng, 500, 4000, 250)
    return [FREE, a, FREE, b, FREE, FREE, c, FREE], \
        rng.integers(0, 10000, 300, dtype=np.uint32)


@pytest.mark.parametrize("case", [
    "empty_row", "keys_at_lo_and_hi", "keys_above_2_31",
    "row_holding_every_key", "no_live_row", "batch_off_128_over_two_blocks",
    "duplicate_keys", "free_rows_between_live_ones"])
def test_pruned_probe_is_dense_probe_and_key_range(case):
    """The probe pruned by key range answers, for every (row, key), the
    dense probe ANDed with ``lo <= key <= hi``: the interpret-mode kernel
    on the device stack, the host twin, and the ``ref.py`` bit-set
    agree, free rows answer nothing, and each path reports the live
    cells as the cells it probed."""
    from repro.kernels.bloom.ops import (bloom_probe_multi,
                                         bloom_probe_pruned,
                                         bloom_probe_pruned_host,
                                         device_stack, stack_filters,
                                         stack_width)
    rng = np.random.default_rng(sum(map(ord, case)))
    rows, q = _pruning_case(case, rng)
    geo = [filter_params(len(k)) if k is not None else (128, 1)
           for k, _, _ in rows]
    filts, meta = stack_filters(
        [bloom_build(k, *g) if k is not None
         else np.full(4, 2 ** 32 - 1, np.uint32) for (k, _, _), g
         in zip(rows, geo)], *zip(*geo))
    filts = np.pad(filts, ((0, 0), (0, stack_width(filts.shape[1])
                                    - filts.shape[1])))
    meta = np.column_stack([meta, [[lo, hi] for _, lo, hi in rows]]) \
        .astype(np.uint32)
    live = np.array([(q >= lo) & (q <= hi) for _, lo, hi in rows])
    want = np.zeros(live.shape, bool)
    for r, ((keys, _, _), (n_bits, k)) in enumerate(zip(rows, geo)):
        if keys is not None:
            want[r] = bloom_probe_ref(bloom_build_ref(keys, n_bits, k), q,
                                      n_bits, k) & live[r]
    dense = bloom_probe_multi(filts, meta[:, :2], q, interpret=True)
    np.testing.assert_array_equal(dense & live, want)
    for hits, cells in (
            bloom_probe_pruned(device_stack(filts), meta, q,
                               interpret=True),
            bloom_probe_pruned_host(filts, meta, q)):
        got = np.zeros(live.shape, bool)
        got[hits.rows, hits.keys] = True
        np.testing.assert_array_equal(got, want)
        assert cells == live.sum()
        assert len(hits.rows) == want.sum()        # each pair once
        assert (np.diff(hits.rows) >= 0).all()


# ------------------------------------------------------------- attention
@pytest.mark.parametrize("B,H,Hkv,S,D,bq,bk", [
    (1, 2, 1, 64, 16, 32, 32),
    (2, 4, 2, 128, 32, 64, 64),
    (1, 8, 8, 96, 16, 64, 32),      # MHA, non-multiple seq
    (2, 4, 1, 128, 64, 128, 128),   # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_sweep(B, H, Hkv, S, D, bq, bk, dtype):
    key = jax.random.PRNGKey(B * 100 + S)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    out = attention(q, k, v, causal=True, bq=bq, bk=bk)
    ref = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                 ref.astype(jnp.float32)))) < tol


# ------------------------------------------------------------------- ssd
@pytest.mark.parametrize("BH,L,P,N,chunk", [
    (1, 64, 8, 4, 16), (2, 100, 16, 8, 32), (3, 256, 32, 16, 64),
])
def test_ssd_sweep(BH, L, P, N, chunk):
    rng = np.random.default_rng(L)
    x = jnp.asarray(rng.standard_normal((BH, L, P)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((BH, L, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((BH, L, N)), jnp.float32)
    alog = jnp.asarray(-np.abs(rng.standard_normal((BH, L))) * 0.2,
                       jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((BH, L))) * 0.2,
                     jnp.float32)
    y = ssd(x, b, c, alog, dt, chunk=chunk)
    ref = ssd_scan_ref(x, b, c, alog, dt)
    assert float(jnp.max(jnp.abs(y - ref))) < 2e-3


def test_ssd_decode_matches_scan():
    """Sequential decode steps reproduce the chunked scan exactly."""
    rng = np.random.default_rng(0)
    BH, L, P, N = 2, 24, 8, 4
    x = jnp.asarray(rng.standard_normal((BH, L, P)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((BH, L, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((BH, L, N)), jnp.float32)
    alog = jnp.asarray(-np.abs(rng.standard_normal((BH, L))) * 0.2,
                       jnp.float32)
    dt = jnp.asarray(np.abs(rng.standard_normal((BH, L))) * 0.2,
                     jnp.float32)
    y_scan = ssd(x, b, c, alog, dt, chunk=8)
    state = jnp.zeros((BH, N, P), jnp.float32)
    outs = []
    for t in range(L):
        state, y_t = ssd_decode_step(state, x[:, t], b[:, t], c[:, t],
                                     alog[:, t], dt[:, t])
        outs.append(y_t)
    y_seq = jnp.stack(outs, axis=1)
    assert float(jnp.max(jnp.abs(y_scan - y_seq))) < 2e-3


# --------------------------------------------------------- paged attention
@pytest.mark.parametrize("B,Hkv,G,D,page,n_pages,max_pages", [
    (2, 1, 1, 16, 4, 16, 4),
    (3, 2, 4, 16, 8, 32, 6),
    (1, 4, 2, 32, 16, 24, 8),
])
def test_paged_attention_sweep(B, Hkv, G, D, page, n_pages, max_pages):
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_kernel
    from repro.kernels.paged_attention.ref import paged_attention_ref
    rng = np.random.default_rng(B * 7 + page)
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    tables = jnp.asarray(np.stack([
        rng.choice(n_pages, max_pages, replace=False) for _ in range(B)]),
        jnp.int32)
    lens = jnp.asarray(rng.integers(1, max_pages * page, B), jnp.int32)
    out = paged_attention_kernel(q, kp, vp, tables, lens)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_paged_attention_matches_contiguous():
    """Paged result == dense decode attention over the gathered cache."""
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.models.layers import decode_attention_jnp
    rng = np.random.default_rng(3)
    B, Hkv, G, D, page, mp = 2, 2, 2, 16, 8, 4
    n_pages = B * mp
    q = jnp.asarray(rng.standard_normal((B, G * Hkv, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    lens = jnp.asarray([13, 29], jnp.int32)
    out = paged_decode_attention(q, kp, vp, tables, lens)
    # contiguous cache: (B, Hkv, S, D)
    kc = kp[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, mp * page, D)
    vc = vp[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, mp * page, D)
    for b in range(B):
        ref = decode_attention_jnp(q[b:b + 1, :, None], kc[b:b + 1],
                                   vc[b:b + 1], lens[b])[:, :, 0]
        assert float(jnp.max(jnp.abs(out[b:b + 1] - ref))) < 2e-5
