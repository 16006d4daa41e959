"""Tiering after a load of distinct records: every merge folds T runs of
a level into one run of the next, so level i holds the i-th base-T
digit of (records / memtable) runs of memtable * T**i records, with the
oldest records deepest; a partial memtable's remainder is one more L0
run."""
from __future__ import annotations


def layout(cfg: dict) -> list[dict]:
    ratio = int(cfg["policy_args"]["size_ratio"])
    mem = int(cfg["memtable_entries"])
    n = int(cfg["records"])
    flushes, rest = divmod(n, mem)
    digits = []
    while flushes:
        flushes, d = divmod(flushes, ratio)
        digits.append(d)
    runs, lo, stamp = [], 0, 0
    for level in range(len(digits) - 1, -1, -1):
        size = mem * ratio ** level
        for _ in range(digits[level]):
            stamp += 1
            runs.append({"level": level, "lo": lo, "hi": lo + size,
                         "files": 1, "stamp": stamp})
            lo += size
    if rest:
        runs.append({"level": 0, "lo": lo, "hi": lo + rest, "files": 1,
                     "stamp": stamp + 1})
    return runs
