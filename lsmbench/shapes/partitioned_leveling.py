"""LevelDB-style partitioned leveling after a load: each level from L1
down holds one file less than its capacity (where the last push left
it, so no level's score reaches 1), the bottom level holds the rest,
L0 is empty, and the oldest records lie deepest."""
from __future__ import annotations

import math


def layout(cfg: dict) -> list[dict]:
    a = cfg["policy_args"]
    n = int(cfg["records"])
    fe = int(a["file_entries"])
    ratio = int(a["size_ratio"])
    cap = float(a["l1_capacity"])
    caps = [cap]                            # L1 .. the level above bottom
    while cap < n:
        cap *= ratio
        caps.append(cap)
    sizes = []
    remaining = n
    for c in caps[:-1]:
        size = min(remaining, max(0, math.ceil(c / fe) - 1) * fe)
        sizes.append(size)
        remaining -= size
    sizes.append(remaining)                 # the bottom level
    runs, lo = [], 0
    bottom = len(sizes)
    for depth in range(bottom, 0, -1):      # oldest records deepest
        size = sizes[depth - 1]
        if size:
            runs.append({"level": depth, "lo": lo, "hi": lo + size,
                         "files": math.ceil(size / fe),
                         "stamp": bottom - depth + 1})
        lo += size
    return runs
