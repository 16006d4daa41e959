"""Finds the knee of an open-loop cell: the highest offered rate the
store serves without a growing backlog.

    python3 -m lsmbench.sweep --workload <name> --seed <n> --seconds <s> \\
        --rates 1000,2000,4000

One process builds the cell's store once, warms it up with the mix's own
traffic, then offers each rate in turn for ``--seconds`` (the first
for the mix's ``warmup_s``, as a warm-up) and prints one JSON line per
rate: operations offered and completed inside the window, the median
and 99th percentile per kind, the pump's share of its budget, and
whether the rate was sustained: every operation answered, and the
median latency of the window's last third of operations no more than
1.5 times that of its first third, plus 5 ms, so that a backlog that
grows through the window fails it.  The sweep stops at the first rate
not sustained and prints the knee, the highest rate sustained.  The
benchmark's own runs never sweep: a cell's rate is fixed in its mix
file, at about 4/5 of the knee this finds.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import spec, store
from .frontdoor import FrontDoor, Log
from .keys import record_keys
from .latency import latencies
from .pump import PumpDriver
from .run import _burst, _compile_counter, _warm_probe
from .traffic import READ, Traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = spec.load()
    _, cfg, mix = spec.cell(bench, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("lsmbench.sweep: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    compiles = _compile_counter()
    backend = store.counting_backend()
    with tempfile.TemporaryDirectory(prefix="lsmbench-") as tmp:
        group, _ = store.build(cfg, args.seed, backend, Path(tmp))
        driver = PumpDriver(group, cfg["io_budget_bytes_per_s"],
                            cfg["pump_quantum_s"], cfg["record_bytes"])
        fd = FrontDoor(group, Log(), int(mix["max_batch"]), driver)
        driver.start()
        records = int(cfg["records"])
        knee = None
        try:
            warm = Traffic(mix, args.seed, records)
            if READ in warm.shares:
                _warm_probe(fd, record_keys(np.arange(int(mix["max_batch"]))),
                            int(mix["max_batch"]))
            _burst(fd, warm, int(mix.get("warmup_burst_ops", 0)),
                   int(mix["max_batch"]))
            records = warm.next_record
            for i, rate in enumerate(float(r) for r in
                                     args.rates.split(",")):
                traffic = Traffic({**mix, "rate_ops_per_s": rate},
                                  args.seed + i, records)
                stats0 = group.stats
                compiled0 = compiles["n"]
                secs = float(mix["warmup_s"]) if i == 0 else args.seconds
                ops = traffic.take(int(round(rate * secs)))
                offsets = traffic.arrivals(secs)
                t0 = time.perf_counter()
                ph = fd.open_loop(ops, t0 + offsets, t0 + secs, 60.0)
                records = traffic.next_record
                pump = driver.window(t0, t0 + secs)
                row = {"rate": rate, "seconds": secs, "offered": len(ops),
                       "completed_in_window":
                           int((ph.done <= t0 + secs).sum()),
                       "drain_s": ph.gave_up - (t0 + secs),
                       "pump_spent_share": pump["spent_share"],
                       "flushes": group.stats["flushes"] - stats0["flushes"],
                       "merges": group.stats["merges"] - stats0["merges"],
                       "compilations": compiles["n"] - compiled0,
                       "warmup": i == 0}
                for kind in np.unique(ops.kind):
                    m = ops.kind == kind
                    lat = latencies(ph.scheduled[m], ph.done[m],
                                    ph.gave_up)
                    row[f"kind{kind}_p50_ms"] = float(np.median(lat)) * 1e3
                    row[f"kind{kind}_p99_ms"] = \
                        float(np.percentile(lat, 99)) * 1e3
                lat = latencies(ph.scheduled, ph.done, ph.gave_up)
                third = len(lat) // 3
                first = float(np.median(lat[:third])) if third else 0.0
                last = float(np.median(lat[-third:])) if third else 0.0
                row["first_third_p50_ms"] = first * 1e3
                row["last_third_p50_ms"] = last * 1e3
                row["sustained"] = bool(not np.isnan(ph.done).any()
                                        and last <= 1.5 * first + 0.005)
                print(json.dumps(row), flush=True)
                if i == 0:
                    continue
                if not row["sustained"]:
                    break
                knee = rate
        finally:
            driver.stop()
            group.close()
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
