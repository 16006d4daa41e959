"""Runs a cell with its store broken, on several seeds in one process, to
show on the chip that ``correct`` comes out false.

    python3 -m lsmbench.control --workload <name> --seeds 1,2,3 \\
        --seconds <s> [--fault <name> | --drop-oldest]

``--drop-oldest`` is the control: the store is built without its oldest
run, breaking the configuration's guarantee that every loaded record
stays readable.  ``--fault`` installs one of ``faults.FAULTS`` below the
front door.  With neither, the cell runs sound.  Each run prints its
checks as one JSON line.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from . import spec, store
from .faults import FAULTS
from .run import run_cell


def broken_build(fault=None, drop_oldest=False):
    """``store.build`` with the control's or a fault's break in it."""
    def build(*args, **kw):
        group, wal_path = store.build(*args, drop_oldest=drop_oldest, **kw)
        if fault is not None:
            FAULTS[fault](group)
        return group, wal_path
    return build


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--drop-oldest", action="store_true")
    args = ap.parse_args(argv)
    bench = spec.load()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("lsmbench.control: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(bench, args.workload, seed, args.seconds, False,
                       time.perf_counter(),
                       build=broken_build(args.fault, args.drop_oldest))
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "drop_oldest": args.drop_oldest,
                          "correct": out["correct"],
                          "checks": {k: c["value"] for k, c in
                                     out["checks"].items()},
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
