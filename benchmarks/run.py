"""Benchmark orchestrator:
``python -m benchmarks.run [--quick|--smoke] [--only m]``.

Runs every paper-figure benchmark + the framework-integration ones,
prints each module's claims map, and exits nonzero if any claim fails.
Results land in artifacts/bench/*.json.

``--smoke`` is the CI rot check: every module runs at its quick sizes,
claims are still reported, but only module ERRORS fail the run —
performance bars are meaningless at smoke sizes; the point is that
benchmark code keeps importing and executing between perf PRs.
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback

MODULES = [
    "fig01_stalls",
    "fig06_blsm",
    "fig08_testing",
    "fig09_10_running",
    "fig11_size_ratio",
    "fig12_constraints",
    "fig13_bursts",
    "fig14_17_queries",
    "fig19_20_sizetiered",
    "fig21_23_partitioned",
    "fig24_partition_size",
    "fig25_27_secondary",
    "engine_throughput",
    "twophase_engine",
    "secondary_engine",
    "latency_tail",
    "kernels_bench",
    "ckpt_twophase",
    "serving_twophase",
    "fleet_scaling",
    "roofline",
    "recovery",
    "availability",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-size rot check: quick sizes, only module "
                         "errors fail (claims reported, not gated)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    mods = [args.only] if args.only else MODULES
    n_claims = n_pass = n_err = 0
    for name in mods:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            res = mod.run(quick=args.quick or args.smoke)
            claims = res.get("claims", {})
            ok = sum(bool(v) for v in claims.values())
            n_claims += len(claims)
            n_pass += ok
            status = "PASS" if ok == len(claims) else "PARTIAL"
            print(f"[bench] {name:24s} {status} ({ok}/{len(claims)} claims, "
                  f"{time.time() - t0:.1f}s)")
            for k, v in claims.items():
                if not v:
                    print(f"    FAILED CLAIM: {k}")
        except Exception as e:
            n_err += 1
            print(f"[bench] {name:24s} ERROR: {e!r}")
            traceback.print_exc()
    print(f"[bench] total: {n_pass}/{n_claims} claims pass, "
          f"{n_err} module errors")
    if args.smoke:
        return 0 if n_err == 0 else 1
    return 0 if (n_pass == n_claims and n_err == 0) else 1


if __name__ == "__main__":
    raise SystemExit(main())
