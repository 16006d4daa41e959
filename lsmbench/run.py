"""Runs one cell of the benchmark once and prints its result line.

    python3 -m lsmbench --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's store is built from the seed (``store.build``), the background
plane starts (``pump.PumpDriver``), and the cell's own traffic warms the
store up so that every program it needs is compiled: one read batch of
each probe size class, the mix's ``warmup_burst_ops`` operations back to
back (enough writes to reach a flush and a merge), then the mix's
``warmup_s`` seconds at its own pace.  The counters reset.  Then the
traffic runs for ``--seconds`` through the front door
(``frontdoor.FrontDoor``).  After the window the store's
answers, a read-back of written and loaded keys, and the reopened
write-ahead log are compared with the plain reference (``check``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, read from a profiler trace
of the window), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``, each compared number beside its limit.  The run exits
non-zero, printing no result, unless JAX's first device is a TPU and
there are as many as the cell asks for.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import check, spec, store
from .frontdoor import RETRY_S, FrontDoor, Log
from .keys import record_keys
from .latency import latencies
from .metrics import reader
from .pump import PumpDriver
from .traffic import INSERT, READ, SCAN, UPDATE, WRITES, Traffic

#: how long the front door keeps serving the window's operations after
#: the window closes before it counts the rest as unanswered (s)
GRACE_S = 60.0
#: written keys and loaded keys read back after the window
READBACK = 4096


class Run:
    """What one run measured, for the metric readers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def latencies(self, *kinds) -> np.ndarray:
        mask = np.isin(self.ops_kind, kinds)
        return latencies(self.phase.scheduled[mask], self.phase.done[mask],
                         self.phase.gave_up)


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _compile_counter():
    import jax
    counts = collections.Counter()

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["n"] += 1
            counts["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return counts


def _gc_pauses():
    """``(start, seconds, generation)`` of each garbage collection from
    now on, by the benchmark's clock, and the hook that records them,
    which the caller takes out of ``gc.callbacks`` again."""
    pauses, start = [], {}

    def hook(phase, info):
        if phase == "start":
            start["t"] = time.perf_counter()
        elif "t" in start:
            t = start.pop("t")
            pauses.append((t, time.perf_counter() - t, info["generation"]))

    gc.callbacks.append(hook)
    return pauses, hook


def _warm_probe(fd: FrontDoor, keys: np.ndarray, max_batch: int) -> None:
    """One read batch of each size class the probe pads to (powers of
    two of 1024 keys) up to ``max_batch``."""
    n = 1024
    while True:
        fd._get(keys[:min(n, max_batch)])
        if n >= max_batch:
            break
        n *= 2


def _burst(fd: FrontDoor, traffic, n: int, max_batch: int) -> None:
    """``n`` operations of the cell's traffic served back to back in
    batches of ``max_batch``, writes retried until admitted: a warm-up
    that reaches the flushes and merges the window will see."""
    done = 0
    while done < n:
        ops = traffic.take(min(max_batch, n - done))
        w = np.isin(ops.kind, WRITES)
        keys, vals = ops.key[w], ops.val[w]
        left = 0
        while left < len(keys):
            left += fd._put(keys[left:], vals[left:])
            if left < len(keys):
                fd._check_driver()
                time.sleep(RETRY_S)
        if (ops.kind == READ).any():
            fd._get(ops.key[ops.kind == READ])
        for i in np.flatnonzero(ops.kind == SCAN):
            fd._scan(int(ops.key[i]), int(ops.hi[i]))
        done += len(ops)


def _open_loop(fd: FrontDoor, ops, offsets: np.ndarray, seconds: float):
    """Serves ``ops`` through the front door at ``offsets`` seconds from
    now; returns when the window opened and the phase's timing.  The
    operations are drawn before the clock starts, so that drawing them
    delays none."""
    t0 = time.perf_counter()
    return t0, fd.open_loop(ops, t0 + offsets, t0 + seconds, GRACE_S)


def _draw(traffic: Traffic, seconds: float):
    """``seconds`` of the cell's traffic and each operation's offset."""
    return (traffic.take(int(round(traffic.rate * seconds))),
            traffic.arrivals(seconds))


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             traced: bool, t_process: float, root: Path = spec.ROOT,
             build=store.build) -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    The cell's files are found under ``root``; ``build`` makes the store
    (``store.build``, or a broken one for the control and the faults)."""
    import jax
    from jax.profiler import TraceAnnotation

    _, cfg, mix = spec.cell(bench, name, root)
    compiles = _compile_counter()
    pauses, gc_hook = _gc_pauses()
    backend = store.counting_backend()
    dev = jax.devices()[0]
    steps = {}
    with tempfile.TemporaryDirectory(prefix="lsmbench-") as tmp:
        t = time.perf_counter()
        steps["jax start"] = t - t_process
        group, wal_path = build(cfg, seed, backend, Path(tmp))
        steps["store build"] = time.perf_counter() - t
        traffic = Traffic(mix, seed, cfg["records"])
        driver = PumpDriver(group, cfg["io_budget_bytes_per_s"],
                            cfg["pump_quantum_s"], cfg["record_bytes"])
        log = Log()
        max_batch = int(mix["max_batch"])
        probe = None
        if traced:
            from .roofline import ProbeBytes
            probe = ProbeBytes(group, backend)
        fd = FrontDoor(group, log, max_batch, driver, probe)
        driver.start()
        try:
            # -- warm-up: the cell's own shapes, then its own traffic --
            loaded = np.random.default_rng([seed, 0x3A]).integers(
                0, cfg["records"], max_batch)
            t = time.perf_counter()
            if READ in traffic.shares:
                _warm_probe(fd, record_keys(loaded), max_batch)
            steps["probe warm-up"] = time.perf_counter() - t
            t = time.perf_counter()
            _burst(fd, traffic, int(mix.get("warmup_burst_ops", 0)),
                   max_batch)
            steps["warm-up burst"] = time.perf_counter() - t
            t = time.perf_counter()
            _open_loop(fd, *_draw(traffic, float(mix["warmup_s"])),
                       float(mix["warmup_s"]))
            steps["warm-up traffic"] = time.perf_counter() - t
            compiles_setup = compiles["n"]
            backend.reset_counts()
            stats0 = group.stats
            probe_bytes0 = probe.total if probe else 0
            trace_dir = Path(tmp) / "trace"
            if traced:
                import jax.profiler as jp
                opts = jp.ProfileOptions()
                opts.python_tracer_level = 0
                jp.start_trace(str(trace_dir), profiler_options=opts)
            # -- the measured window --
            ops, offsets = _draw(traffic, seconds)
            kinds = ops.kind
            with TraceAnnotation("lsmbench.window"):
                t_start, phase = _open_loop(fd, ops, offsets, seconds)
            t_close = t_start + seconds
            if traced:
                jp.stop_trace()
            compiles_window = compiles["n"] - compiles_setup
            stats1 = group.stats
            probe_bytes = (probe.total - probe_bytes0) if probe else 0
        finally:
            driver.stop()
            gc.callbacks.remove(gc_hook)
        pump = driver.window(t_start, t_close)
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        # -- read back written and loaded keys (still the timed path) --
        rng = np.random.default_rng([seed, 0x8ACC])
        wkeys, wvals = log.writes()
        back = rng.choice(wkeys, min(READBACK, len(wkeys)), replace=False) \
            if len(wkeys) else np.empty(0, np.uint32)
        back = np.concatenate([back, record_keys(
            rng.integers(0, cfg["records"], READBACK))])
        n_reads = len(log.reads)
        for s in range(0, len(back), max_batch):
            fd._get(back[s:s + max_batch])
        readback = log.reads[n_reads:]
        del log.reads[n_reads:]
        decisions = dict(backend.decisions)
        group.close()
        from repro.core.wal import WriteAheadLog
        reopened = WriteAheadLog(wal_path)
        wal_keys, wal_vals = reopened.entries_since(0)
        reopened.close()
        red = None
        if traced:
            from . import trace as tr
            red = tr.reduce(tr.load(next(trace_dir.rglob("*.xplane.pb"))))
    # -- the reference, once the store's work is done --
    hist = check.loaded_history(store.record_values(seed, cfg["records"]),
                                wkeys, wvals)
    unanswered = int(np.isnan(phase.done).sum())
    numbers = {
        "read_mismatches": check.read_mismatches(hist, log.reads),
        "scan_mismatches": check.scan_mismatches(hist, log.scans),
        "readback_mismatches": check.read_mismatches(hist, readback),
        "wal_mismatches": check.wal_mismatches(wal_keys, wal_vals,
                                               wkeys, wvals),
        "unanswered": unanswered,
        "not_compiled": sum(n for (op, mode), n in decisions.items()
                            if mode != "compiled"),
    }
    correct = all(numbers[k] <= lim for k, lim in check.LIMITS.items())
    wrong = numbers["read_mismatches"] + numbers["scan_mismatches"]
    completed = int((phase.done <= t_close).sum())
    run = Run(setup_s=t_start - t_process, seconds=float(seconds),
              window=(t_start, t_close), phase=phase, ops_kind=kinds,
              completed=completed, trace=red, probe_bytes=probe_bytes,
              device_kind=dev.device_kind, pump=pump, memory_peak=mem,
              gc=[p for p in pauses if t_start <= p[0] < t_close],
              stats_delta={k: stats1[k] - stats0[k] for k in stats1})
    metrics = {}
    for m in spec.metrics_for(bench, name, traced):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": int(len(phase.done)),
           "failed": unanswered + wrong, "metrics": metrics,
           "device": device}
    if red is not None:
        from .trace import breakdown
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = breakdown(red)
    out["checks"] = {k: {"value": numbers[k], "limit": lim}
                     for k, lim in check.LIMITS.items()}
    out["_notes"] = _notes(run, steps, compiles_setup, compiles_window,
                           compiles, decisions)
    return out


def _notes(run, steps, compiles_setup, compiles_window, compiles,
           decisions):
    """Lines for standard error: what the window saw besides metrics."""
    ph = run.phase
    d = run.stats_delta
    lines = ["set-up: " + ", ".join(f"{k} {v:.3f} s" for k, v in
                                    steps.items()),
             f"window: {d['flushes']} flushes, {d['merges']} merges "
             f"completed",
             f"compilations: set-up {compiles_setup}, window "
             f"{compiles_window} ({compiles['s']:.3f} s compiling in all)",
             "decisions: " + ", ".join(f"{op}->{mode} {n}" for (op, mode), n
                                       in sorted(decisions.items()))]
    lag = ph.picked - ph.scheduled
    lag = lag[np.isfinite(lag)]
    if len(lag):
        over = np.percentile(ph.overshoot, 99) if ph.overshoot else 0.0
        lines.append(f"generator: pick-up lag p50 {np.median(lag) * 1e3:.3f}"
                     f" ms, p99 {np.percentile(lag, 99) * 1e3:.3f} ms; "
                     f"sleep overshoot p99 {over * 1e3:.3f} ms")
    lines.append(f"pump: offered {run.pump['offered_share']:.4f} and spent "
                 f"{run.pump['spent_share']:.4f} of the budget owed; "
                 f"{len(run.pump['holds'])} calls")
    for kind, label in ((READ, "read"), (SCAN, "scan"), (UPDATE, "update"),
                        (INSERT, "insert")):
        lat = run.latencies(kind)
        if len(lat):
            lines.append(f"{label}: n {len(lat)}, p50 "
                         f"{np.median(lat) * 1e3:.3f} ms, p99 "
                         f"{np.percentile(lat, 99) * 1e3:.3f} ms")
    t0 = run.window[0]
    holds, at = run.pump["holds"], run.pump["at"]
    lines.append("pump: longest holds " + ", ".join(
        f"{holds[i] * 1e3:.1f} ms at +{at[i] - t0:.2f} s"
        for i in np.argsort(holds)[::-1][:3]))
    gen2 = [p for p in run.gc if p[2] == 2]
    lines.append(f"gc: {len(run.gc)} collections in the window, "
                 f"{len(gen2)} of generation 2, longest "
                 f"{max((p[1] for p in run.gc), default=0.0) * 1e3:.1f} ms")
    lat = latencies(ph.scheduled, ph.done, ph.gave_up)
    if len(lat) >= 100:
        worst = ph.scheduled[lat >= np.percentile(lat, 99)] - t0
        bins, counts = np.unique(np.floor(worst * 4) / 4,
                                 return_counts=True)
        lines.append("slowest 1% of ops, scheduled at: " + ", ".join(
            f"+{b:.2f} s ({c})" for b, c in
            sorted(zip(bins, counts), key=lambda bc: -bc[1])[:4]))
    lines.append(f"completed {run.completed} ops in {run.seconds} s; "
                 f"device memory peak {run.memory_peak} bytes")
    return lines


def main(argv=None) -> int:
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.load()
    wl, _, _ = spec.cell(bench, args.workload)
    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        _say(f"lsmbench: the program is missing ({src / 'repro'})")
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _say(f"lsmbench: needs a TPU; JAX's first device is "
             f"{devs[0].platform!r}")
        return 2
    if len(devs) < int(wl["chips"]):
        _say(f"lsmbench: the cell needs {wl['chips']} chips, JAX sees "
             f"{len(devs)}")
        return 2
    sys.path.insert(0, str(src))
    from repro.compile_cache import enable_compile_cache
    _say(f"compile cache: {enable_compile_cache()}")
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_process)
    emit(out)
    return 0


def emit(out: dict) -> None:
    """Notes and each compared number beside its limit on standard error,
    then the result line as the last line of standard output."""
    for line in out.pop("_notes"):
        _say(line)
    for k, c in out["checks"].items():
        _say(f"check {k}: {c['value']} (limit {c['limit']})")
    _say(f"correct: {out['correct']}")
    print(json.dumps(out), flush=True)
