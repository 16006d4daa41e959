"""The benchmark's front door: the client's side of the served path.

An open loop takes the operations whose scheduled time has come and
coalesces them into one call per kind: ``put_batch`` for writes (the
entries it does not admit stay queued and are offered again, so a write
completes only when it is admitted), ``get_batch`` for reads, and one
``scan_range`` per scan.  Each operation's latency runs from its
scheduled time to the completion of the call that carried it, so a
stall or a lock held by the pump charges every operation it delays.

Every admitted write, every read's answer and every scan's answer is
logged with its position in the admitted-write history, for the check
against the reference.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np
from jax.profiler import TraceAnnotation

from .traffic import READ, SCAN, WRITES, Ops

#: pause before a stalled writer offers its queue again (s)
RETRY_S = 0.0005


@dataclass
class Log:
    """What the store admitted and answered, in call order."""
    wkeys: list = field(default_factory=list)
    wvals: list = field(default_factory=list)
    admitted: int = 0
    reads: list = field(default_factory=list)   # (pos, keys, found, vals)
    scans: list = field(default_factory=list)   # (pos, lo, hi, keys, vals)

    def admit(self, keys, vals) -> None:
        if len(keys):
            self.wkeys.append(np.array(keys, np.uint32))
            self.wvals.append(np.array(vals, np.int32))
            self.admitted += len(keys)

    def writes(self) -> tuple[np.ndarray, np.ndarray]:
        if not self.wkeys:
            return np.empty(0, np.uint32), np.empty(0, np.int32)
        return np.concatenate(self.wkeys), np.concatenate(self.wvals)


@dataclass
class Phase:
    """Timing of one phase of traffic."""
    scheduled: np.ndarray        # per op (s, perf_counter clock)
    picked: np.ndarray           # when the loop took it up
    done: np.ndarray             # completion of its call, NaN if never
    gave_up: float               # when the loop stopped waiting
    stalls: list                 # (start, end) spans of a stalled writer
    overshoot: list              # sleep overshoot past the next arrival


class _Stall:
    def __init__(self):
        self.since = None
        self.spans: list[tuple[float, float]] = []

    def update(self, stalled: bool, t: float) -> None:
        if stalled and self.since is None:
            self.since = t
        elif not stalled and self.since is not None:
            self.spans.append((self.since, t))
            self.since = None

    def close(self, t: float) -> list:
        self.update(False, t)
        return self.spans


class FrontDoor:
    def __init__(self, group, log: Log, max_batch: int, driver=None,
                 probe_bytes=None):
        self.group = group
        self.log = log
        self.max_batch = int(max_batch)
        self.driver = driver
        self.probe_bytes = probe_bytes      # traced runs: probe accounting

    def _check_driver(self) -> None:
        if self.driver is not None and self.driver.error is not None:
            raise RuntimeError("the pump thread failed") \
                from self.driver.error

    def _put(self, keys, vals) -> int:
        with TraceAnnotation("lsmbench.put"):
            n = self.group.put_batch(keys, vals)
        self.log.admit(keys[:n], vals[:n])
        return n

    def _get(self, keys) -> None:
        pos = self.log.admitted
        if self.probe_bytes is not None:
            self.probe_bytes.before(keys)
        with TraceAnnotation("lsmbench.get"):
            found, vals = self.group.get_batch(keys)
        if self.probe_bytes is not None:
            self.probe_bytes.after()
        self.log.reads.append((pos, keys, found, vals))

    def _scan(self, lo: int, hi: int) -> None:
        pos = self.log.admitted
        with TraceAnnotation("lsmbench.scan"):
            ks, vs = self.group.scan_range(lo, hi)
        self.log.scans.append((pos, lo, hi, ks, vs))

    def open_loop(self, ops: Ops, scheduled: np.ndarray, close: float,
                  grace_s: float) -> Phase:
        """Serve ``ops`` at their ``scheduled`` times; after ``close``
        keep serving what is left for up to ``grace_s``."""
        n = len(ops)
        is_w = np.isin(ops.kind, WRITES)
        is_r = ops.kind == READ
        is_s = ops.kind == SCAN
        picked = np.full(n, np.nan)
        done = np.full(n, np.nan)
        wq = rq = sq = np.empty(0, np.int64)
        stall, overshoot = _Stall(), []
        nxt = 0
        deadline = close + grace_s
        while True:
            self._check_driver()
            now = time.perf_counter()
            due = int(np.searchsorted(scheduled, now, side="right"))
            if due > nxt:
                new = np.arange(nxt, due)
                picked[new] = now
                wq = np.concatenate([wq, new[is_w[new]]])
                rq = np.concatenate([rq, new[is_r[new]]])
                sq = np.concatenate([sq, new[is_s[new]]])
                nxt = due
            if len(wq):
                take = wq[:self.max_batch]
                a = self._put(ops.key[take], ops.val[take])
                t = time.perf_counter()
                done[take[:a]] = t
                wq = wq[a:]
                stall.update(a < len(take), t)
            if len(rq):
                take = rq[:self.max_batch]
                self._get(ops.key[take])
                done[take] = time.perf_counter()
                rq = rq[len(take):]
            for i in sq:
                self._scan(int(ops.key[i]), int(ops.hi[i]))
                done[i] = time.perf_counter()
            sq = sq[:0]
            if nxt == n and not len(wq) and not len(rq):
                break
            if time.perf_counter() > deadline:
                break
            if not len(rq):
                if len(wq):
                    time.sleep(RETRY_S)
                elif nxt < n:
                    wait = scheduled[nxt] - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                        overshoot.append(time.perf_counter()
                                         - scheduled[nxt])
        end = time.perf_counter()
        return Phase(scheduled, picked, done, end, stall.close(end),
                     overshoot)
