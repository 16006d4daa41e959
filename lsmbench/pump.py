"""The background plane as a user runs it: a copy of the pacing loop of
the repo's ``repro.core.engine.BackgroundDriver``, timed.

Each pass owes ``elapsed * rate`` entries of I/O budget, offers the
deficit (capped at four quanta) to ``StorageGroup.pump`` under the
group's lock, and sleeps ``quantum_s``.  The copy records when each
``pump`` call took the lock and how long it held it, what it was
offered and what it spent, and wraps the call in a profiler span.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation


class PumpDriver:
    def __init__(self, group, bytes_per_s: float, quantum_s: float,
                 entry_bytes: int):
        self.group = group
        self.per_s = float(bytes_per_s) / entry_bytes
        self.quantum_s = float(quantum_s)
        self._lock = group.lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        self.calls: list[tuple[float, float, int, int]] = []
        #            (lock taken at, hold s, offered, spent)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="lsmbench-pump")
        self._thread.start()

    def _run(self) -> None:
        try:
            t0 = time.monotonic()
            delivered = 0.0
            q_max = max(1, int(4 * self.per_s * self.quantum_s))
            while not self._stop.is_set():
                deficit = (time.monotonic() - t0) * self.per_s - delivered
                quantum = min(int(deficit), q_max)
                if quantum >= 1:
                    with self._lock:
                        t1 = time.perf_counter()
                        with TraceAnnotation("lsmbench.pump"):
                            spent = self.group.pump(quantum)
                        hold = time.perf_counter() - t1
                    self.calls.append((t1, hold, quantum, int(spent)))
                    delivered += quantum
                self._stop.wait(self.quantum_s)
        except BaseException as e:            # surfaced by the client loop
            self.error = e

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=120)
            if self._thread.is_alive():
                raise RuntimeError("the pump thread did not stop")
            self._thread = None

    def window(self, t0: float, t1: float) -> dict:
        """Pump calls that took the lock in ``[t0, t1)``: hold times (s),
        and the entries offered and spent against the budget owed."""
        calls = [c for c in list(self.calls) if t0 <= c[0] < t1]
        owed = (t1 - t0) * self.per_s
        return {"holds": np.array([c[1] for c in calls]),
                "at": np.array([c[0] for c in calls]),
                "offered_share": sum(c[2] for c in calls) / owed,
                "spent_share": sum(c[3] for c in calls) / owed,
                "spent": sum(c[3] for c in calls)}
