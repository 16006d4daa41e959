"""Latency arithmetic, kept with the benchmark so that no change to the
program changes the yardstick.

``LatencyRecorder`` is a copy of the repo's
``repro.core.metrics.LatencyRecorder`` (``np.percentile`` over the
observed samples); ``latencies`` adds the rule for operations that
never completed: they count as late by the whole time the run waited
for them.
"""
from __future__ import annotations

import numpy as np


class LatencyRecorder:
    """Per-operation latency sampler for an open-loop foreground: each
    sample is ``completion - scheduled``, so a stall charges every
    operation it delays."""

    def __init__(self):
        self._samples: list[float] = []

    def __len__(self) -> int:
        return len(self._samples)

    def observe(self, seconds: float) -> None:
        self._samples.append(float(seconds))

    def percentiles(self, pcts=(50.0, 99.0, 99.9)) -> dict[float, float]:
        if not self._samples:
            return {float(p): 0.0 for p in pcts}
        a = np.asarray(self._samples)
        return {float(p): float(np.percentile(a, p)) for p in pcts}

    def summary(self) -> dict:
        p = self.percentiles()
        return {"n": len(self), "p50": p[50.0], "p99": p[99.0],
                "p999": p[99.9], "max": float(max(self._samples))
                if self._samples else 0.0}


def latencies(scheduled: np.ndarray, completed: np.ndarray,
              gave_up: float) -> np.ndarray:
    """Seconds from each operation's scheduled time to the completion of
    the call that carried it.  An operation that never completed (NaN)
    counts as late by the time the run waited for it (``gave_up``
    minus its schedule), which is past every limit."""
    done = np.where(np.isnan(completed), gave_up, completed)
    return done - np.asarray(scheduled, np.float64)


def tail(lat: np.ndarray, pct: float) -> float:
    """The ``pct`` percentile of ``lat``, as ``LatencyRecorder`` takes
    it."""
    rec = LatencyRecorder()
    rec._samples = np.asarray(lat, np.float64).tolist()
    return rec.percentiles((pct,))[float(pct)]
