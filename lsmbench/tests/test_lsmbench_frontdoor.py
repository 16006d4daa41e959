"""The front door's latency arithmetic: each operation counts from its
scheduled time, so a stalled write and the reads queued behind a slow
call are charged the whole wait."""
import time
import threading

import numpy as np
import pytest

from lsmbench.frontdoor import FrontDoor, Log
from lsmbench.latency import latencies, tail
from lsmbench.traffic import READ, UPDATE, Ops


class FakeGroup:
    """Refuses every write until ``stall_until``; reads take ``read_s``."""

    def __init__(self, stall_until=0.0, read_s=0.0):
        self.stall_until = stall_until
        self.read_s = read_s

    def lock(self):
        return threading.RLock()

    def put_batch(self, keys, vals):
        return 0 if time.perf_counter() < self.stall_until else len(keys)

    def get_batch(self, keys):
        time.sleep(self.read_s)
        return np.ones(len(keys), bool), np.zeros(len(keys), np.int32)


def _ops(kinds):
    n = len(kinds)
    return Ops(np.array(kinds, np.int8), np.arange(n, dtype=np.uint32),
               np.arange(n, dtype=np.int32), np.zeros(n, np.int64))


def test_a_stalled_write_counts_from_its_scheduled_time():
    t0 = time.perf_counter() + 0.01
    group = FakeGroup(stall_until=t0 + 0.2)
    log = Log()
    fd = FrontDoor(group, log, max_batch=16)
    sched = t0 + np.array([0.0, 0.05])
    ph = fd.open_loop(_ops([UPDATE, UPDATE]), sched, t0 + 0.1, 5.0)
    lat = latencies(ph.scheduled, ph.done, ph.gave_up)
    assert lat[0] >= 0.2 - 0.005          # stalled from 0 to 0.2
    assert lat[1] >= 0.15 - 0.005
    assert log.admitted == 2
    assert len(ph.stalls) == 1
    s, e = ph.stalls[0]
    # the stall ends at the first retry after 0.2 s; a loaded machine
    # may wake the retry late, never early
    assert t0 <= s < t0 + 0.2 <= e <= t0 + 0.7


def test_reads_queued_behind_a_slow_call_are_charged_the_wait():
    t0 = time.perf_counter() + 0.01
    group = FakeGroup(read_s=0.1)
    fd = FrontDoor(group, Log(), max_batch=16)
    sched = t0 + np.array([0.0, 0.01, 0.02])
    ph = fd.open_loop(_ops([READ] * 3), sched, t0 + 0.05, 5.0)
    lat = latencies(ph.scheduled, ph.done, ph.gave_up)
    # the first read's call ends at 0.1; the next two wait for it, then
    # ride one more call together
    assert lat[0] >= 0.1 - 0.002
    assert lat[1] >= 0.19 - 0.002 and lat[2] >= 0.18 - 0.002
    assert ph.done[1] == ph.done[2]


def test_unanswered_operations_count_the_whole_wait():
    lat = latencies(np.array([1.0, 2.0]), np.array([1.5, np.nan]), 10.0)
    assert list(lat) == [0.5, 8.0]
    assert tail(np.arange(101, dtype=float), 99.0) == pytest.approx(99.0)
