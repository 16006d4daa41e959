"""Median (ms) over every read operation scheduled in the window, from
its scheduled arrival to the completion of the call that carried it; an
operation that never completed counts as late by the whole time the run
waited for it."""
from ..latency import tail
from ..traffic import READ


def read(run):
    lat = run.latencies(READ)
    return tail(lat, 50.0) * 1e3 if len(lat) else None
