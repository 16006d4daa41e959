"""99th percentile (ms) over every write operation scheduled in the
window, from its scheduled arrival to the completion of the call that
carried it; an operation that never completed counts as late by the
whole time the run waited for it."""
from ..latency import tail
from ..traffic import UPDATE, INSERT


def read(run):
    lat = run.latencies(UPDATE, INSERT)
    return tail(lat, 99.0) * 1e3 if len(lat) else None
