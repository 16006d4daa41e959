"""99th percentile (ms) of how long a background ``pump`` call held the
store's lock, over the calls that took it in the window."""
from ..latency import tail


def read(run):
    holds = run.pump["holds"]
    return tail(holds, 99.0) * 1e3 if len(holds) else None
