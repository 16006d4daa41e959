"""99th percentile (ms) of how long a get or put call waited for the
store's group lock (``lsm.lock`` inside ``lsm.get`` or ``lsm.put``):
the time foreground calls waited for the pump."""
from ..latency import tail


def read(run):
    sp = getattr(run, "spans", None)
    if not sp:
        return None
    waits = [s for name, parent, s in sp["calls"]
             if name == "lsm.lock" and parent in ("lsm.get", "lsm.put")]
    return tail(waits, 99.0) * 1e3 if waits else None
