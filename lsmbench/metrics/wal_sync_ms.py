"""Total time (ms) the store spent in WAL fsyncs (``lsm.wal.sync``:
group commits in put calls and the sync of every pump epoch) in the
traced window."""


def read(run):
    sp = getattr(run, "spans", None)
    if not sp or not sp["spans"]:
        return None
    return 1e3 * sp["spans"].get("lsm.wal.sync", {"total_s": 0.0})["total_s"]
