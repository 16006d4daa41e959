"""Total time (ms) the pump spent flushing memtables (``lsm.pump.flush``:
seal and sort, table build, checksum, bind) in the traced window."""


def read(run):
    sp = getattr(run, "spans", None)
    if not sp or not sp["spans"]:
        return None
    return 1e3 * sp["spans"].get("lsm.pump.flush",
                                 {"total_s": 0.0})["total_s"]
