"""Mean host self time (ms) of the table search after the probe
(``lsm.get.search``) per get call (``lsm.get``): the store's own spans
in the traced window."""


def read(run):
    sp = getattr(run, "spans", None)
    if not sp or "lsm.get" not in sp["spans"]:
        return None
    search = sp["spans"].get("lsm.get.search", {"self_s": 0.0})
    return 1e3 * search["self_s"] / sp["spans"]["lsm.get"]["count"]
