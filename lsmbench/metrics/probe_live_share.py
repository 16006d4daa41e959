"""Share (%) of the cells the Bloom probe screened in the window that it
needed: 100 times the window's ``probe_live_cells`` (the (table, key)
pairs whose table's key range holds the key, the rule
``roofline.probe_bytes`` counts bytes by) over its ``probe_cells`` (the
stack rows times keys each launch screened, padding included), both
counters of ``StorageGroup.stats``."""


def read(run):
    cells = run.stats_delta.get("probe_cells", 0)
    if cells <= 0:
        return None
    return 100.0 * run.stats_delta["probe_live_cells"] / cells
