"""Starting shapes of a store, one module per merge policy.

``shapes/<policy>.py`` defines ``layout(cfg) -> list[dict]``: the runs a
load of ``cfg["records"]`` records through that policy leaves, each as
``{"level", "lo", "hi", "files", "stamp"}`` — records ``[lo, hi)`` in
insert order, split into ``files`` files of equal count with disjoint
key ranges, and the run's data age (higher is newer).
"""
