"""Metric readers, one module per family: ``metrics/<family>.py``
defines ``read(run) -> float | None`` for every metric whose name up to
its first dot is ``<family>``.  ``run`` is the ``Run`` record of
``lsmbench.run``.  A reader that finds nothing to read returns None, and
the metric is left out of the result line."""
from __future__ import annotations

import importlib


def reader(name: str):
    family = name.split(".", 1)[0]
    return importlib.import_module(f"{__package__}.{family}").read
