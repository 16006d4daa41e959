"""Program spans: named host intervals in the profiler's trace.

``span(name)`` is ``jax.profiler.TraceAnnotation``.  It records only
while a profiler trace runs, into the same trace as the device's
programs and on the same clock, so a trace reader can say what the
host was doing while the device sat idle.  With no trace running a span
costs about half a microsecond; there is no switch.

Every span of the store is named ``lsm.<layer>[.<step>]``; a span's
parent is the span that holds it on the same thread.  A span opens at
a layer boundary, never inside a per-table or per-key loop.  Counters
live in the trees' ``stats`` (``StorageGroup.stats``), computed once
per call from arrays the code already has.
"""
from jax.profiler import TraceAnnotation as span

__all__ = ["span"]
