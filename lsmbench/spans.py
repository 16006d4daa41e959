"""The program's own spans in a profiler trace: what the store was doing.

The store opens ``lsm.``-prefixed spans at its layer boundaries
(``repro.core.tracing``: ``lsm.get``, ``lsm.get.probe``, ``lsm.pump``,
...).  They lie in the same planes as the benchmark's ``lsmbench.``
spans and the device's programs, on the same clock, and a span's parent
is the span that holds it on the same thread.  ``trace.reduce`` reads
only ``lsmbench.`` spans, so these leave its numbers unchanged.

``reduce(planes)`` gives, over the window, for the spans that start in
it:

- ``spans``: per span name its ``count``, ``total_s`` and ``self_s``
  (duration less the child spans it holds);
- ``calls``: ``(name, parent, seconds)`` of each span, the parent
  ``None`` at the top, for percentiles;
- ``programs``: device seconds per (program span, program name), a
  program going to the innermost ``lsm.`` span that holds its start;
- ``gaps``: each idle gap of ``trace.reduce``, in its order, as
  ``(benchmark span, program span, seconds)``: the program span is the
  innermost one holding the gap's midpoint, ``None`` where none does.

Where several threads hold a point, the span that started last names
it, the rule of ``trace.reduce``.  Program spans record only while a
profiler trace runs, so they cost nothing in an untraced run.

    python3 -m lsmbench.spans --workload <cell> --seed <n> --seconds <s>

runs the cell as ``python3 -m lsmbench ... --trace 1`` does and adds to
its result line the span table, the span metrics' values, the idle gaps
named by program span and the share of idle seconds inside the
benchmark's get, put and pump calls that a program span names.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import sys
import types

from . import run
from . import trace as tr
from .metrics import reader

PREFIX = "lsm."
#: benchmark spans whose idle seconds a program span should name
CALLS = ("get", "put", "pump")
#: metric families read from program spans
METRICS = ("search_ms", "lock_wait_p99_ms", "wal_sync_ms", "flush_ms")


def _nest(spans):
    """One thread's spans ``(name, start, end)``, nested: each span's
    parent index and self time, and the thread's time cut into
    segments ``(start, end, span index)`` held by the innermost span."""
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    ends = [e for _, _, e in spans]
    parent = [-1] * len(spans)
    self_t = [0.0] * len(spans)
    segs, stack, at = [], [], 0.0

    def cut(i, a, b):
        if b > a:
            segs.append((a, b, i))
            self_t[i] += b - a

    for i, (_, s, _) in enumerate(spans):
        while stack and ends[stack[-1]] <= s:
            j = stack.pop()
            cut(j, at, ends[j])
            at = ends[j]
        if stack:
            cut(stack[-1], at, s)
            parent[i] = stack[-1]
            ends[i] = min(ends[i], ends[stack[-1]])
        stack.append(i)
        at = s
    while stack:
        j = stack.pop()
        cut(j, at, ends[j])
        at = ends[j]
    return spans, ends, parent, self_t, segs


class _Innermost:
    """``at(t)``: the innermost program span holding ``t``; of several
    threads', the one that started last."""

    def __init__(self, threads):
        self.threads = []
        for spans, _, _, _, segs in threads:
            self.threads.append((spans, segs, [sg[0] for sg in segs]))

    def at(self, t: float):
        best = None
        for spans, segs, starts in self.threads:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and segs[i][1] >= t:
                sp = spans[segs[i][2]]
                if best is None or sp[1] > best[1]:
                    best = sp
        return best[0] if best else None


def reduce(planes: list[dict]) -> dict:
    """The program spans of ``planes`` over the window (module
    docstring)."""
    bench, threads, devices, window = [], [], [], None
    for plane in planes:
        if plane["name"].startswith("/device:"):
            evs = [ev for ln in plane["lines"]
                   if ln["name"] in tr.MODULE_LINES for ev in ln["events"]]
            if evs:
                devices.append(evs)
            continue
        for ln in plane["lines"]:
            ours, theirs = [], []
            for name, s, d in ln["events"]:
                if name == tr.WINDOW:
                    window = (s, s + d)
                elif name.startswith(tr.SPAN_PREFIX):
                    theirs.append((name[len(tr.SPAN_PREFIX):], s, s + d))
                elif name.startswith(PREFIX):
                    ours.append((name, s, s + d))
            bench.append(theirs)
            if ours:
                threads.append(_nest(ours))
    if window is None:
        raise ValueError("the trace holds no window span")
    w0, w1 = window
    table = collections.defaultdict(lambda: {"count": 0, "total_s": 0.0,
                                             "self_s": 0.0})
    calls = []
    for spans, ends, parent, self_t, _ in threads:
        for i, (name, s, _) in enumerate(spans):
            if not w0 <= s < w1:
                continue
            row = table[name]
            row["count"] += 1
            row["total_s"] += (ends[i] - s) / 1e9
            row["self_s"] += self_t[i] / 1e9
            calls.append((name, spans[parent[i]][0] if parent[i] >= 0
                          else None, (ends[i] - s) / 1e9))
    ours = _Innermost(threads)
    theirs = tr._Spans(bench)
    programs = collections.Counter()
    gaps = []
    for evs in devices:
        inside = []
        for name, s, d in evs:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                programs[(ours.at(s), tr.program_name(name))] += \
                    (e0 - s0) / 1e9
                inside.append((s0, e0))
        merged = tr._union(inside)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                gaps.append((theirs.at(mid), ours.at(mid), (b - a) / 1e9))
    return {"spans": dict(table), "calls": calls,
            "programs": dict(programs), "gaps": gaps}


def label(gap) -> str:
    """A gap's breakdown label: ``host:<benchmark span>/<program span>``,
    or ``trace.breakdown``'s ``host:<benchmark span>`` where no program
    span holds it."""
    theirs, ours, _ = gap
    return f"host:{theirs}/{ours}" if ours else f"host:{theirs}"


def idle_gaps(sp: dict, n: int = 10) -> list:
    """The ``n`` longest idle gaps, each named by ``label``."""
    return [[label(g), g[2]] for g in
            sorted(sp["gaps"], key=lambda g: -g[2])[:n]]


def named_share(sp: dict, calls=CALLS):
    """Share (%) of the idle seconds inside the benchmark's ``calls``
    spans that a program span names; None where there are none."""
    inside = [g for g in sp["gaps"] if g[0] in calls]
    idle = sum(g[2] for g in inside)
    if idle <= 0:
        return None
    return 100.0 * sum(g[2] for g in inside if g[1]) / idle


def report(planes: list[dict]) -> dict:
    """What the tool adds to a traced run's result line."""
    sp = reduce(planes)
    rec = types.SimpleNamespace(spans=sp)
    return {"spans": {k: sp["spans"][k] for k in sorted(sp["spans"])},
            "span_metrics": {m: reader(m)(rec) for m in METRICS},
            "span_idle_gaps": idle_gaps(sp),
            "named_idle_share": named_share(sp),
            "span_programs": [[s, p, v] for (s, p), v in
                              sorted(sp["programs"].items(),
                                     key=lambda kv: -kv[1])[:10]]}


@contextlib.contextmanager
def kept_planes():
    """While it is open, each trace that ``run.run_cell`` reduces is kept:
    the list it yields gains the trace's planes."""
    kept, load = [], tr.load

    def keep(path):
        kept.append(load(path))
        return kept[-1]

    tr.load = keep
    try:
        yield kept
    finally:
        tr.load = load


def main(argv=None) -> int:
    """``python3 -m lsmbench`` with ``--trace 1``, its result line and
    notes extended by ``report``."""
    argv = list(sys.argv[1:] if argv is None else argv)
    emit = run.emit

    with kept_planes() as kept:
        def emit_report(out):
            if kept:
                out.update(report(kept[-1]))
                out["_notes"] += [
                    f"span {k}: n {v['count']}, total {v['total_s']:.6f} s, "
                    f"self {v['self_s']:.6f} s"
                    for k, v in out["spans"].items()]
            emit(out)

        run.emit = emit_report
        try:
            return run.main(argv + ["--trace", "1"])
        finally:
            run.emit = emit


if __name__ == "__main__":
    raise SystemExit(main())
