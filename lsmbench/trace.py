"""Reduction of a profiler trace to the numbers the metrics read.

Input is a list of planes, each ``{"name", "lines": [{"name",
"events": [(name, start_ns, duration_ns), ...]}]}``, as ``load`` reads
it from an ``.xplane.pb`` file.  Device planes are named
``/device:<kind>:<n>``; their programs are the events of the
``XLA Modules`` line.  Host spans are the events whose name starts with
``lsmbench.``: the front door's ``get``, ``put`` and ``scan`` calls,
the pump's calls and the measured ``window``.

``reduce`` gives, over the window:

- ``busy_s``: the union of the intervals in which a program ran on a
  device, averaged over the devices that ran any;
- ``window_s``: the window's length;
- ``programs``: device seconds per program name;
- ``by_span``: device seconds per (host span, program name), a program
  going to the innermost benchmark span that holds its start;
- ``gaps``: every idle interval, labelled with the benchmark span
  that holds its midpoint, or ``idle`` where there is none.
"""
from __future__ import annotations

import bisect
import collections
import re

SPAN_PREFIX = "lsmbench."
WINDOW = SPAN_PREFIX + "window"
MODULE_LINES = ("XLA Modules",)
_ID = re.compile(r"\(\d+\)$")


def load(path) -> list[dict]:
    """Planes of an ``.xplane.pb`` file in ``reduce``'s form."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [(ev.name, float(ev.start_ns),
                                    float(ev.duration_ns))
                                   for ev in line.events]}
                       for line in plane.lines]}
            for plane in pd.planes]


def program_name(name: str) -> str:
    """A device program's name without its trailing unique id."""
    return _ID.sub("", name).strip()


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Spans:
    """Benchmark spans for ``at(t)``: of the spans holding ``t``, the
    one that started last.  Spans of one host thread do not overlap, so
    each thread has at most one candidate."""

    def __init__(self, per_thread):
        self.threads = []
        for spans in per_thread:
            spans = sorted(spans, key=lambda sp: sp[1])   # (kind, s, e)
            self.threads.append((spans, [sp[1] for sp in spans]))

    def at(self, t: float) -> str:
        best = None
        for spans, starts in self.threads:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and spans[i][2] >= t and \
                    (best is None or spans[i][1] > best[1]):
                best = spans[i]
        return best[0] if best else "idle"


def reduce(planes: list[dict]) -> dict:
    threads, window = [], None
    devices = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            mods = [ln for ln in plane["lines"]
                    if ln["name"] in MODULE_LINES]
            evs = [ev for ln in mods for ev in ln["events"]]
            if evs:
                devices.append(evs)
            continue
        for ln in plane["lines"]:
            spans = []
            for name, s, d in ln["events"]:
                if name == WINDOW:
                    window = (s, s + d)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):], s, s + d))
            threads.append(spans)
    if window is None:
        raise ValueError("the trace holds no window span")
    w0, w1 = window
    host = _Spans(threads)
    programs = collections.Counter()
    by_span = collections.Counter()
    busy = []
    gaps = []
    for evs in devices:
        inside = []
        for name, s, d in evs:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            prog = program_name(name)
            programs[prog] += (e0 - s0) / 1e9
            by_span[(host.at(s), prog)] += (e0 - s0) / 1e9
            inside.append((s0, e0))
        merged = _union(inside)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((host.at((a + b) / 2), (b - a) / 1e9))
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": (w1 - w0) / 1e9,
            "devices": len(devices),
            "programs": dict(programs),
            "by_span": dict(by_span),
            "gaps": gaps}


def device_seconds(red: dict, match: str, span: str | None = None) -> float:
    """Device seconds of programs whose name contains ``match``, inside
    the host span ``span`` when one is given."""
    if span is None:
        return sum(v for k, v in red["programs"].items() if match in k)
    return sum(v for (sp, k), v in red["by_span"].items()
               if sp == span and match in k)


def breakdown(red: dict, n: int = 10) -> dict:
    """The ``n`` programs that took most device time, and the ``n``
    longest idle gaps, each named by what the host was doing."""
    ops = sorted(red["programs"].items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(red["gaps"], key=lambda g: -g[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"host:{k}", v] for k, v in gaps]}
