"""The service's own phases, as its spans and profiler annotations show them.

``SolveService`` records each phase of a request twice (see
``repro/telemetry/spans.py``): as a span on the result, on the host's
``perf_counter``, and as a ``jax.profiler.TraceAnnotation`` named
``amp.<phase>`` in the profiler's host trace, on the clock the device
planes share. A span may carry a fifth element, a dict of counts.

``span_counts`` reads the counts off the window's answers. ``extract``
keeps what ``trace_reduce.extract`` keeps plus the ``amp.*`` host
events. ``idle_by_phase`` cuts each idle gap of the first device where
an ``amp.*`` span starts or ends inside it and puts each piece under the
innermost ``amp.*`` span over it, else under the ``bench.*`` span that
``trace_reduce`` would name it by (its rule of labels, piece by piece;
a trace without ``amp.*`` spans splits as ``reduce``'s breakdown does); ``tail_idle_s`` is
the idle time under ``amp.complete``, its children included.
"""
from __future__ import annotations

import bisect

import trace_reduce as tr

AMP_PREFIX = "amp."
TAIL = "amp.complete"
NO_PHASE = "host: no bench span"    # trace_reduce's label of a bare gap


def span_counts(ctx, name: str) -> list | None:
    """The counts dicts of the service's span ``name`` over the window's
    answers; a span a batch shares counts once. None when no answer has
    such a span with counts (a program that records none)."""
    seen = {}
    for i in ctx["ids"]:
        for sp in ctx["log"].results[i].spans or ():
            if sp[0] == name and len(sp) > 4:
                seen[(sp[2], sp[3])] = sp[4]
    return list(seen.values()) or None


def extract(path: str) -> list:
    """``trace_reduce.extract``'s events of one trace file, and the host
    events of the service's phases."""
    from jax.profiler import ProfileData
    out = tr.extract(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(AMP_PREFIX):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name, "t0": float(ev.start_ns),
                                "dur": float(ev.duration_ns), "stats": {}})
    return out


def _window(events: list) -> tuple:
    win = [e for e in events if e["name"] == tr.WINDOW]
    if not win:
        raise ValueError(f"trace has no {tr.WINDOW!r} span")
    return win[0]["t0"], win[0]["t0"] + win[0]["dur"]


def idle_gaps(events: list) -> list:
    """[a, b) idle gaps of the first device in the window, found as
    ``trace_reduce.reduce`` finds them."""
    lo, hi = _window(events)
    planes = sorted({e["plane"] for e in events
                     if e["line"] in (tr.OPS_LINE, tr.MODULES_LINE)})
    if not planes:
        return []
    pe = [e for e in events if e["plane"] == planes[0]]
    op_ev = ([e for e in pe if e["line"] == tr.OPS_LINE]
             or [e for e in pe if e["line"] == tr.MODULES_LINE])
    busy = tr._union([(e["t0"], e["t0"] + e["dur"]) for e in op_ev], lo, hi)
    gaps, prev = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


class _Spans:
    """Host spans indexed by start, for the ones over an interval."""

    def __init__(self, events: list):
        self.ev = sorted(events, key=lambda e: e["t0"])
        self.t0 = [e["t0"] for e in self.ev]
        self.width = max((e["dur"] for e in self.ev), default=0.0)

    def over(self, a: float, b: float) -> list:
        lo = bisect.bisect_left(self.t0, a - self.width)
        hi = bisect.bisect_left(self.t0, b)
        return [e for e in self.ev[lo:hi] if e["t0"] + e["dur"] > a]


def idle_by_phase(events: list) -> dict:
    """Seconds of the first device's idle time by the innermost ``amp.*``
    span over each piece of a gap, else by its ``bench.*`` span; largest
    first."""
    amp = _Spans([e for e in events if e["name"].startswith(AMP_PREFIX)])
    bench = _Spans([e for e in events
                    if e["name"].startswith(tr.HOST_PREFIX)
                    and e["name"] != tr.WINDOW])
    out: dict = {}
    for a, b in idle_gaps(events):
        near = amp.over(a, b)
        cuts = sorted({a, b} | {t for e in near
                                for t in (e["t0"], e["t0"] + e["dur"])
                                if a < t < b})
        for p, q in zip(cuts, cuts[1:]):
            label = tr._label(near, p, q)
            if label == NO_PHASE:
                label = tr._label(bench.over(p, q), p, q)
            out[label] = out.get(label, 0.0) + (q - p) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def tail_idle_s(events: list) -> float:
    """Seconds of the first device's idle time that fall under an
    ``amp.complete`` span (results and drift tail included)."""
    lo, hi = _window(events)
    tails = tr._union([(e["t0"], e["t0"] + e["dur"]) for e in events
                       if e["name"] == TAIL], lo, hi)
    tot = 0.0
    for a, b in idle_gaps(events):
        for c, d in tails:
            tot += max(0.0, min(b, d) - max(a, c))
    return tot * 1e-9


def window_s(events: list) -> float:
    lo, hi = _window(events)
    return (hi - lo) * 1e-9


def batches(events: list) -> int:
    """``amp.complete`` spans that end inside the window: one per batch
    finalized on the batched path."""
    lo, hi = _window(events)
    return sum(1 for e in events if e["name"] == TAIL
               and lo <= e["t0"] + e["dur"] <= hi)
