"""Per-request trace spans (DESIGN.md §12).

A span is a plain JSON-able list ``[name, host, t0, t1]`` with
``time.perf_counter()`` timestamps (monotonic *per host*; hosts are not
clock-synchronized, which is why the Chrome-trace export maps each host
to its own ``pid`` instead of fabricating a global timeline). A span may
carry a fifth element, a dict of counts, exported as the trace event's
``args``.

Span vocabulary along the request path (indented: nested in the span
above):

    admit        submit(): request prepared, keyed and queued
    route        cluster frontend routing decision (cluster only)
    retry        failure detected -> re-admission on a surviving host
                 (failover/hedge only; precedes a fresh route span)
    batch_wait   admitted -> the request's bucket batch dispatched
    operands     operand build / device upload (cache hit makes it short)
    compute      dispatch -> device results materialized
      pull       wait for the device + device-to-host copy of the results
    complete     result finalization
      results    slice-out and rate accounting of each request
        wire_measure  rANS coding + wire-model accounting (measure_wire only)
      drift      SE-drift tail; counts ``{"lookups", "misses"}`` of the
                 SE-prediction memo and ``{"table", "quadrature"}`` of
                 the SE recursion's MMSE evaluations (telemetry/drift.py)

``phase`` records one service phase: the span above, and the same
interval as a ``jax.profiler.TraceAnnotation`` named ``amp.<phase>`` in
the profiler's host trace, which shares its clock with the device
planes. Phases without a span of their own appear only there:
``dp_allocate`` (inside admit), ``a_stack`` and ``params`` (inside
operands) and ``dispatch`` (the enqueue of the engine call).

Spans ride on ``SolveRequest.spans`` / ``SolveResult.spans`` and cross
host boundaries inside codec JSON headers (floats round-trip exactly
through Python's ``json``).
"""
from __future__ import annotations

import json
import time
from typing import IO, Iterable, List, Optional, Sequence

from jax.profiler import TraceAnnotation

__all__ = [
    "now", "span", "phase", "span_names", "spans_monotonic", "missing_spans",
    "expected_spans", "tag_host", "chrome_trace_events", "write_trace_jsonl",
]

Span = List  # [name: str, host: str | None, t0: float, t1: float(, counts)]

CORE_SPANS = ("admit", "batch_wait", "operands", "compute", "pull",
              "complete", "results", "drift")


def now() -> float:
    return time.perf_counter()


def span(name: str, t0: float, t1: Optional[float] = None,
         host: Optional[str] = None) -> Span:
    return [name, host, float(t0), float(t1 if t1 is not None else now())]


class phase:
    """``with phase(name, on) as sp:`` times the block as the span ``sp``
    (``[name, None, t0, t1]``, t1 filled in on exit) inside a profiler
    annotation ``amp.<name>``. With ``on=False`` it does nothing and
    ``sp`` is None: no annotation, no timestamps."""

    __slots__ = ("_ann", "_span")

    def __init__(self, name: str, on: bool = True):
        if on:
            self._ann = TraceAnnotation("amp." + name)
            self._span = [name, None, 0.0, 0.0]
        else:
            self._ann = self._span = None

    def __enter__(self) -> Optional[Span]:
        if self._ann is not None:
            self._ann.__enter__()
            self._span[2] = time.perf_counter()
        return self._span

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._span[3] = time.perf_counter()
            self._ann.__exit__(*exc)
        return False


def span_names(spans: Optional[Sequence[Span]]) -> List[str]:
    return [s[0] for s in (spans or [])]


def tag_host(spans: Optional[Sequence[Span]], host: str) -> List[Span]:
    """Fill in the host field on spans that don't have one yet (the
    backend emits host=None; the frontend knows which host it routed to)."""
    return [[s[0], s[1] if s[1] is not None else host, *s[2:]]
            for s in (spans or [])]


def expected_spans(*, wire: bool = False, cluster: bool = False) -> List[str]:
    names = list(CORE_SPANS)
    if wire:
        names.insert(names.index("drift"), "wire_measure")
    if cluster:
        names.insert(1, "route")
    return names


def missing_spans(spans: Optional[Sequence[Span]], *, wire: bool = False,
                  cluster: bool = False) -> List[str]:
    """Names from the expected vocabulary absent from ``spans`` — an
    incomplete span tree means some plane dropped instrumentation."""
    have = set(span_names(spans))
    return [n for n in expected_spans(wire=wire, cluster=cluster)
            if n not in have]


def spans_monotonic(spans: Optional[Sequence[Span]]) -> bool:
    """Every span well-formed (t1 >= t0) and, per host, span start times
    non-decreasing in list order (the order the planes appended them)."""
    last_t0: dict = {}
    for s in (spans or []):
        name, host, t0, t1 = s[0], s[1], float(s[2]), float(s[3])
        if t1 < t0:
            return False
        if t0 < last_t0.get(host, -float("inf")):
            return False
        last_t0[host] = t0
    return True


def chrome_trace_events(request_id: int, spans: Sequence[Span]) -> List[dict]:
    """Chrome trace-event ``"X"`` (complete) events for one request.

    pid = host (hosts have independent clocks — keeping them in separate
    pid lanes is honest about skew), tid = request id, ts/dur in us; a
    span's counts ride as ``args``.
    """
    out = []
    for s in (spans or []):
        name, host, t0, t1 = s[0], s[1], float(s[2]), float(s[3])
        ev = {
            "name": name, "ph": "X", "pid": str(host or "local"),
            "tid": int(request_id), "ts": t0 * 1e6,
            "dur": max(t1 - t0, 0.0) * 1e6, "cat": "amp",
        }
        if len(s) > 4:
            ev["args"] = dict(s[4])
        out.append(ev)
    return out


def write_trace_jsonl(fp: IO[str], results: Iterable) -> int:
    """Append one Chrome trace event per line for each result carrying
    spans. Returns the number of events written. The file is valid JSONL;
    ``[`` + join(lines, ",") + ``]`` is a loadable Chrome trace."""
    n = 0
    for r in results:
        spans = getattr(r, "spans", None)
        if not spans:
            continue
        rid = getattr(r, "request_id", -1)
        for ev in chrome_trace_events(rid, spans):
            fp.write(json.dumps(ev, separators=(",", ":")) + "\n")
            n += 1
    return n
