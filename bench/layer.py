"""Arithmetic shared by the per-layer metric readers (``bench/metrics``).

Each reader gets the run's context: ``log`` (what the loop saw),
``ids`` (answers due in the window), ``trace`` (the reduced profiler
trace of the window's last stretch with its ``host_span``, or None),
``cfg``, ``peaks``. A reader that finds nothing to read returns None, and
the metric is left out of the line; so does a device reader whose trace
lost kernel launches.
"""
from __future__ import annotations

import numpy as np

import roofline


def admit_ms(ctx) -> float | None:
    """Mean harness-clock time in ``submit()`` over the calls that
    dispatched no batch: admission alone."""
    t = [s for s, dispatched in ctx["log"].admit if not dispatched]
    return float(np.mean(t)) * 1e3 if t else None


def span_ms(ctx, name: str) -> float | None:
    """The service's own span ``name`` over the window's answers; spans a
    batch shares count once."""
    seen = {}
    for i in ctx["ids"]:
        for sp in ctx["log"].results[i].spans or ():
            if sp[0] == name:
                seen[(sp[2], sp[3])] = sp[3] - sp[2]
    if not seen:
        return None
    v = np.asarray(list(seen.values()))
    return float(np.mean(v)) * 1e3


# the LC kernels of each layout, as ``harness.KERNELS`` labels them
LC = {"row": ("z", "f"), "col": ("r", "inner")}


def _launch(ctx):
    """Bytes of one launch of each LC kernel, from the padded bucket stack
    of the window's batches; None unless every batch had one shape."""
    res = [ctx["log"].results[i] for i in ctx["ids"]]
    shapes = {(r.bucket.layout, r.bucket.n_proc, r.bucket.mp_pad,
               r.bucket.n_pad, r.batch_size) for r in res}
    if len(shapes) != 1:
        return None
    layout, p, mp, n, b = shapes.pop()
    if b != ctx["cfg"]["service"]["bucket_policy"]["max_batch"]:
        return None
    a_bytes = 2 if ctx["cfg"]["a_dtype"] == "bfloat16" else 4
    if layout == "col":
        if ctx["cfg"]["col_inner"] != 1:
            return None
        return {k: roofline.col_launch_bytes(k, b, p, mp, n // p, a_bytes)
                for k in LC["col"]}
    return {k: roofline.row_launch_bytes(k, b, p, mp, n, a_bytes)
            for k in LC["row"]}


def expected_launches(ctx) -> int:
    """Launches of each LC kernel that the traced stretch has to hold:
    ``t_max`` (one per iteration) for every batch that was dispatched and
    answered inside it, by the service's ``compute`` span (dispatch to
    finalize, on the harness's clock)."""
    lo, hi = ctx["trace"]["host_span"]
    batches = {}
    for i in ctx["ids"]:
        r = ctx["log"].results[i]
        for sp in r.spans or ():
            if sp[0] == "compute" and lo <= sp[2] and sp[3] <= hi:
                batches[(sp[2], sp[3])] = r.bucket.t_max
    return sum(batches.values())


def trace_complete(ctx) -> bool:
    """Whether the trace kept every LC kernel launch it should hold. The
    profiler drops events once its buffers fill; a trace that lost some
    would read too much idle time and too little kernel time. On several
    devices each one runs its part of every batch, so each has to hold
    the launches."""
    tr = ctx["trace"]
    if tr is None or not tr["devices"] or "host_span" not in tr:
        return False
    layouts = {ctx["log"].results[i].bucket.layout for i in ctx["ids"]}
    if len(layouts) != 1:
        return False
    want = expected_launches(ctx)
    kernels = LC[layouts.pop()]
    if "plane_counts" in tr:
        return want > 0 and all(c >= want for k in kernels
                                for c in tr["plane_counts"][k].values())
    return want > 0 and all(tr["kernels"][k]["count"] >= want
                            for k in kernels)


def lc_time_and_bytes(ctx):
    """(seconds, bytes) of the LC kernels in the traced stretch, or None."""
    per = _launch(ctx)
    if per is None or ctx["peaks"] is None or not trace_complete(ctx):
        return None
    k = ctx["trace"]["kernels"]
    secs = sum(k[name]["seconds"] for name in per)
    if secs <= 0:
        return None
    return secs, sum(k[name]["launches"] * b for name, b in per.items())


def lc_roofline(ctx) -> float | None:
    """LC kernels' bytes over their device time, as a share of the chip's
    peak HBM bandwidth, in %."""
    tb = lc_time_and_bytes(ctx)
    if tb is None:
        return None
    secs, nbytes = tb
    return 100.0 * nbytes / secs / ctx["peaks"]["hbm_bytes_per_s"]


def _solve_seconds(ctx) -> float:
    from harness import SOLVE_MODULE
    return sum(v["seconds"] for k, v in ctx["trace"]["modules"].items()
               if SOLVE_MODULE in k)


def gc_share(ctx) -> float | None:
    """Share of the solve programs' device time outside the LC kernels
    (denoiser, transport, rate control, layout copies), in %."""
    tb = lc_time_and_bytes(ctx)
    if tb is None:
        return None
    secs = _solve_seconds(ctx)
    if secs <= 0:
        return None
    return 100.0 * (secs - tb[0]) / secs


def idle_share(ctx) -> float | None:
    """Share of the traced window in which no operation ran on the
    device, in %."""
    tr = ctx["trace"]
    if not trace_complete(ctx) or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
