"""The reduction from a profiler trace to busy time, kernel time, idle
gaps and the per-layer metrics, on a small trace recorded on one v5e
(``data/trace_v5e.json``: 120 ms of a column backlog at N=12,000, M=3,000,
P=4, and 13 ms of the row cell with its ops shorter than 2 us left out)."""
import json
import os
import re
import types

import numpy as np
import pytest

import _paths
import harness
import layer
import roofline
import trace_reduce

EVENTS = json.load(open(os.path.join(_paths.BENCH, "tests", "data",
                                     "trace_v5e.json")))
SHAPE = {"col": ("col", 4, 3072, 12032), "row": ("row", 30, 112, 10240)}


def _busy_by_grid(events, lo, hi, step=100.0):
    """Busy seconds on a 0.1 us grid: a second, cruder way to the union."""
    grid = np.zeros(int((hi - lo) / step) + 1, bool)
    for e in events:
        if e["line"] != "XLA Ops":
            continue
        a = int(max(e["t0"] - lo, 0) / step)
        b = int(min(e["t0"] + e["dur"] - lo, hi - lo) / step)
        grid[a:b] = True
    return grid.sum() * step * 1e-9


@pytest.mark.parametrize("key", ["col", "row"])
def test_busy_and_idle_add_up(key):
    ev = EVENTS[key]
    red = trace_reduce.reduce(ev, harness.KERNELS)
    win = [e for e in ev if e["name"] == "bench.window"][0]
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(win["dur"] * 1e-9)
    assert red["busy_s"] == pytest.approx(
        _busy_by_grid(ev, win["t0"], win["t0"] + win["dur"]), abs=2e-6)
    idle = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], abs=1e-9)
    assert all(k.startswith("bench.") for k, _ in
               red["breakdown"]["idle_gaps"])


@pytest.mark.parametrize("key", ["col", "row"])
def test_kernel_time_by_hand(key):
    ev = EVENTS[key]
    red = trace_reduce.reduce(ev, harness.KERNELS)
    lo = 0.0
    hi = [e for e in ev if e["name"] == "bench.window"][0]["dur"]
    want = {"z": ("%amp_local_pallas_grid", "= ("),
            "f": ("%amp_local_pallas_grid", "= f32"),
            "r": ("%col_residual_pallas", ""),
            "inner": ("%col_inner_pallas", "")}
    for label, (prefix, mark) in want.items():
        hits = [e for e in ev if e["line"] == "XLA Ops"
                and e["name"].startswith(prefix) and mark in e["name"]]
        secs = sum(min(e["t0"] + e["dur"], hi) - max(e["t0"], lo)
                   for e in hits) * 1e-9
        assert red["kernels"][label]["count"] == len(hits)
        assert red["kernels"][label]["seconds"] == pytest.approx(secs)
    # the loop op that holds the kernels stays out of the op table
    assert not any(k.startswith("%while") for k in red["ops"])
    top = red["breakdown"]["device_ops"][0][0]
    assert top.startswith("%col_residual_pallas" if key == "col"
                          else "%amp_local_pallas_grid")


# launches of each LC kernel the recorded stretches hold (col 9 + 9, row
# 2 z-passes and 1 f-pass): a batch of this many iterations fits in each
KEPT = {"col": 9, "row": 1}


def _ctx(key, t_max=None):
    layout, p, mp, n = SHAPE[key]
    t_max = KEPT[key] if t_max is None else t_max
    bucket = types.SimpleNamespace(layout=layout, n_proc=p, mp_pad=mp,
                                   n_pad=n, t_max=t_max)
    # one batch dispatched and answered inside the traced stretch
    spans = [("compute", None, 10.0, 11.0)]
    res = types.SimpleNamespace(bucket=bucket, batch_size=16, spans=spans)
    log = types.SimpleNamespace(results={0: res}, admit=[(1e-4, False)])
    cfg = {"a_dtype": "float32", "col_inner": 1,
           "service": {"bucket_policy": {"max_batch": 16}}}
    trace = trace_reduce.reduce(EVENTS[key], harness.KERNELS)
    trace["host_span"] = (9.5, 11.5)
    return {"cfg": cfg, "log": log, "ids": [0], "trace": trace,
            "peaks": roofline.peaks("TPU v5 lite")}


def test_roofline_shares_from_the_trace():
    col, row = _ctx("col"), _ctx("row")
    # column kernels stream A near the HBM peak, the row kernels at half
    assert 80.0 < layer.lc_roofline(col) < 100.0
    assert 40.0 < layer.lc_roofline(row) < 70.0
    for ctx in (col, row):
        assert 0.0 < layer.gc_share(ctx) < 50.0
    assert 0.0 < layer.idle_share(col) < 100.0


def test_launches_cut_by_the_window_count_by_their_share():
    red = trace_reduce.reduce(EVENTS["col"], harness.KERNELS)
    for label in ("r", "inner"):
        k = red["kernels"][label]
        assert k["count"] - 1 < k["launches"] < k["count"]
    # bytes follow the share inside the window, so the roofline share is
    # that of the time inside it
    col = _ctx("col")
    per = layer._launch(col)
    secs, nbytes = layer.lc_time_and_bytes(col)
    assert nbytes == pytest.approx(sum(red["kernels"][k]["launches"] * b
                                       for k, b in per.items()))


@pytest.mark.parametrize("key", ["col", "row"])
def test_a_trace_that_lost_launches_reads_nothing(key):
    # a batch of more iterations than the trace kept launches for: the
    # profiler dropped events, and no device metric is read from it
    ctx = _ctx(key, t_max=KEPT[key] + 4)
    assert layer.expected_launches(ctx) == KEPT[key] + 4
    assert not layer.trace_complete(ctx)
    assert layer.lc_roofline(ctx) is None
    assert layer.gc_share(ctx) is None
    assert layer.idle_share(ctx) is None
    # a batch dispatched before the traced stretch is not expected in it
    ctx = _ctx(key, t_max=KEPT[key] + 4)
    ctx["trace"]["host_span"] = (10.5, 11.5)
    assert layer.expected_launches(ctx) == 0
    assert layer.lc_roofline(ctx) is None


def test_no_trace_or_no_device_reads_nothing():
    ctx = _ctx("col")
    ctx["trace"] = None
    assert layer.lc_roofline(ctx) is None
    assert layer.idle_share(ctx) is None
    host_only = [e for e in EVENTS["col"] if not e["line"].startswith("XLA")]
    ctx["trace"] = trace_reduce.reduce(host_only, harness.KERNELS)
    assert layer.idle_share(ctx) is None
    assert layer.lc_roofline(ctx) is None


@pytest.mark.parametrize("key", ["col", "row"])
def test_every_device_of_several_has_to_hold_its_launches(key):
    # the recorded stretch as if two devices ran it; then the second one
    # loses its kernel events, while the sum over both still suffices
    ev = EVENTS[key]
    dev = {e["plane"] for e in ev if e["line"] == "XLA Ops"}.pop()
    twin = [dict(e, plane=dev + "#2") for e in ev if e["plane"] == dev]
    ctx = _ctx(key)
    ctx["trace"] = dict(trace_reduce.reduce(ev + twin, harness.KERNELS),
                        host_span=(9.5, 11.5))
    counts = ctx["trace"]["plane_counts"]
    assert {k: sorted(v) for k, v in counts.items()} == {
        k: sorted([dev, dev + "#2"]) for k in harness.KERNELS}
    assert layer.trace_complete(ctx)
    assert layer.idle_share(ctx) is not None
    lost = [e for e in twin if not any(
        re.search(p, e["name"]) for p in harness.KERNELS.values())]
    ctx["trace"] = dict(trace_reduce.reduce(ev + lost, harness.KERNELS),
                        host_span=(9.5, 11.5))
    label = layer.LC[SHAPE[key][0]][0]
    assert ctx["trace"]["kernels"][label]["count"] >= KEPT[key]
    assert not layer.trace_complete(ctx)
    assert layer.idle_share(ctx) is None
