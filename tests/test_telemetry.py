"""Telemetry-plane tests (DESIGN.md §12): metrics registry + Prometheus
rendering, span propagation across every serving path (batched,
singleton fast path, measured-wire twin, cluster), codec round-trips of
span headers and metrics frames, TCP RTT histograms, and the live
SE-drift monitor — including the tier-2 acceptance criterion that a
mis-rated solve is flagged while clean solves pass."""
import dataclasses
import io
import json
import math
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.denoisers import BernoulliGauss
from repro.serving import (BucketPolicy, ClusterService, PrewarmSpec,
                           RouterPolicy, SolveRequest, SolveService,
                           decode_metrics, decode_request, encode_metrics,
                           encode_request, encode_result, decode_result)
from repro.serving.frontend import BackendServer, LocalBackend, TcpBackend
from repro.telemetry import (DRIFT_ALERT, MetricsRegistry, hist_quantile,
                             merge_snapshots, prometheus_text, se_drift,
                             se_prediction)
from repro.telemetry import spans as spans_mod
from repro.telemetry.spans import (chrome_trace_events, expected_spans,
                                   missing_spans, phase, span, span_names,
                                   spans_monotonic, tag_host,
                                   write_trace_jsonl)

POL = BucketPolicy(max_batch=8, n_quantum=64, mp_quantum=8)


def make_reqs(n_req, n=128, m=64, p=4, t=8, seed=0, snr_db=20.0,
              declared_snr=None, policy="fixed", **req_kw):
    """Requests whose data is generated at ``snr_db`` but *declared* at
    ``declared_snr`` (defaults to the truth) — the mis-rated knob for the
    drift tests."""
    import jax

    from repro.core.amp import sample_problem
    from repro.core.state_evolution import CSProblem

    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=snr_db)
    deltas = None
    if policy == "fixed":
        deltas = np.full(t, 0.05, np.float32)
        deltas[0] = np.inf
    reqs = []
    for i in range(n_req):
        _, a, y = sample_problem(jax.random.PRNGKey(seed + i), n, m, prior,
                                 prob.sigma_e2)
        reqs.append(SolveRequest(
            y=y, a=a, prior=prior, n_proc=p, n_iter=t, policy=policy,
            deltas=deltas,
            snr_db=declared_snr if declared_snr is not None else snr_db,
            **req_kw))
    return prior, reqs


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------

def test_counter_gauge_labels_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("amp_requests_total", "requests", ("layout",))
    g = reg.gauge("amp_inflight", "in flight")
    c.inc(layout="row")
    c.inc(2.0, layout="row")
    c.inc(layout="col")
    g.set(7.0)
    snap = reg.snapshot()
    by_name = {m["name"]: m for m in snap["metrics"]}
    assert by_name["amp_requests_total"]["kind"] == "counter"
    samples = {tuple(s["labels"].items()): s["value"]
               for s in by_name["amp_requests_total"]["samples"]}
    assert samples == {(("layout", "col"),): 1.0, (("layout", "row"),): 3.0}
    assert by_name["amp_inflight"]["samples"] == [{"labels": {}, "value": 7.0}]
    # label mismatch and re-registration with a different shape both fail
    with pytest.raises(ValueError):
        c.inc(host="x")
    with pytest.raises(ValueError):
        reg.gauge("amp_requests_total")
    with pytest.raises(ValueError):
        reg.counter("amp_requests_total", labelnames=("host",))
    # same name + same shape returns the same metric object
    assert reg.counter("amp_requests_total", labelnames=("layout",)) is c
    # set_total is absolute assignment (collector mirroring), not adding
    c.set_total(10.0, layout="row")
    assert reg.snapshot()["metrics"][-1]["samples"][-1]["value"] == 10.0


def test_histogram_counts_and_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("amp_lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    (s,) = reg.snapshot()["metrics"][0]["samples"]
    assert s["bounds"] == [0.01, 0.1, 1.0]
    assert s["counts"] == [1, 2, 1, 1]          # last bucket = +Inf overflow
    assert s["count"] == 5 and s["sum"] == pytest.approx(5.605)
    assert hist_quantile(s, 0.5) == 0.1
    assert hist_quantile(s, 0.95) == 1.0        # +Inf reports largest bound
    assert hist_quantile({"count": 0, "bounds": [], "counts": []}, 0.5) is None
    with pytest.raises(ValueError):
        reg.histogram("amp_bad", buckets=())


def test_registry_thread_safety():
    """Concurrent increments/observations from many threads lose nothing
    and snapshots taken mid-flight are never torn (count == sum of bucket
    counts)."""
    reg = MetricsRegistry()
    c = reg.counter("amp_n_total")
    h = reg.histogram("amp_v", buckets=(0.5,))
    n_threads, per_thread = 8, 2000

    def work():
        for i in range(per_thread):
            c.inc()
            h.observe((i % 2) * 1.0)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for _ in range(50):                         # reads racing the writers
        (s,) = reg.snapshot()["metrics"][1]["samples"] or [
            {"counts": [0, 0], "count": 0}]
        assert sum(s["counts"]) == s["count"]
    for t in threads:
        t.join()
    snap = reg.snapshot()
    by_name = {m["name"]: m for m in snap["metrics"]}
    assert by_name["amp_n_total"]["samples"][0]["value"] == \
        n_threads * per_thread
    assert by_name["amp_v"]["samples"][0]["count"] == n_threads * per_thread


def test_prometheus_text_rendering():
    reg = MetricsRegistry()
    reg.counter("amp_x_total", "help text", ("k",)).inc(3, k='a"b\\c')
    reg.histogram("amp_h", buckets=(1.0, 2.0)).observe(1.5)
    text = prometheus_text(reg.snapshot())
    lines = text.strip().splitlines()
    assert "# TYPE amp_h histogram" in lines
    assert 'amp_h_bucket{le="1"} 0' in lines
    assert 'amp_h_bucket{le="2"} 1' in lines
    assert 'amp_h_bucket{le="+Inf"} 1' in lines
    assert "amp_h_sum 1.5" in lines and "amp_h_count 1" in lines
    assert "# HELP amp_x_total help text" in lines
    # label values escaped per the exposition format
    assert r'amp_x_total{k="a\"b\\c"} 3' in lines
    assert prometheus_text({"metrics": []}) == ""


def test_merge_snapshots_adds_host_label():
    def one(v):
        r = MetricsRegistry()
        r.counter("amp_c_total", labelnames=("layout",)).inc(v, layout="row")
        return r.snapshot()

    merged = merge_snapshots([("h0", one(1)), ("h1", one(2))])
    (m,) = merged["metrics"]
    assert m["labelnames"] == ["host", "layout"]
    assert [(s["labels"]["host"], s["value"]) for s in m["samples"]] == \
        [("h0", 1.0), ("h1", 2.0)]
    # merged output renders (host= label on every series, no summing)
    assert 'amp_c_total{host="h0",layout="row"} 1' in \
        prometheus_text(merged)


# ---------------------------------------------------------------------------
# span helpers + codec round-trips
# ---------------------------------------------------------------------------

def test_span_vocabulary_helpers():
    assert expected_spans() == ["admit", "batch_wait", "operands",
                                "compute", "pull", "complete", "results",
                                "drift"]
    # coding nests in the request's results span, before the drift tail
    assert expected_spans(wire=True)[-3:] == ["results", "wire_measure",
                                              "drift"]
    assert expected_spans(cluster=True)[1] == "route"
    spans = [span(n, i, i + 0.5) for i, n in enumerate(expected_spans())]
    assert missing_spans(spans) == []
    assert missing_spans(spans, wire=True) == ["wire_measure"]
    assert missing_spans(None) == expected_spans()
    assert spans_monotonic(spans) and spans_monotonic(None)
    assert not spans_monotonic([span("a", 1.0, 0.5)])        # t1 < t0
    assert not spans_monotonic([span("a", 2.0, 3.0), span("b", 1.0, 4.0)])
    # per-host ordering: interleaved hosts are each monotone on their own
    assert spans_monotonic([span("a", 5.0, 6.0, host="x"),
                            span("b", 1.0, 2.0, host="y"),
                            span("c", 6.0, 7.0, host="x")])
    assert tag_host([["a", None, 0.0, 1.0], ["b", "h", 1.0, 2.0]], "z") == \
        [["a", "z", 0.0, 1.0], ["b", "h", 1.0, 2.0]]
    # a span's counts survive host tagging
    assert tag_host([["d", None, 0.0, 1.0, {"lookups": 2}]], "z") == \
        [["d", "z", 0.0, 1.0, {"lookups": 2}]]


def test_chrome_trace_export():
    spans = [span("admit", 1.0, 1.5, host="frontend"),
             span("compute", 2.0, 2.25)]
    evs = chrome_trace_events(7, spans)
    assert evs[0] == {"name": "admit", "ph": "X", "pid": "frontend",
                      "tid": 7, "ts": 1e6, "dur": 0.5e6, "cat": "amp"}
    assert evs[1]["pid"] == "local"
    fp = io.StringIO()
    import types
    n = write_trace_jsonl(fp, [
        types.SimpleNamespace(request_id=7, spans=spans),
        types.SimpleNamespace(request_id=8, spans=None)])
    assert n == 2
    parsed = [json.loads(l) for l in fp.getvalue().splitlines()]
    assert [e["name"] for e in parsed] == ["admit", "compute"]


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit with its ``perf_counter`` time."""

    def __init__(self):
        self.log = []
        rec = self

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                rec.log.append(("enter", self.name, spans_mod.now()))

            def __exit__(self, *exc):
                rec.log.append(("exit", self.name, spans_mod.now()))
        self.cls = Ann

    def bounds(self, name):
        """(enter, exit) times of each annotation ``name``, in order."""
        ent = [t for k, n, t in self.log if k == "enter" and n == name]
        ext = [t for k, n, t in self.log if k == "exit" and n == name]
        return list(zip(ent, ext))


@pytest.fixture
def annotations(monkeypatch):
    rec = _Annotations()
    monkeypatch.setattr(spans_mod, "TraceAnnotation", rec.cls)
    return rec


def test_phase_span_and_annotation(annotations):
    with phase("pull") as sp:
        with phase("inner"):
            pass
    assert sp[0] == "pull" and sp[1] is None and sp[3] >= sp[2] > 0.0
    # the span lies inside its annotation; the child nests in both
    (pe, px), = annotations.bounds("amp.pull")
    (ie, ix), = annotations.bounds("amp.inner")
    assert pe <= sp[2] <= ie <= ix <= sp[3] <= px
    with phase("off", False) as off:
        pass
    assert off is None
    assert not annotations.bounds("amp.off")


def test_chrome_trace_counts_ride_as_args():
    spans = [span("compute", 2.0, 2.25),
             ["drift", None, 2.25, 2.5, {"lookups": 3, "misses": 1}]]
    evs = chrome_trace_events(4, spans)
    assert "args" not in evs[0]
    assert evs[1]["args"] == {"lookups": 3, "misses": 1}
    assert evs[1]["dur"] == pytest.approx(0.25e6)


def test_codec_span_counts_roundtrip():
    spans = [["admit", None, 1.0, 1.5],
             ["drift", "host0", 2.0, 2.5, {"lookups": 16, "misses": 13}]]
    _, (req,) = make_reqs(1)
    req = dataclasses.replace(req)
    req.spans = [list(s) for s in spans]
    assert decode_request(encode_request(req)).spans == spans
    res = dataclasses.replace(_solved_singleton(),
                              spans=[list(s) for s in spans])
    assert decode_result(encode_result(res)).spans == spans


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(
    st.sampled_from(["admit", "route", "compute"]),
    st.sampled_from([None, "frontend", "host0"]),
    st.floats(0.0, 1e6, allow_nan=False),
    st.floats(0.0, 1e6, allow_nan=False)), max_size=6),
    st.floats(0.0, 10.0, allow_nan=False))
def test_codec_span_and_drift_headers_roundtrip(raw, drift):
    """Spans and se_drift ride codec JSON headers bit-exactly in both
    directions (request and result frames)."""
    spans = [span(n, t0, t1, host=h) for n, h, t0, t1 in raw]
    _, (req,) = make_reqs(1)
    req = dataclasses.replace(req)
    req.spans = [list(s) for s in spans]
    back = decode_request(encode_request(req))
    assert back.spans == spans

    res = dataclasses.replace(_solved_singleton(), se_drift=float(drift),
                              spans=[list(s) for s in spans] or None)
    back = decode_result(encode_result(res))
    assert back.se_drift == float(drift)
    assert back.spans == res.spans


_SINGLETON_CACHE = []


def _solved_singleton():
    if not _SINGLETON_CACHE:
        svc = SolveService(policy=POL, rate_accounting=False)
        _, reqs = make_reqs(1, seed=77)
        _SINGLETON_CACHE.append(svc.solve(reqs)[0])
    return _SINGLETON_CACHE[0]


def test_codec_metrics_frame_roundtrip():
    reg = MetricsRegistry()
    reg.counter("amp_c_total", "c", ("layout",)).inc(2, layout="row")
    reg.histogram("amp_h", buckets=(0.1, 1.0)).observe(0.5)
    snap = reg.snapshot()
    host, back = decode_metrics(encode_metrics("host3", snap))
    assert host == "host3" and back == snap
    # strict frame validation: wrong kind, junk fields, bad payloads
    from repro.serving.codec import CodecError, _pack, _unpack
    _, reqs = make_reqs(1)
    with pytest.raises(CodecError):
        decode_metrics(encode_request(reqs[0]))    # not a metrics frame
    buf = encode_metrics("h", snap)
    header, arrays = _unpack(buf)
    header["extra"] = 1
    with pytest.raises(CodecError):
        decode_metrics(_pack(header, arrays))
    with pytest.raises(CodecError):
        decode_metrics(_pack({"kind": "metrics", "host": "h",
                              "metrics": {"metrics": "nope"}}, {}))


# ---------------------------------------------------------------------------
# spans + drift through the solve service (every dispatch path)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def telem_svc():
    svc = SolveService(policy=POL, rate_accounting=False)
    _, reqs = make_reqs(8)
    return svc, svc.solve(reqs)


def test_batched_path_span_tree(telem_svc):
    svc, results = telem_svc
    for r in results:
        assert r.batch_size == 8
        assert missing_spans(r.spans) == []
        assert span_names(r.spans) == expected_spans()
        assert spans_monotonic(r.spans), r.spans
        # all spans on one (local) host until a cluster tags them
        assert {s[1] for s in r.spans} == {None}
    # the batch-execution spans are shared verbatim across the group
    ops = {tuple(s) for r in results for s in r.spans if s[0] == "operands"}
    assert len(ops) == 1


def test_local_admit_span_is_real(telem_svc):
    """A local request's admit span covers preparing, keying and queueing
    it: it ends after it starts, and batch_wait starts where it ends."""
    _, results = telem_svc
    for r in results:
        adm, wait = r.spans[0], r.spans[1]
        assert adm[0] == "admit" and adm[3] > adm[2]
        assert wait[0] == "batch_wait" and wait[2] == adm[3]


def test_complete_covers_results_and_drift():
    """``complete`` ends after the drift tail, with ``results`` and
    ``drift`` inside it, and the latency histogram ends where it ends."""
    svc = SolveService(policy=POL, rate_accounting=False)
    _, reqs = make_reqs(8, seed=10)
    results = svc.solve(reqs)
    want = 0.0
    for r in results:
        by = {s[0]: s for s in r.spans}
        co, rs, dr = by["complete"], by["results"], by["drift"]
        assert co[2] <= rs[2] <= rs[3] <= dr[2] <= dr[3] <= co[3]
        # pull ends where compute ends, inside it
        assert by["compute"][2] <= by["pull"][2] <= by["pull"][3] \
            == by["compute"][3] <= co[2]
        want += co[3] - by["admit"][2]
    by_name = {m["name"]: m for m in svc.metrics()["metrics"]}
    (lat,) = by_name["amp_request_latency_seconds"]["samples"]
    assert lat["count"] == len(results)
    assert lat["sum"] == pytest.approx(want, rel=1e-9)


def test_drift_counts_lossless_and_bt():
    """A batch of lossless and BT requests: the drift span counts one
    SE-prediction lookup per answer, and no lossless answer misses once
    its operating point has been seen (lossless schedules repeat). The
    registry mirrors the running totals."""
    svc = SolveService(policy=POL, rate_accounting=False)
    _, warm = make_reqs(2, seed=10, policy="lossless")
    warm_res = svc.solve(warm)
    _, lossless = make_reqs(3, seed=20, policy="lossless")
    _, bt = make_reqs(5, seed=30, policy="bt")
    results = svc.solve(lossless + bt)
    drift = {tuple(s[:4]): s[4] for r in results for s in r.spans
             if s[0] == "drift"}
    assert len(drift) == 1              # one tail for the one batch
    (counts,) = drift.values()
    assert counts["lookups"] == len(results)
    assert 0 <= counts["misses"] <= len(bt)
    by_name = {m["name"]: m for m in svc.metrics()["metrics"]}
    got = {s["labels"]["result"]: s["value"] for s in
           by_name["amp_se_prediction_lookups_total"]["samples"]}
    tails = {tuple(s[:4]): s[4] for r in warm_res + results
             for s in r.spans if s[0] == "drift"}.values()
    assert got == {"hit": sum(c["lookups"] - c["misses"] for c in tails),
                   "miss": sum(c["misses"] for c in tails)}


def test_operands_and_compute_keep_their_bounds(annotations, monkeypatch):
    """``operands`` runs from after the engine lookup to the end of the
    operand build (before the engine call is enqueued); ``compute`` from
    there to the end of the pull (device results materialized) — the
    bounds they had before the phases around them were recorded."""
    from repro.core.engine import AmpEngine
    from repro.serving import service as service_mod

    calls = {}

    def timed(owner, name):
        orig = getattr(owner, name)

        def wrapped(*a, **kw):
            t0 = spans_mod.now()
            out = orig(*a, **kw)
            calls.setdefault(name, []).append((t0, spans_mod.now()))
            return out
        monkeypatch.setattr(owner, name, wrapped)

    timed(service_mod.SolveService, "_engine")
    timed(service_mod.SolveService, "_a_batch")
    timed(service_mod.SolveService, "_y_and_params")
    timed(AmpEngine, "dispatch_het")
    timed(AmpEngine, "trace_of")
    svc = SolveService(policy=POL, rate_accounting=False)
    _, reqs = make_reqs(8, seed=50)
    calls.clear()
    results = svc.solve(reqs)
    by = {s[0]: s for s in results[0].spans}
    op, cp, pl = by["operands"], by["compute"], by["pull"]
    assert calls["_engine"][-1][1] <= op[2] <= calls["_a_batch"][0][0]
    assert calls["_y_and_params"][0][1] <= op[3] \
        <= calls["dispatch_het"][0][0]
    assert cp[2] == op[3]
    assert calls["trace_of"][0][1] <= cp[3] == pl[3]
    assert calls["dispatch_het"][0][1] <= pl[2] <= calls["trace_of"][0][0]
    # the annotations carry the same phases on the profiler's clock
    for name in ("admit", "operands", "a_stack", "params", "dispatch",
                 "pull", "complete", "results", "drift"):
        assert annotations.bounds("amp." + name), name
    (oe, ox), = annotations.bounds("amp.operands")
    assert oe <= op[2] <= op[3] <= ox


def test_batched_path_drift_clean(telem_svc):
    """Clean solves (true SNR declared) have well-defined drift and a
    typical value well under the alert line. Per-request bounds are NOT
    asserted here: at n=128/m=64 individual AMP realizations deviate
    from SE heavily (the monitor is advisory for a reason) — the tier-2
    acceptance test pins the clean/mis-rated separation at n=512."""
    _, results = telem_svc
    drifts = [r.se_drift for r in results]
    assert all(d is not None and math.isfinite(d) for d in drifts), drifts
    assert float(np.median(drifts)) < 0.75, drifts


def test_service_metrics_surface(telem_svc):
    svc, results = telem_svc
    snap = svc.metrics()
    by_name = {m["name"]: m for m in snap["metrics"]}
    req_total = sum(s["value"]
                    for s in by_name["amp_requests_total"]["samples"])
    assert req_total >= len(results)
    (lat,) = [s for s in by_name["amp_request_latency_seconds"]["samples"]
              if s["labels"]["layout"] == "row"]
    assert lat["count"] >= len(results)
    assert sum(lat["counts"]) == lat["count"]
    (dr,) = by_name["amp_se_drift"]["samples"]
    assert dr["count"] >= len(results)
    # collector-pulled engine/cache counters are present and consistent
    comp = sum(s["value"]
               for s in by_name["amp_engine_compiles_total"]["samples"])
    assert comp == svc.compile_count() > 0
    assert "amp_operand_cache_hits_total" in by_name
    # the drift tails' MMSE evaluations, by path, total their spans' counts
    evals = {s["labels"]["path"]: s["value"] for s in
             by_name["amp_se_mmse_evaluations_total"]["samples"]}
    tails = {tuple(s[:4]): s[4] for r in results for s in r.spans
             if s[0] == "drift"}.values()
    assert evals == {path: sum(c[path] for c in tails)
                     for path in ("table", "quadrature")}
    text = svc.metrics_text()
    assert "# TYPE amp_request_latency_seconds histogram" in text
    assert "amp_se_drift_bucket" in text


def test_singleton_fast_path_span_tree():
    """The singleton fast path (lone lossless row request) emits the same
    complete span vocabulary as the batched path."""
    svc = SolveService(policy=POL, rate_accounting=False)
    _, (req,) = make_reqs(1, seed=30)
    req = dataclasses.replace(req, policy="lossless", deltas=None)
    svc.submit(req)
    (res,) = svc.flush()
    assert res.batch_size == 1
    assert svc.stats()["singleton_dispatches"] == 1
    assert missing_spans(res.spans) == []
    assert spans_monotonic(res.spans), res.spans
    assert res.se_drift is not None and res.se_drift < DRIFT_ALERT


def test_measure_wire_span_tree():
    """The measured-wire engine twin adds the wire_measure span and keeps
    the tree monotone (the complete span starts after coding ends)."""
    svc = SolveService(policy=POL, rate_accounting=False)
    _, reqs = make_reqs(2, seed=40, measure_wire=True)
    results = svc.solve(reqs)
    for r in results:
        assert r.bytes_on_wire is not None
        assert missing_spans(r.spans, wire=True) == []
        assert span_names(r.spans) == expected_spans(wire=True)
        assert spans_monotonic(r.spans), r.spans


def test_telemetry_off_is_clean(annotations):
    """No spans, no drift, no metrics, and no profiler annotation."""
    svc = SolveService(policy=POL, rate_accounting=False, telemetry=False)
    _, reqs = make_reqs(2, seed=60)
    results = svc.solve(reqs)
    for r in results:
        assert r.spans is None and r.se_drift is None
    assert svc.metrics() == {"metrics": []}
    assert svc.metrics_text() == ""
    assert annotations.log == []


# ---------------------------------------------------------------------------
# cluster: cross-host span trees, metrics aggregation, TCP RTT
# ---------------------------------------------------------------------------

def test_cluster_span_tree_and_merged_metrics():
    prior, reqs = make_reqs(16, seed=100)
    cl = ClusterService(n_hosts=2, policy=POL,
                        router_policy=RouterPolicy(min_replicas=2),
                        rate_accounting=False)
    try:
        results = sorted(cl.solve(reqs), key=lambda r: r.request_id)
        hosts_seen = set()
        for r in results:
            assert missing_spans(r.spans, cluster=True) == []
            # frontend admit/route, then the backend's own full tree
            # (its admit re-stamps on the backend clock)
            assert span_names(r.spans) == \
                ["admit", "route"] + expected_spans()
            assert spans_monotonic(r.spans), r.spans
            # frontend spans tagged "frontend"; backend spans tagged with
            # the routed host (never None after _absorb)
            assert r.spans[0][1] == r.spans[1][1] == "frontend"
            backend_hosts = {s[1] for s in r.spans[2:]}
            assert len(backend_hosts) == 1
            assert backend_hosts < {"host0", "host1"}
            hosts_seen |= backend_hosts
        assert hosts_seen == {"host0", "host1"}
        # merged snapshot: frontend + per-host series under a host label
        snap = cl.metrics()
        by_name = {m["name"]: m for m in snap["metrics"]}
        sub = by_name["amp_cluster_submitted_total"]["samples"]
        assert [(s["labels"]["host"], s["value"]) for s in sub] == \
            [("frontend", float(len(reqs)))]
        lat_hosts = {s["labels"]["host"]
                     for s in by_name["amp_request_latency_seconds"]
                     ["samples"]}
        assert lat_hosts == {"host0", "host1"}
        served = {s["labels"]["host"]: s["value"]
                  for s in by_name["amp_router_served_total"]["samples"]}
        assert served == {"host0": 8.0, "host1": 8.0}
        text = cl.metrics_text()
        assert 'amp_requests_total{host="host0",layout="row"}' in text
    finally:
        cl.close()


def test_tcp_metrics_frame_and_rtt():
    """The b"M" frame pulls a remote host's snapshot over the wire, and
    TcpBackend times every frame kind into its RTT histograms — surfaced
    as amp_tcp_* series on the frontend registry."""
    prior, reqs = make_reqs(16, seed=120)
    server = BackendServer(LocalBackend(
        "host1", SolveService(policy=POL, rate_accounting=False)))
    server.start()
    try:
        tcp = TcpBackend((server.host, server.port), "host1")
        cl = ClusterService(
            backends=[LocalBackend("host0",
                                   SolveService(policy=POL,
                                                rate_accounting=False)),
                      tcp],
            policy=POL, router_policy=RouterPolicy(min_replicas=2))
        results = sorted(cl.solve(reqs), key=lambda r: r.request_id)
        assert len(results) == len(reqs)
        # remote snapshot crossed the wire as a codec frame
        snap = tcp.metrics()
        names = {m["name"] for m in snap["metrics"]}
        assert "amp_requests_total" in names
        # RTT histograms recorded per frame kind, in milliseconds
        rtt = tcp.rtt_stats()
        assert rtt["S"]["count"] >= 8            # submits crossed the wire
        assert rtt["M"]["count"] >= 1
        for s in rtt.values():
            assert 0.0 <= s["p50_ms"] <= s["p95_ms"] <= s["max_ms"]
        assert cl.rtt_stats() == {"host1": rtt}
        # frontend collector folds RTT quantiles into the merged snapshot
        text = cl.metrics_text()
        assert 'amp_tcp_rtt_p95_seconds{host="host1",op="S"}' in text
        assert 'amp_requests_total{host="host1",layout="row"}' in text
        cl.close(shutdown_remote=True)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# SE drift unit + tier-2 acceptance (mis-rated solve flagged)
# ---------------------------------------------------------------------------

def test_se_prediction_memoized():
    from repro.core.state_evolution import CSProblem

    prob = CSProblem(n=512, m=160, prior=BernoulliGauss(eps=0.1),
                     snr_db=20.0)
    ev = np.full(6, 1e-3)
    p1 = se_prediction(prob, 6, ev, n_proc=5)
    p2 = se_prediction(prob, 6, ev + 1e-9, n_proc=5)    # same rounded key
    assert p1 is p2
    p3 = se_prediction(prob, 6, ev * 2.0, n_proc=5)     # real change: miss
    assert p3 is not p1
    # col layout predictions exist and differ from row
    pc = se_prediction(prob, 6, ev, layout="col", n_proc=5)
    assert pc.shape == (6,) and not np.allclose(pc, p1)
    # drift of the prediction against itself is ~0
    d, _ = se_drift(prob, p1, ev, n_proc=5)
    assert d == pytest.approx(0.0, abs=1e-12)
    d_nan, _ = se_drift(prob, np.zeros(6), ev, n_proc=5)
    assert math.isnan(d_nan)


@pytest.mark.tier2
def test_drift_monitor_flags_misrated_solve():
    """Acceptance (ISSUE 9): requests that declare the wrong operating
    point (data generated at 20 dB, declared 40 dB) trip the drift alert;
    the clean half of the same stream passes. Larger instances than the
    span tests (n=512) keep the clean population concentrated well away
    from the alert line, and lossless transport makes the late-iteration
    variance floor purely noise-determined — so the 100x sigma_e2
    mis-declaration shows up at full strength instead of hiding under
    quantization noise."""
    svc = SolveService(policy=POL, rate_accounting=False)
    _, clean = make_reqs(8, n=512, m=256, t=10, seed=200,
                         policy="lossless")
    _, misrated = make_reqs(8, n=512, m=256, t=10, seed=300, snr_db=20.0,
                            declared_snr=40.0, policy="lossless")
    res_clean = svc.solve(clean)
    res_bad = svc.solve(misrated)
    for r in res_clean:
        assert r.se_drift is not None and r.se_drift < DRIFT_ALERT
    for r in res_bad:
        assert r.se_drift is not None and r.se_drift > DRIFT_ALERT, \
            r.se_drift
    by_name = {m["name"]: m for m in svc.metrics()["metrics"]}
    alerts = sum(s["value"]
                 for s in by_name["amp_se_drift_alerts_total"]["samples"])
    assert alerts == len(res_bad)
    # the drift histogram separates the populations: p95 over the mixed
    # stream exceeds what the clean half alone would produce
    (dr,) = by_name["amp_se_drift"]["samples"]
    assert hist_quantile(dr, 0.95) >= DRIFT_ALERT
