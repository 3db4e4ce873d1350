"""The service's phases in the benchmark: the idle split by ``amp.*``
span, the readers of the result-tail spans, and the reduction that the
accepted device metrics read, pinned to its recorded output."""
import json
import os
import types

import pytest

import _paths
import _tiny
import harness
import phase_trace
import phases
import trace_reduce

DATA = os.path.join(_paths.BENCH, "tests", "data")
EVENTS = json.load(open(os.path.join(DATA, "trace_v5e.json")))
READERS = ("drift_ms.backlog", "results_ms.backlog",
           "drift_hit_share.backlog")


@pytest.mark.parametrize("key", ["col", "row"])
def test_reduce_output_is_unchanged(key):
    """``reduce`` on the recorded v5e trace gives exactly the keys and
    values it gave when the accepted metrics were defined."""
    want = json.load(open(os.path.join(DATA, "reduce_v5e.json")))[key]
    got = trace_reduce.reduce(EVENTS[key], harness.KERNELS)
    assert json.loads(json.dumps(got)) == want


@pytest.mark.parametrize("key", ["col", "row"])
def test_idle_split_falls_back_to_the_bench_spans(key):
    """A trace with no ``amp.*`` spans splits its idle time exactly as
    ``reduce``'s breakdown does."""
    red = trace_reduce.reduce(EVENTS[key], harness.KERNELS)
    split = phases.idle_by_phase(EVENTS[key])
    assert split == pytest.approx(dict(red["breakdown"]["idle_gaps"]))
    assert sum(split.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    assert phases.tail_idle_s(EVENTS[key]) == 0.0
    assert phases.batches(EVENTS[key]) == 0


def _ev(name, t0, t1, plane="/host:CPU", line="python"):
    return {"plane": plane, "line": line, "name": name, "t0": float(t0),
            "dur": float(t1 - t0), "stats": {}}


def _op(t0, t1):
    return _ev("%fusion.1 = f32[8] fusion()", t0, t1,
               plane="/device:TPU:0", line=trace_reduce.OPS_LINE)


SYNTH = [
    _ev("bench.window", 0, 110),
    _op(0, 10), _op(40, 50), _op(70, 80), _op(95, 100),
    _ev("bench.poll", 5, 97),
    # gap [10, 40): the end of a pull, then complete with its results
    # and drift children
    _ev("amp.pull", 4, 12), _ev("amp.complete", 12, 42),
    _ev("amp.results", 12, 14), _ev("amp.drift", 14, 36),
    # gap [50, 70): an admit inside the harness's submit, then the poll
    _ev("bench.submit", 49, 60), _ev("amp.admit", 52, 58),
    # gap [80, 95): only the harness's poll; [100, 110): no span at all
]


def test_idle_goes_under_the_innermost_phase():
    split = phases.idle_by_phase(SYNTH)
    assert split == pytest.approx({
        "amp.drift": 22e-9, "amp.complete": 4e-9, "amp.results": 2e-9,
        "amp.pull": 2e-9, "amp.admit": 6e-9,
        "bench.submit": 2e-9,            # [50, 52)
        "bench.poll": 12e-9 + 15e-9,     # [58, 70) mostly in it, [80, 95)
        phases.NO_PHASE: 10e-9})
    assert list(split)[0] == "bench.poll"       # largest first
    assert sum(split.values()) == pytest.approx(75e-9)
    assert phases.tail_idle_s(SYNTH) == pytest.approx(28e-9)
    assert phases.window_s(SYNTH) == pytest.approx(110e-9)
    assert phases.batches(SYNTH) == 1
    out = phase_trace.split(SYNTH)
    assert out["amp_share"] == pytest.approx(36.0 / 75.0)
    assert out["idle_ms_per_batch"]["amp.drift"] == pytest.approx(22e-6)
    assert out["tail_idle_share"] == pytest.approx(100.0 * 28.0 / 110.0)


def _ctx(spans_by_id):
    results = {i: types.SimpleNamespace(spans=sp)
               for i, sp in spans_by_id.items()}
    return {"ids": sorted(results),
            "log": types.SimpleNamespace(results=results)}


def test_span_counts_count_a_shared_span_once():
    shared = ["drift", None, 1.0, 2.0, {"lookups": 4, "misses": 3}]
    other = ["drift", None, 3.0, 4.0, {"lookups": 4, "misses": 1}]
    ctx = _ctx({0: [shared], 1: [shared], 2: [other], 3: None})
    assert phases.span_counts(ctx, "drift") == [shared[4], other[4]]
    assert harness.load_reader(_paths.ROOT, "drift_hit_share.backlog")(
        ctx) == pytest.approx(100.0 * 4 / 8)


def test_readers_read_nothing_from_a_program_without_the_spans():
    """A program that records the older span vocabulary, four elements
    each: every new reader returns None and none raises."""
    old = [["admit", None, 0.0, 0.0], ["batch_wait", None, 0.0, 1.0],
           ["operands", None, 1.0, 2.0], ["compute", None, 2.0, 3.0],
           ["complete", None, 3.0, 4.0]]
    ctx = _ctx({0: old, 1: old})
    for name in READERS:
        assert harness.load_reader(_paths.ROOT, name)(ctx) is None, name


@pytest.mark.parametrize("cell", ["row_paper.bt_backlog",
                                  "col_paper.bt_backlog"])
def test_result_tail_metrics_in_a_tiny_traced_run(cell):
    res = _tiny.run(cell, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["drift_ms.backlog"]["value"] > 0.0
    assert m["results_ms.backlog"]["value"] > 0.0
    assert 0.0 <= m["drift_hit_share.backlog"]["value"] <= 100.0
    assert m["drift_hit_share.backlog"]["unit"] == "%"
    # no device on the CPU: the device metrics are left out
    assert "device_idle_share.backlog" not in m


def test_extract_keeps_the_service_phases(tmp_path):
    """The service's ``amp.*`` annotations land in the profiler's host
    trace, where ``extract`` keeps them."""
    import jax

    from repro.core.denoisers import BernoulliGauss
    from repro.serving import BucketPolicy, SolveRequest, SolveService

    rng = jax.random.PRNGKey(0)
    a = jax.random.normal(rng, (32, 64)) / 8.0
    reqs = [SolveRequest(y=a @ jax.random.normal(jax.random.PRNGKey(i), (64,)),
                         a=a, prior=BernoulliGauss(eps=0.1), n_proc=2,
                         n_iter=4, policy="lossless") for i in range(2)]
    svc = SolveService(policy=BucketPolicy(max_batch=2, n_quantum=64,
                                           mp_quantum=8),
                       rate_accounting=False)
    svc.solve(reqs)                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.solve(reqs)
    finally:
        jax.profiler.stop_trace()
    names = {e["name"] for e in
             phases.extract(trace_reduce.trace_file(str(tmp_path)))}
    assert {"amp.admit", "amp.operands", "amp.a_stack", "amp.params",
            "amp.dispatch", "amp.pull", "amp.complete", "amp.results",
            "amp.drift"} <= names
