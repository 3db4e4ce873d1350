"""Solve-service tests: bucketing, continuous batching, and the ISSUE 2
acceptance criterion — a heterogeneous batch (different SNR, eps, P, and
fixed/DP/BT policies per request) matches single-request solves."""
import jax
import numpy as np
import pytest

from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss
from repro.core.engine import (AmpEngine, BTRateControl, DPSchedule,
                               EcsqTransport, EngineConfig, FixedSchedule,
                               het_params, stack_het_params)
from repro.core.rate_alloc import dp_allocate
from repro.core.rate_distortion import RDModel
from repro.core.state_evolution import CSProblem
from repro.serving import (Batcher, BucketPolicy, SolveRequest, SolveService,
                           bucket_for, pad_batch_size, placement_for)


# ---------------------------------------------------------------------------
# bucketing / batching units
# ---------------------------------------------------------------------------

def test_bucket_rounding():
    pol = BucketPolicy(n_quantum=256, mp_quantum=16, t_quantum=4)
    k = bucket_for(600, 180, 5, 6, "ecsq", pol)
    assert (k.n_pad, k.mp_pad, k.n_proc, k.t_max) == (768, 48, 5, 8)
    assert k.m_pad == 240
    # exact multiples stay unpadded
    k2 = bucket_for(512, 160, 5, 8, "ecsq", pol)
    assert (k2.n_pad, k2.mp_pad, k2.t_max) == (512, 32, 8)
    # P and transport are structural: distinct buckets
    assert bucket_for(512, 160, 5, 8, "ecsq", pol) != \
        bucket_for(512, 160, 10, 8, "ecsq", pol)
    assert bucket_for(512, 160, 5, 8, "block8", pol) != k2
    with pytest.raises(AssertionError):
        bucket_for(512, 161, 5, 8, "ecsq", pol)  # M not divisible by P


def test_pad_batch_size():
    pol = BucketPolicy(max_batch=128)
    assert [pad_batch_size(b, pol) for b in (1, 2, 3, 8, 9, 128)] == \
        [1, 2, 4, 8, 16, 128]


def test_placement_selection():
    """Size-threshold placement (DESIGN.md §6): local off-mesh, data for
    small requests, proc for large ones whose P splits over the devices;
    aspect-ratio layout routing (DESIGN.md §7) rides along."""
    pol = BucketPolicy(shard_elems=1 << 20)
    assert placement_for(512, 160, 4, 1, pol) == ("local", "row")
    assert placement_for(512, 160, 4, 8, pol) == ("data", "row")
    assert placement_for(4096, 1280, 8, 8, pol) == ("proc", "row")
    # P not divisible by the device count: falls back to data-parallel
    assert placement_for(4096, 1280, 6, 8, pol) == ("data", "row")
    # placement is part of the compile-cache key
    k_d = bucket_for(512, 128, 4, 8, "ecsq", pol, "data")
    k_l = bucket_for(512, 128, 4, 8, "ecsq", pol, "local")
    assert k_d != k_l and k_d.placement == "data"
    # default stays "local" so single-device keys are unchanged
    assert bucket_for(512, 128, 4, 8, "ecsq", pol).placement == "local"


def test_placement_routes_tall_n_to_column():
    """Acceptance (ISSUE 4): a tall-N request (N/M >= col_aspect, N*M >=
    shard_elems) routes to the column layout — processor-sharded on a
    mesh, column-partitioned locally off-mesh."""
    pol = BucketPolicy(shard_elems=1 << 20)
    assert pol.col_aspect == 4.0
    # N/M = 8 >= 4 and N*M = 2^21 >= shard_elems: proc placement, col layout
    assert placement_for(4096, 512, 8, 8, pol) == ("proc", "col")
    assert placement_for(4096, 512, 8, 1, pol) == ("local", "col")
    # small tall requests batch data-parallel but stay column-partitioned
    assert placement_for(1024, 128, 4, 8, pol) == ("data", "col")
    # N not divisible by P: the column layout cannot slice evenly -> row
    assert placement_for(4098, 512, 8, 1, pol)[1] == "row"
    # just under the aspect threshold -> row
    assert placement_for(2044, 512, 4, 1, pol)[1] == "row"
    # layout is part of the compile-cache key; column m_pad is the padded
    # full M (rows are shared, not split) and n_pad pads per-slice
    k_c = bucket_for(4096, 500, 8, 8, "ecsq", pol, "local", "col")
    assert k_c.layout == "col"
    assert k_c.m_pad == 512 and k_c.mp_pad == 512   # round_up(500, 256)
    assert k_c.n_pad == 4096                        # slices already padded
    k_r = bucket_for(4096, 512, 8, 8, "ecsq", pol, "local", "row")
    assert k_c != k_r and k_r.m_pad == 512


def test_batcher_dispatch_and_drain():
    pol = BucketPolicy(max_batch=4)
    b = Batcher(pol)
    k1 = bucket_for(512, 160, 5, 8, "ecsq", pol)
    k2 = bucket_for(256, 80, 5, 8, "ecsq", pol)
    # group dispatches exactly at max_batch
    for i in range(3):
        assert b.add(k1, f"a{i}") is None
    assert b.add(k2, "b0") is None
    key, group = b.add(k1, "a3")
    assert key == k1 and group == ["a0", "a1", "a2", "a3"]
    assert len(b) == 1
    rest = list(b.drain())
    assert rest == [(k2, ["b0"])] and len(b) == 0


def test_stack_schedules_padding():
    """Per-request schedules of different lengths stack into one (B, T)
    operand, inf-padded past each request's own T (= its t_active)."""
    lanes = [het_params(s, 4, 8, 8, BernoulliGauss())
             for s in (np.array([0.1, 0.2]), np.array([0.3]))]
    params = stack_het_params(lanes)
    out = params.sched
    assert out.shape == (2, 4) and out.dtype == np.float32
    assert params.t_active.tolist() == [2, 1]
    assert np.allclose(out[0, :2], [0.1, 0.2]) and np.isinf(out[0, 2:]).all()
    assert out[1, 0] == np.float32(0.3) and np.isinf(out[1, 1:]).all()


# ---------------------------------------------------------------------------
# heterogeneous batch correctness (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mixed_ctx():
    """Five requests spanning eps, SNR, P, shapes, T, and all policies,
    with the single-request reference solve for each."""
    specs = [
        # (eps, snr_db, n, m, p, t, policy)
        (0.10, 20.0, 600, 180, 5, 6, "fixed"),
        (0.05, 20.0, 768, 240, 5, 8, "lossless"),
        (0.10, 15.0, 500, 150, 5, 5, "bt"),
        (0.10, 20.0, 600, 180, 5, 6, "dp"),
        (0.05, 20.0, 512, 160, 4, 8, "fixed"),   # aspect 3.2: stays row
    ]
    reqs, refs = [], []
    for i, (eps, snr, n, m, p, t, policy) in enumerate(specs):
        prior = BernoulliGauss(eps=eps)
        prob = CSProblem(n=n, m=m, prior=prior, snr_db=snr)
        s0, a, y = sample_problem(jax.random.PRNGKey(i), n, m, prior,
                                  prob.sigma_e2)
        kw = {}
        if policy == "fixed":
            deltas = np.full(t, 0.05, np.float32)
            deltas[0] = np.inf
            kw["deltas"] = deltas
            ctrl = FixedSchedule(deltas)
        elif policy == "lossless":
            ctrl = FixedSchedule(np.full(t, np.inf, np.float32))
        elif policy == "dp":
            # the RD table for this prior ships in .cache (repo-committed)
            rd = RDModel(prior)
            dp = dp_allocate(prob, p, t, 2.0 * t, rd=rd)
            sched = DPSchedule(dp, rd, p)
            kw["deltas"] = sched.deltas
            ctrl = sched
        else:  # bt — service builds identical tables (same ctor args)
            ctrl = BTRateControl(prob, p, t, 1.005, 6.0, "ecsq")
        reqs.append(SolveRequest(y=y, a=a, prior=prior, snr_db=snr,
                                 n_proc=p, n_iter=t, policy=policy, **kw))
        eng = AmpEngine(prior,
                        EngineConfig(n_proc=p, n_iter=t,
                                     collect_symbols=False),
                        EcsqTransport(), ctrl)
        refs.append((eng.solve(y, a), s0))
    return specs, reqs, refs


def test_heterogeneous_batch_matches_single(mixed_ctx):
    """Acceptance: mixed (SNR, eps, P, policy) batch == single solves to
    <= 1e-5 MSE difference."""
    specs, reqs, refs = mixed_ctx
    svc = SolveService(policy=BucketPolicy(max_batch=8))
    results = svc.solve(reqs)
    assert [r.request_id for r in results] == list(range(len(reqs)))
    for (res, (ref, s0), spec) in zip(results, refs, specs):
        mse_diff = float(np.mean((res.x - ref.x) ** 2))
        assert mse_diff <= 1e-5, (spec, mse_diff)
        # trace agreement on the request's own iteration range
        np.testing.assert_allclose(res.sigma2_hat, ref.sigma2_hat,
                                   rtol=1e-4)
        np.testing.assert_allclose(res.deltas, ref.deltas, rtol=1e-4)


def test_bt_rate_accounting_matches_controller(mixed_ctx):
    """The BT request's realized rates equal the in-graph controller's
    decisions from the reference solve."""
    specs, reqs, refs = mixed_ctx
    svc = SolveService(policy=BucketPolicy(max_batch=8))
    results = svc.solve(reqs)
    i_bt = next(i for i, s in enumerate(specs) if s[-1] == "bt")
    ref, _ = refs[i_bt]
    np.testing.assert_allclose(results[i_bt].rates, ref.rates, atol=5e-3)
    assert np.isfinite(results[i_bt].total_bits)
    # lossless requests report zero tracked bits
    i_ll = next(i for i, s in enumerate(specs) if s[-1] == "lossless")
    assert results[i_ll].total_bits == 0.0
    assert np.isinf(results[i_ll].rates).all()


def test_masked_early_exit_is_exact():
    """A short-T request inside a long-T bucket returns exactly its own
    T-iteration solve (the masked scan freezes, not truncates).

    The 512/128 shape sits exactly at the aspect threshold, so this rides
    the *column* bucket — and pins it against a row-layout reference
    (both are exactly centralized AMP under lossless fusion)."""
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=512, m=128, prior=prior)
    s0, a, y = sample_problem(jax.random.PRNGKey(9), prob.n, prob.m, prior,
                              prob.sigma_e2)
    svc = SolveService(policy=BucketPolicy(max_batch=4, t_quantum=8))
    short = SolveRequest(y=y, a=a, prior=prior, n_proc=4, n_iter=3,
                         policy="lossless")
    long_ = SolveRequest(y=y, a=a, prior=prior, n_proc=4, n_iter=8,
                         policy="lossless")
    res_short, res_long = svc.solve([short, long_])
    # both in one bucket (t_max=8), short frozen after 3 iterations
    assert res_short.bucket == res_long.bucket
    eng = AmpEngine(prior, EngineConfig(n_proc=4, n_iter=3,
                                        collect_symbols=False),
                    EcsqTransport(),
                    FixedSchedule(np.full(3, np.inf)))
    ref3 = eng.solve(y, a)
    assert float(np.mean((res_short.x - ref3.x) ** 2)) <= 1e-10
    assert res_short.sigma2_hat.shape == (3,)
    # and the long one kept iterating (strictly better fit)
    assert float(np.mean((res_long.x - s0) ** 2)) < \
        float(np.mean((res_short.x - s0) ** 2))


def test_block_transport_bucket_matches_single():
    """block8 transport: separate bucket, matches the single-request
    BlockQuantTransport solve, and reports the fixed wire rate."""
    from repro.core.engine import BlockQuantTransport
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=600, m=180, prior=prior)
    s0, a, y = sample_problem(jax.random.PRNGKey(3), prob.n, prob.m, prior,
                              prob.sigma_e2)
    svc = SolveService(policy=BucketPolicy(max_batch=4))
    res, = svc.solve([SolveRequest(y=y, a=a, prior=prior, n_proc=5,
                                   n_iter=6, policy="lossless",
                                   transport="block8")])
    eng = AmpEngine(prior, EngineConfig(n_proc=5, n_iter=6,
                                        collect_symbols=False),
                    BlockQuantTransport(bits=8, block=512),
                    FixedSchedule(np.full(6, np.inf)))
    ref = eng.solve(y, a)
    assert float(np.mean((res.x - ref.x) ** 2)) <= 1e-5
    np.testing.assert_allclose(res.rates, 8.0 + 16.0 / 512)
    assert res.bucket.transport == "block8"
    # rate policies are meaningless under a fixed-width wire: rejected
    with pytest.raises(AssertionError, match="no effect under"):
        svc.solve([SolveRequest(y=y, a=a, prior=prior, n_proc=5, n_iter=6,
                                policy="bt", transport="block8")])


def test_resubmitting_same_request_object():
    """Template reuse: the same SolveRequest object submitted twice yields
    two distinct results (no id aliasing)."""
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=256, m=64, prior=prior)
    _, a, y = sample_problem(jax.random.PRNGKey(4), prob.n, prob.m, prior,
                             prob.sigma_e2)
    svc = SolveService(policy=BucketPolicy(max_batch=4),
                       rate_accounting=False)
    req = SolveRequest(y=y, a=a, prior=prior, n_proc=4, n_iter=4,
                       policy="lossless")
    r1, r2 = svc.solve([req, req])
    assert r1.request_id != r2.request_id
    np.testing.assert_allclose(r1.x, r2.x)


def test_stream_continuous_batching():
    """stream() dispatches full groups eagerly and flushes stragglers."""
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=256, m=64, prior=prior)
    insts = [sample_problem(jax.random.PRNGKey(i), prob.n, prob.m, prior,
                            prob.sigma_e2) for i in range(5)]
    svc = SolveService(policy=BucketPolicy(max_batch=2),
                       rate_accounting=False)
    reqs = [SolveRequest(y=i[2], a=i[1], prior=prior, n_proc=4, n_iter=4,
                         policy="lossless") for i in insts]

    pulled = []

    def feed():
        for i, r in enumerate(reqs):
            pulled.append(i)
            yield r

    # (request_id, requests submitted so far, executed batch width)
    events = [(res.request_id, len(pulled), res.batch_size)
              for res in svc.stream(feed())]
    # ids 0,1 dispatched as a full width-2 group the moment the group
    # filled — before request 2 was even pulled from the input
    assert events[0] == (0, 2, 2) and events[1] == (1, 2, 2)
    assert events[2] == (2, 4, 2) and events[3] == (3, 4, 2)
    # the straggler flushes at end of input as a width-1 batch
    assert events[4] == (4, 5, 1)


def test_solve_preserves_foreign_buffered_results():
    """solve() must not swallow results of earlier submit() calls that its
    flush happens to complete."""
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=256, m=64, prior=prior)
    insts = [sample_problem(jax.random.PRNGKey(i), prob.n, prob.m, prior,
                            prob.sigma_e2) for i in range(2)]
    svc = SolveService(policy=BucketPolicy(max_batch=8),
                       rate_accounting=False)
    early_id = svc.submit(SolveRequest(y=insts[0][2], a=insts[0][1],
                                       prior=prior, n_proc=4, n_iter=4,
                                       policy="lossless"))
    out = svc.solve([SolveRequest(y=insts[1][2], a=insts[1][1], prior=prior,
                                  n_proc=4, n_iter=4, policy="lossless")])
    assert [r.request_id for r in out] == [early_id + 1]
    later = svc.flush()
    assert [r.request_id for r in later] == [early_id]

    # stream() honors the same contract
    early2 = svc.submit(SolveRequest(y=insts[0][2], a=insts[0][1],
                                     prior=prior, n_proc=4, n_iter=4,
                                     policy="lossless"))
    streamed = list(svc.stream([SolveRequest(y=insts[1][2], a=insts[1][1],
                                             prior=prior, n_proc=4,
                                             n_iter=4, policy="lossless")]))
    assert [r.request_id for r in streamed] == [early2 + 1]
    assert [r.request_id for r in svc.flush()] == [early2]


# ---------------------------------------------------------------------------
# demand windows (DESIGN.md §11: the autoscaler's scrape signal)
# ---------------------------------------------------------------------------

def test_batcher_demand_windows_partition_the_stream():
    pol = BucketPolicy(max_batch=4)
    b = Batcher(pol)
    k1 = bucket_for(512, 160, 5, 8, "ecsq", pol)
    k2 = bucket_for(256, 80, 5, 8, "ecsq", pol)
    for i in range(5):
        b.add(k1, i)
    b.add(k2, "x")
    # lifetime counts survive dispatch/drain (5 admissions dispatched one
    # full group already)
    assert b.demand() == {k1: 5, k2: 1}
    # first take returns everything, second only the delta, zero-delta
    # buckets are omitted
    assert b.take_demand() == {k1: 5, k2: 1}
    assert b.take_demand() == {}
    b.add(k1, 5)
    assert b.take_demand() == {k1: 1}
    # successive windows partition the stream: sum == lifetime
    assert b.demand() == {k1: 6, k2: 1}


def test_batcher_clear_demand_semantics():
    pol = BucketPolicy(max_batch=4)
    b = Batcher(pol)
    k = bucket_for(512, 160, 5, 8, "ecsq", pol)
    for i in range(3):
        b.add(k, i)
    # mark-only clear: window restarts, history stays
    b.clear_demand()
    assert b.take_demand() == {}
    assert b.demand() == {k: 3}
    b.add(k, 3)
    assert b.take_demand() == {k: 1}
    # lifetime clear: both restart
    b.clear_demand(lifetime=True)
    assert b.demand() == {} and b.take_demand() == {}
    b.add(k, 4)
    assert b.demand() == {k: 1} and b.take_demand() == {k: 1}


def test_batcher_demand_concurrent_admission():
    """Admissions racing a scrape thread: every request lands in exactly
    one take window (no double- or under-counting across takes)."""
    import threading

    pol = BucketPolicy(max_batch=1 << 30)   # no dispatch, pure counting
    b = Batcher(pol)
    k = bucket_for(512, 160, 5, 8, "ecsq", pol)
    n_threads, per_thread = 8, 500
    taken = []
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            taken.append(b.take_demand())
        taken.append(b.take_demand())        # final sweep

    def admit():
        for i in range(per_thread):
            b.add(k, i)

    scr = threading.Thread(target=scraper)
    scr.start()
    workers = [threading.Thread(target=admit) for _ in range(n_threads)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    stop.set()
    scr.join()
    total = sum(d.get(k, 0) for d in taken)
    assert total == n_threads * per_thread
    assert b.demand() == {k: n_threads * per_thread}


def test_stats_consistent_under_background_prewarm():
    """stats() snapshots under the service lock via atomic engine
    counters: while a background prewarm thread compiles the menu and
    mutates the engine maps, every stats() read must be internally
    consistent (compiles.total equals the sum of its own by_bucket
    entries — never a torn count)."""
    from repro.serving import PrewarmSpec

    prior = BernoulliGauss(eps=0.1)
    svc = SolveService(policy=BucketPolicy(max_batch=8),
                       rate_accounting=False)
    menu = [PrewarmSpec(n=128, m=64, n_proc=4, n_iter=t, policy="fixed",
                        prior=prior, batch_widths=(1, 2))
            for t in (4, 8, 12)]
    th = svc.prewarm(menu, background=True)
    while th.is_alive():
        st = svc.stats()
        assert st["compiles"]["total"] == sum(st["compiles"]["by_bucket"]
                                              .values())
        assert st["dispatches"]["total"] == sum(st["dispatches"]
                                                ["by_bucket"].values())
    th.join()
    st = svc.stats()
    assert st["compiles"]["total"] == svc.compile_count() > 0
