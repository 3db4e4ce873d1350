"""Column-layout (C-MP-AMP) engine tests — ISSUE 4 acceptance criteria.

The layout-parity pin rests on an exact identity: at ``n_inner == 1`` the
fused boundary Onsager carry makes column-partitioned C-MP-AMP with exact
fusion *identical* to centralized AMP (``ColumnPartition`` docstring), so
the column code path — column splits, residual fusion, boundary carry,
per-slice einsums — must reproduce the single-processor
``AmpEngine.solve`` to float-reassociation accuracy.
"""
import jax
import numpy as np
import pytest

from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss, make_mmse_interp
from repro.core.engine import (AmpEngine, BlockQuantTransport,
                               ColDPSchedule, ColumnBTRateControl,
                               ColumnPartition, EcsqTransport, EngineConfig,
                               ExactFusion, FixedSchedule, col_bt_delta_for,
                               het_params, split_problem_cols,
                               stack_het_params)
from repro.core.rate_alloc import col_sigma_q2_for_rate, dp_allocate_col
from repro.core.state_evolution import CSProblem, se_trajectory_col
from repro.serving import BucketPolicy, SolveRequest, SolveService


@pytest.fixture(scope="module")
def golden_point():
    """The paper's Sec. 4 operating point (kappa=0.3, 20dB, eps=0.05)."""
    prior = BernoulliGauss(eps=0.05)
    prob = CSProblem(n=2000, m=600, prior=prior, snr_db=20.0)
    s0, a, y = sample_problem(jax.random.PRNGKey(0), prob.n, prob.m, prior,
                              prob.sigma_e2)
    return prob, s0, a, y


def _col_engine(prior, p, t, transport=None, controller=None, n_inner=1,
                **cfg_kw):
    return AmpEngine(
        prior,
        EngineConfig(n_proc=p, n_iter=t, collect_symbols=False,
                     layout=ColumnPartition(n_inner=n_inner), **cfg_kw),
        transport if transport is not None else ExactFusion(),
        controller)


def test_column_exact_matches_single_processor_solve(golden_point):
    """Acceptance: column-layout exact transport == single-processor
    ``solve`` to <= 1e-10 MSE at the golden operating point."""
    prob, s0, a, y = golden_point
    t = 10
    ref = AmpEngine(prob.prior,
                    EngineConfig(n_proc=1, n_iter=t,
                                 collect_symbols=False)).solve(y, a)
    for p in (4, 8):
        col = _col_engine(prob.prior, p, t).solve(y, a)
        d = float(np.mean((col.x - ref.x) ** 2))
        assert d <= 1e-10, (p, d)
        np.testing.assert_allclose(col.sigma2_hat, ref.sigma2_hat,
                                   rtol=1e-5)
        # and it actually recovers the signal
        assert float(col.mse(s0)[-1]) < 5e-4


def test_column_quantized_envelope(golden_point):
    """ECSQ on the exchanged residuals: noise accounting reports exactly
    P * Delta^2 / 12 per round and quality degrades gracefully."""
    prob, s0, a, y = golden_point
    t, p = 10, 4
    exact = _col_engine(prob.prior, p, t).solve(y, a)
    deltas = np.full(t, 0.02, np.float32)
    deltas[0] = np.inf   # round 0 exchanges zeros: conventionally lossless
    q = _col_engine(prob.prior, p, t, EcsqTransport(),
                    FixedSchedule(deltas)).solve(y, a)
    np.testing.assert_allclose(q.extra_var[1:], p * 0.02**2 / 12.0,
                               rtol=1e-6)
    assert q.extra_var[0] == 0.0
    mse_e, mse_q = float(exact.mse(s0)[-1]), float(q.mse(s0)[-1])
    assert mse_q < 1.5 * mse_e, (mse_q, mse_e)
    # coarse bins must visibly hurt (the accounting has teeth)
    coarse = np.full(t, 0.2, np.float32)
    coarse[0] = np.inf
    qc = _col_engine(prob.prior, p, t, EcsqTransport(),
                     FixedSchedule(coarse)).solve(y, a)
    assert float(qc.mse(s0)[-1]) > 2.0 * mse_e


def test_column_block_transport(golden_point):
    """int8 block quantization of the residual exchange: near-exact
    quality; zero contributions (round 0) inject zero noise."""
    prob, s0, a, y = golden_point
    t, p = 10, 4
    exact = _col_engine(prob.prior, p, t).solve(y, a)
    b8 = _col_engine(prob.prior, p, t,
                     BlockQuantTransport(bits=8, block=512)).solve(y, a)
    assert b8.extra_var[0] == 0.0
    assert np.all(b8.extra_var[1:] > 0)
    assert float(b8.mse(s0)[-1]) < 1.3 * float(exact.mse(s0)[-1])


def test_column_multi_inner_rounds(golden_point):
    """n_inner > 1 (the communication-saving regime): 5 rounds x 2 inner
    iterations converge close to 10 lossless fused rounds while fusing
    half as often."""
    prob, s0, a, y = golden_point
    ref = _col_engine(prob.prior, 4, 10).solve(y, a)
    two = _col_engine(prob.prior, 4, 5, n_inner=2).solve(y, a)
    mse_ref, mse_two = float(ref.mse(s0)[-1]), float(two.mse(s0)[-1])
    assert mse_two < 3.0 * mse_ref, (mse_two, mse_ref)
    # per-round progress is monotone
    assert np.all(np.diff(two.mse(s0)) < 0)


def test_column_bt_controller(golden_point):
    """In-graph column BT: round 0 is free, later rounds spend finite
    rates bounded by r_max, and the quantized trajectory tracks the
    lossless one within the c_ratio discipline's intent."""
    prob, s0, a, y = golden_point
    t, p = 10, 4
    mm = make_mmse_interp(prob.prior)
    ctrl = ColumnBTRateControl(prob, p, t, c_ratio=1.05, r_max=6.0,
                               mmse_fn=mm)
    tr = _col_engine(prob.prior, p, t, EcsqTransport(), ctrl).solve(y, a)
    assert np.isinf(tr.deltas[0]) and tr.rates[0] == 0.0
    assert np.all(np.isfinite(tr.deltas[1:]))
    assert np.all(tr.rates[1:] <= 6.0 + 1e-6)
    assert np.all(tr.rates[1:] > 0)
    exact = _col_engine(prob.prior, p, t).solve(y, a)
    assert float(tr.mse(s0)[-1]) < 1.5 * float(exact.mse(s0)[-1])

    # the pure decision function agrees with a host-side re-evaluation of
    # the same rule: base + P*sigma_Q^2 <= target, admissible bin closed
    # form (quantization noise lands additively on the fused residual)
    tb = ctrl.tables
    for s, v in ((3, float(tb.targets[3]) / 1.05), (5, 0.01)):
        delta, rate = col_bt_delta_for(tb, s, np.float32(v))
        d_blk = float(np.interp(np.log(v), tb.log_v, tb.log_m))
        d_blk = float(np.exp(d_blk))
        base = prob.sigma_e2 + d_blk / prob.kappa
        target = float(tb.targets[s])
        v_r = (prob.prior.second_moment - d_blk) / (prob.kappa * p)
        sq2_adm = max(target - base, 0.0) / p
        sq2_cap = (2.0 ** float(tb.u_cap)) ** 2 * v_r / 12.0
        sq2 = min(max(sq2_adm, sq2_cap), v_r)
        assert abs(float(delta) - np.sqrt(12.0 * sq2)) < 1e-3 * float(delta)


def test_dp_allocate_col():
    """Column DP: budget respected, more budget -> no worse final MSE,
    and the realized ColDPSchedule starts lossless."""
    prob = CSProblem(n=2000, m=600, prior=BernoulliGauss(eps=0.05),
                     snr_db=20.0)
    mm = make_mmse_interp(prob.prior)
    t, p = 8, 4
    dp_lo = dp_allocate_col(prob, p, t, r_total=7.0, mmse_fn=mm)
    dp_hi = dp_allocate_col(prob, p, t, r_total=28.0, mmse_fn=mm)
    for dp, budget in ((dp_lo, 7.0), (dp_hi, 28.0)):
        assert dp.rates[0] == 0.0
        assert dp.rates.sum() <= budget + 1e-9
        assert np.all(np.diff(dp.sigma2_d) <= 1e-12)   # block MSE decreases
    assert dp_hi.sigma2_d[-1] <= dp_lo.sigma2_d[-1]
    sched = ColDPSchedule(dp_hi, prob, p)
    assert np.isinf(sched.deltas[0])
    assert np.all(np.isfinite(sched.deltas[1:]))
    # rate -> distortion model is monotone and capped at the source var
    sq2 = col_sigma_q2_for_rate(np.array([0.0, 1.0, 4.0]), 1e-3, prob, p)
    assert sq2[0] >= sq2[1] >= sq2[2]


def test_column_se_properties():
    """Two-stage column SE: lossless decreasing, quantization dominates
    clean, vanishing noise recovers it, and n_inner=1 lossless equals the
    centralized recursion."""
    from repro.core.state_evolution import se_trajectory
    prob = CSProblem(n=2000, m=600, prior=BernoulliGauss(eps=0.05),
                     snr_db=20.0)
    mm = make_mmse_interp(prob.prior)
    tau, d = se_trajectory_col(prob, 4, 10, 1, mmse_fn=mm)
    assert np.all(np.diff(d) <= 1e-12)
    # n_inner=1 lossless column SE == centralized SE (same recursion)
    cen = se_trajectory(prob, 10, mmse_fn=mm)
    np.testing.assert_allclose(tau, cen[:-1], rtol=1e-9)
    sq2 = np.full(10, 1e-4)
    sq2[0] = 0.0
    tau_q, d_q = se_trajectory_col(prob, 4, 10, 1, sigma_q2=sq2, mmse_fn=mm)
    assert np.all(d_q >= d - 1e-15)
    tau_t, d_t = se_trajectory_col(prob, 4, 10, 1, sigma_q2=sq2 * 1e-9,
                                   mmse_fn=mm)
    np.testing.assert_allclose(d_t, d, rtol=1e-6)
    # more inner iterations per round -> no worse end point per round
    _, d2 = se_trajectory_col(prob, 4, 10, 2, mmse_fn=mm)
    assert np.all(d2 <= d + 1e-15)


def test_column_solve_many_matches_solve(golden_point):
    """vmap-batched column solves match per-instance column solves."""
    prob, _, a, _ = golden_point
    prior = prob.prior
    t, p, b = 6, 4, 3
    insts = [sample_problem(jax.random.PRNGKey(i + 1), prob.n, prob.m,
                            prior, prob.sigma_e2) for i in range(b)]
    ys = np.stack([i[2] for i in insts])
    a_mats = np.stack([i[1] for i in insts])
    eng = _col_engine(prior, p, t)
    batch = eng.solve_many(ys, a_mats)
    for i in range(b):
        single = _col_engine(prior, p, t).solve(ys[i], a_mats[i])
        np.testing.assert_allclose(batch.x[i], single.x, atol=5e-5)
    shared = eng.solve_many(ys, a_mats[0])
    single0 = _col_engine(prior, p, t).solve(ys[0], a_mats[0])
    np.testing.assert_allclose(shared.x[0], single0.x, atol=5e-5)


def test_serving_column_bucket_matches_single(golden_point):
    """A tall request through the service (auto-routed to a column
    bucket, padded columns/rows/rounds) == the direct column engine
    solve; mixed row+column streams batch side by side."""
    prior = BernoulliGauss(eps=0.02)
    n, m, p, t = 2048, 256, 8, 8   # aspect 8: column layout
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=20.0)
    s0, a, y = sample_problem(jax.random.PRNGKey(7), n, m, prior,
                              prob.sigma_e2)
    svc = SolveService(policy=BucketPolicy(max_batch=8))
    row_req = SolveRequest(y=y[:160], a=np.asarray(a)[:160, :512],
                           prior=prior, n_proc=4, n_iter=6,
                           policy="lossless")   # aspect 3.2: row bucket
    col_req = SolveRequest(y=y, a=a, prior=prior, n_proc=p, n_iter=t,
                           policy="lossless")
    col_bt = SolveRequest(y=y, a=a, prior=prior, n_proc=p, n_iter=t,
                          policy="bt")
    res = svc.solve([col_req, row_req, col_bt])
    assert res[0].bucket.layout == "col"
    assert res[1].bucket.layout == "row"
    assert res[2].bucket.layout == "col"

    ref = _col_engine(prior, p, t).solve(y, a)
    d = float(np.mean((res[0].x - ref.x) ** 2))
    assert d <= 1e-10, d
    np.testing.assert_allclose(res[0].sigma2_hat, ref.sigma2_hat, rtol=1e-4)

    ctrl = ColumnBTRateControl(prob, p, t, 1.005, 6.0)
    ref_bt = _col_engine(prior, p, t, EcsqTransport(), ctrl).solve(y, a)
    d_bt = float(np.mean((res[2].x - ref_bt.x) ** 2))
    assert d_bt <= 1e-8, d_bt
    np.testing.assert_allclose(res[2].rates, ref_bt.rates, atol=5e-3)
    assert res[2].tracked and np.isfinite(res[2].total_bits)
    # recovery quality sanity on the tall problem
    assert float(np.mean((res[0].x - s0) ** 2)) < 5e-4


def test_column_het_padding_is_exact(golden_point):
    """Direct het call with padded columns (per-slice), padded rows and a
    frozen tail: instance results equal the unpadded single solves."""
    prob, _, a, y = golden_point
    prior = prob.prior
    p, t_max = 4, 12
    m_pad, np_pad = 640, 512
    s1, a1, y1 = sample_problem(jax.random.PRNGKey(3), 1800, 560, prior,
                                prob.sigma_e2)
    a_b = np.zeros((2, p, m_pad, np_pad), np.float32)
    y_b = np.zeros((2, m_pad), np.float32)
    a_b[0, :, :600, :500] = split_problem_cols(np.asarray(a, np.float32), p)
    y_b[0, :600] = y
    a_b[1, :, :560, :450] = split_problem_cols(np.asarray(a1, np.float32),
                                               p)
    y_b[1, :560] = y1
    params = stack_het_params([
        het_params(np.full(10, np.inf, np.float32), t_max, 600, 2000, prior,
                   col=True),
        het_params(np.concatenate([[np.inf], np.full(7, 0.02)]), t_max,
                   560, 1800, prior, col=True)])
    eng = _col_engine(prior, p, t_max, EcsqTransport(), collect_xs=False)
    tr = eng.solve_het(a_b, y_b, params)

    ref0 = _col_engine(prior, p, 10).solve(y, a)
    x0 = tr.x[0].reshape(p, np_pad)[:, :500].reshape(-1)
    assert float(np.mean((x0 - ref0.x) ** 2)) <= 1e-10
    deltas1 = np.concatenate([[np.inf], np.full(7, 0.02)]).astype(np.float32)
    ref1 = _col_engine(prior, p, 8, EcsqTransport(),
                       FixedSchedule(deltas1)).solve(y1, a1)
    x1 = tr.x[1].reshape(p, np_pad)[:, :450].reshape(-1)
    assert float(np.mean((x1 - ref1.x) ** 2)) <= 1e-8
    np.testing.assert_allclose(tr.sigma2_hat[1][:8], ref1.sigma2_hat,
                               rtol=1e-4)
    assert np.all(tr.sigma2_hat[1][8:] == 0.0)   # frozen tail masked out


def test_auto_layout_does_not_mutate_request_template():
    """Auto layout routing is pinned on the service's copy, not on the
    caller's request object — the same layout=None template submitted to
    services with different aspect policies routes per-policy."""
    prior = BernoulliGauss(eps=0.05)
    prob = CSProblem(n=1024, m=256, prior=prior, snr_db=20.0)
    _, a, y = sample_problem(jax.random.PRNGKey(2), prob.n, prob.m, prior,
                             prob.sigma_e2)
    req = SolveRequest(y=y, a=a, prior=prior, n_proc=4, n_iter=4,
                       policy="lossless")
    svc_col = SolveService(policy=BucketPolicy(max_batch=4))
    r_col, = svc_col.solve([req])
    assert r_col.bucket.layout == "col"          # aspect 4 >= default 4.0
    assert req.layout is None                    # template untouched
    svc_row = SolveService(policy=BucketPolicy(max_batch=4,
                                               col_aspect=16.0))
    r_row, = svc_row.solve([req])
    assert r_row.bucket.layout == "row"
    np.testing.assert_allclose(r_col.x, r_row.x, atol=5e-5)


def test_column_rate_accounting_round_indexing():
    """Realized rates for column fixed/DP schedules: round 0 counts 0.0
    bits (zero contributions), round 1 models the payload built from the
    *post-round-0* estimate — a one-round-stale readoff would collapse
    the round-1 residual variance to ~0 and report ~0 bits."""
    prior = BernoulliGauss(eps=0.05)
    n, m, p, t = 2048, 512, 8, 6
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=20.0)
    _, a, y = sample_problem(jax.random.PRNGKey(4), n, m, prior,
                             prob.sigma_e2)
    svc = SolveService(policy=BucketPolicy(max_batch=4))
    deltas = np.concatenate([[np.inf],
                             np.full(t - 1, 0.02)]).astype(np.float32)
    res, = svc.solve([SolveRequest(y=y, a=a, prior=prior, n_proc=p,
                                   n_iter=t, policy="fixed",
                                   deltas=deltas)])
    assert res.bucket.layout == "col"
    assert res.rates[0] == 0.0
    assert np.all(np.isfinite(res.rates))
    # the first real exchange is the largest payload: several bits, and
    # rates stay within the same order across rounds (no collapse)
    assert res.rates[1] > 1.0, res.rates
    assert res.tracked and res.total_bits == res.rates[1:].sum()
    # fully lossless column requests stay untracked (no spurious 0.0)
    res_ll, = svc.solve([SolveRequest(y=y, a=a, prior=prior, n_proc=p,
                                      n_iter=t, policy="lossless")])
    assert not res_ll.tracked and np.isinf(res_ll.rates).all()


@pytest.mark.parametrize("p,n_inner", [(4, 1), (4, 2), (8, 2)])
def test_column_kernel_interpret_matches_einsum(golden_point, p, n_inner):
    """ISSUE 5 acceptance: column solves with ``use_kernel`` +
    ``kernel_interpret=True`` (fused residual + fused inner-step Pallas
    kernels, M tile-padded) match the einsum reference to <= 1e-6 MSE on
    the parity grid — exact fusion and ECSQ, n_inner 1 and 2."""
    prob, s0, a, y = golden_point
    t = 6
    deltas = np.full(t, 0.02, np.float32)
    deltas[0] = np.inf
    for transport, ctrl in ((ExactFusion(), None),
                            (EcsqTransport(), FixedSchedule(deltas))):
        ref = _col_engine(prob.prior, p, t, transport, ctrl,
                          n_inner=n_inner).solve(y, a)
        pal = _col_engine(prob.prior, p, t, transport, ctrl,
                          n_inner=n_inner, use_kernel=True,
                          kernel_interpret=True).solve(y, a)
        d = float(np.mean((pal.x - ref.x) ** 2))
        assert d <= 1e-6, (type(transport).__name__, d)
        np.testing.assert_allclose(pal.sigma2_hat, ref.sigma2_hat,
                                   rtol=1e-4)
        np.testing.assert_allclose(pal.extra_var, ref.extra_var, rtol=1e-5)


def test_column_het_kernel_interpret_matches_ref(golden_point):
    """The heterogeneous column path (padded columns via ``n_mask``,
    padded rows, frozen tail) through the kernel suite == the einsum het
    solve — the mask rides into the fused kernel's in-kernel denoise."""
    prob, _, a, y = golden_point
    prior = prob.prior
    p, t_max = 4, 8
    m_pad, np_pad = 640, 512
    a_b = np.zeros((1, p, m_pad, np_pad), np.float32)
    y_b = np.zeros((1, m_pad), np.float32)
    a_b[0, :, :600, :500] = split_problem_cols(np.asarray(a, np.float32), p)
    y_b[0, :600] = y
    params = stack_het_params([het_params(np.full(6, np.inf, np.float32),
                                          t_max, 600, 2000, prior,
                                          col=True)])
    ref = _col_engine(prior, p, t_max, EcsqTransport(),
                      collect_xs=False).solve_het(a_b, y_b, params)
    pal = _col_engine(prior, p, t_max, EcsqTransport(), collect_xs=False,
                      use_kernel=True,
                      kernel_interpret=True).solve_het(a_b, y_b, params)
    assert pal.x.shape == ref.x.shape     # bucket shapes preserved
    d = float(np.mean((pal.x - ref.x) ** 2))
    assert d <= 1e-6, d
    np.testing.assert_allclose(pal.sigma2_hat, ref.sigma2_hat, rtol=1e-4)


def test_column_rejects_row_controller(golden_point):
    """A row-wise BT controller predicts through the wrong SE: refused."""
    from repro.core.engine import BTRateControl
    prob, _, a, y = golden_point
    ctrl = BTRateControl(prob, 4, 8, 1.005, 6.0, "ecsq")
    eng = _col_engine(prob.prior, 4, 8, EcsqTransport(), ctrl)
    with pytest.raises(AssertionError, match="ColumnBTRateControl"):
        eng.solve(y, a)


def test_service_col_proc_placement_matches_local(multidev):
    """A tall request big enough for processor sharding: the column mesh
    placement (column blocks across devices, het path) must reproduce the
    local column bucket exactly (ISSUE 4 acceptance: tall-N requests with
    N*M >= shard_elems route to ('proc', 'col'))."""
    multidev("""
import jax, numpy as np
from repro.compat import make_mesh
from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss
from repro.core.state_evolution import CSProblem
from repro.serving import BucketPolicy, SolveRequest, SolveService

prior = BernoulliGauss(eps=0.02)
prob = CSProblem(n=4096, m=512, prior=prior, snr_db=20.0)
s0, a, y = sample_problem(jax.random.PRNGKey(5), prob.n, prob.m, prior,
                          prob.sigma_e2)
mesh = make_mesh((8,), ('data',))

svc_proc = SolveService(policy=BucketPolicy(shard_elems=1), mesh=mesh)
svc_loc = SolveService(policy=BucketPolicy())
req = lambda policy: SolveRequest(y=y, a=a, prior=prior, snr_db=20.0,
                                  n_proc=8, n_iter=7, policy=policy)
for policy in ('lossless', 'bt'):
    rp, = svc_proc.solve([req(policy)])
    rl, = svc_loc.solve([req(policy)])
    assert rp.bucket.placement == 'proc' and rp.bucket.layout == 'col'
    assert rl.bucket.placement == 'local' and rl.bucket.layout == 'col'
    d = float(np.mean((rp.x - rl.x) ** 2))
    if policy == 'lossless':
        assert d <= 1e-12, d
        np.testing.assert_allclose(rp.sigma2_hat, rl.sigma2_hat, rtol=1e-5)
    else:
        # BT decisions are discontinuous in the plug-in: behavioral compare
        mse_p = float(np.mean((rp.x - s0) ** 2))
        mse_l = float(np.mean((rl.x - s0) ** 2))
        assert mse_p <= 1.3 * mse_l + 1e-8, (mse_p, mse_l)
        assert np.isfinite(rp.total_bits)
print('ok')
""", 8, timeout=900)


def test_solve_sharded_col_matches_emulated(multidev):
    """Device-sharded column solve (column blocks across the mesh, psum of
    residual contributions + boundary Onsager scalar) == the emulated
    column solve, exact transport bitwise-close (ISSUE 4 multidev)."""
    multidev("""
import jax, numpy as np
from repro.compat import make_mesh
from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss
from repro.core.engine import (AmpEngine, ColumnPartition, EngineConfig,
                               EcsqTransport, ExactFusion, FixedSchedule,
                               PsumFusion)
from repro.core.state_evolution import CSProblem

prior = BernoulliGauss(eps=0.05)
prob = CSProblem(n=2048, m=512, prior=prior, snr_db=20.0)
s0, a, y = sample_problem(jax.random.PRNGKey(1), prob.n, prob.m, prior,
                          prob.sigma_e2)
mesh = make_mesh((8,), ('data',))

for p in (8, 16):
    lay = ColumnPartition(n_inner=1)
    cfg = EngineConfig(n_proc=p, n_iter=8, collect_symbols=False, layout=lay)
    em = AmpEngine(prior, cfg, ExactFusion()).solve(y, a)
    sh = AmpEngine(prior, cfg, PsumFusion(axis='data')).solve_sharded(
        y, a, mesh)
    d = float(np.mean((em.x - sh.x) ** 2))
    assert d <= 1e-12, (p, d)
    np.testing.assert_allclose(sh.sigma2_hat, em.sigma2_hat, rtol=1e-6)

# quantized residual exchange across the mesh: same accounting
deltas = np.full(8, 0.02, np.float32); deltas[0] = np.inf
cfg = EngineConfig(n_proc=8, n_iter=8, collect_symbols=False,
                   layout=ColumnPartition(n_inner=1))
em = AmpEngine(prior, cfg, EcsqTransport(),
               FixedSchedule(deltas)).solve(y, a)
sh = AmpEngine(prior, cfg, PsumFusion(axis='data', local=EcsqTransport()),
               FixedSchedule(deltas)).solve_sharded(y, a, mesh)
np.testing.assert_allclose(sh.extra_var, em.extra_var, rtol=1e-6)
np.testing.assert_allclose(sh.sigma2_hat, em.sigma2_hat, rtol=0.02)
mse_em = float(em.mse(s0)[-1]); mse_sh = float(np.mean((sh.x - s0) ** 2))
assert abs(mse_sh - mse_em) <= 0.05 * mse_em + 1e-8, (mse_sh, mse_em)
print('ok')
""", 8, timeout=900)
