"""The drift tail's per-prior MMSE table (telemetry/drift.py): accuracy
against ``denoisers.mmse``, SE predictions and drifts against the
quadrature path, the domain fallback, one build per prior, and the
``table`` / ``quadrature`` counts on the service's drift span."""
from __future__ import annotations

import os
import sys
import threading

import numpy as np
import pytest

from repro.core.denoisers import BernoulliGauss, mmse
from repro.core.state_evolution import (CSProblem, se_trajectory_col,
                                        se_trajectory_erasure)
from repro.telemetry import drift
from repro.telemetry.drift import (DRIFT_COUNTS, MMSETable, mmse_table,
                                   se_drift_batch, se_prediction)

PRIORS = [BernoulliGauss(0.03), BernoulliGauss(0.05), BernoulliGauss(0.10),
          BernoulliGauss(0.10, mu_s=0.3, sigma_s=1.2)]


def _counts() -> dict:
    return dict.fromkeys(DRIFT_COUNTS, 0)


@pytest.mark.parametrize("prior", PRIORS, ids=str)
def test_table_matches_quadrature(prior):
    v = np.geomspace(MMSETable.V_MIN, MMSETable.V_MAX, 2000)
    counts = _counts()
    rel = np.abs(mmse_table(prior)(v, counts) / mmse(v, prior) - 1.0)
    low = v < 1e-3
    assert rel[low].max() <= 1e-4
    assert rel[~low].max() <= 1e-5
    assert counts["table"] == v.size and counts["quadrature"] == 0


def test_wide_slab_table_below_quadrature_error():
    """A wider slab carries more of the quadrature's own ripple; the
    table stays far inside what 10x the nodes would move."""
    prior = BernoulliGauss(0.05, mu_s=0.5, sigma_s=1.5)
    for lo, hi in ((MMSETable.V_MIN, 1e-3), (1e-3, 1e-1)):
        v = np.geomspace(lo, hi, 24)
        ref = mmse(v, prior)
        dev = np.abs(mmse_table(prior)(v) / ref - 1.0)
        own = np.abs(mmse(v, prior, n_nodes=40_001) / ref - 1.0)
        assert dev.max() <= min(1e-4, own.max() / 20)


def _prob(eps=0.10, snr_db=20.0, **kw):
    return CSProblem(n=10_000, m=3_000, prior=BernoulliGauss(eps, **kw),
                     snr_db=snr_db)


def _schedule(t, seed):
    ev = np.random.default_rng(seed).uniform(1e-5, 1e-3, t)
    return ev.astype(np.float32).astype(np.float64)


@pytest.mark.parametrize("layout,n_proc,erasure,n_inner", [
    ("row", 30, 0.0, 1), ("row", 30, 0.1, 1),
    ("col", 4, 0.0, 1), ("col", 4, 0.1, 2)])
@pytest.mark.parametrize("eps,t", [(0.03, 8), (0.10, 20)])
def test_prediction_matches_quadrature_path(layout, n_proc, erasure,
                                            n_inner, eps, t):
    prob = _prob(eps)
    ev = _schedule(t, seed=5)
    counts = _counts()
    pred = se_prediction(prob, t, ev, layout=layout, n_proc=n_proc,
                         erasure_rate=erasure, n_inner=n_inner,
                         counts=counts)
    sq = ev / n_proc
    if layout == "col":
        want = se_trajectory_col(prob, n_proc, n_outer=t, n_inner=n_inner,
                                 sigma_q2=sq, erasure_rate=erasure)[0]
    else:
        want = se_trajectory_erasure(prob, sq, n_proc, erasure)[:t]
    np.testing.assert_allclose(pred, want, rtol=1e-5, atol=0)
    assert counts["misses"] == 1
    assert counts["table"] == t * n_inner and counts["quadrature"] == 0


def test_drift_batch_matches_quadrature_path():
    prob, t, b = _prob(0.05), 10, 6
    rng = np.random.default_rng(7)
    ev = np.stack([_schedule(t, seed=100 + i) for i in range(b)])
    want_pred = np.stack([
        se_trajectory_erasure(prob, ev[i] / 30, 30, 0.0)[:t]
        for i in range(b)])
    s2 = want_pred * np.exp(rng.normal(0.0, 0.3, (b, t)))
    want = np.abs(np.log(s2 / want_pred)).mean(axis=1)
    counts = _counts()
    got = se_drift_batch(prob, s2, ev, n_proc=30, counts=counts)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert counts["lookups"] == counts["misses"] == b
    assert counts["table"] == b * t


def test_outside_domain_runs_quadrature_exactly():
    prior = BernoulliGauss(0.05)
    table = mmse_table(prior)
    v = np.array([5e-5, MMSETable.V_MIN, 0.01, MMSETable.V_MAX, 20.0])
    counts = _counts()
    got = table(v, counts)
    assert got[0] == mmse(5e-5, prior)[0]
    assert got[4] == mmse(20.0, prior)[0]
    assert counts == {**_counts(), "table": 3, "quadrature": 2}
    # through the SE recursion: at 60 dB the late steps fall under V_MIN
    prob = _prob(0.05, snr_db=60.0)
    ev = _schedule(10, seed=11) * 1e-6
    counts = _counts()
    pred = se_prediction(prob, 10, ev, n_proc=30, counts=counts)
    want = se_trajectory_erasure(prob, ev / 30, 30, 0.0)[:10]
    np.testing.assert_allclose(pred, want, rtol=1e-5, atol=0)
    assert counts["quadrature"] > 0
    assert counts["table"] + counts["quadrature"] == 10


def test_one_build_per_prior_under_concurrent_use(monkeypatch):
    monkeypatch.setattr(drift, "_tables", {})
    builds = []
    build = MMSETable._build

    def counted(self):
        builds.append(self.prior)
        return build(self)
    monkeypatch.setattr(MMSETable, "_build", counted)
    prior = BernoulliGauss(0.0713)
    n = (os.cpu_count() or 1) + 1
    start = threading.Barrier(n)
    out = []

    def first_use():
        start.wait()
        out.append((mmse_table(prior), float(mmse_table(prior)(0.02)[0])))
    threads = [threading.Thread(target=first_use) for _ in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert builds == [prior]
    assert len(out) == n
    assert len({id(tb) for tb, _ in out}) == 1
    assert len({m for _, m in out}) == 1


def test_batched_drift_span_counts_table_evaluations():
    """A BT batch through the service: its shared drift span counts one
    table evaluation per SE step of every miss, none by quadrature."""
    import jax

    from repro.core.amp import sample_problem
    from repro.serving import BucketPolicy, SolveRequest, SolveService

    prior, t = BernoulliGauss(eps=0.1), 8
    prob = CSProblem(n=128, m=64, prior=prior, snr_db=20.0)
    reqs = []
    for i in range(4):
        _, a, y = sample_problem(jax.random.PRNGKey(70 + i), prob.n, prob.m,
                                 prior, prob.sigma_e2)
        reqs.append(SolveRequest(y=y, a=a, prior=prior, n_proc=4, n_iter=t,
                                 policy="bt", snr_db=20.0))
    svc = SolveService(policy=BucketPolicy(max_batch=4, n_quantum=64,
                                           mp_quantum=8),
                       rate_accounting=False)
    results = svc.solve(reqs)
    tails = {tuple(s[:4]): s[4] for r in results for s in r.spans
             if s[0] == "drift"}
    assert len(tails) == 1
    (counts,) = tails.values()
    assert counts["lookups"] == len(reqs)
    assert counts["misses"] >= 1
    assert counts["table"] == counts["misses"] * t
    assert counts["quadrature"] == 0
