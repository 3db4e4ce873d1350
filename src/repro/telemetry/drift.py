"""Live SE-drift monitor (DESIGN.md §12).

The paper's promise is analytic predictability: quantized SE (eq. 8) and
its column/erasure extensions say what per-iteration variance a solve
*should* realize.  The engine already computes the realized plug-in
trajectory in-graph (``EngineTrace.sigma2_hat`` — no extra FLOPs), so
comparing the two per request is nearly free and turns mis-modeled
quantization error, erasure bursts, or stale RD tables into an alert
instead of a silent MSE regression.

Alignment with the engine's plug-in (verified against core/engine.py):

- Row layout: ``sigma2_hat[t] = ||z_t||^2 / m`` estimates the SE message
  variance *before* iteration t's transport noise is injected, i.e.
  ``se_trajectory_erasure(...)[t]`` (which starts at sigma_0^2).  The
  transport-injected variance rides separately as
  ``extra_var[t] = P * sigma_Q^2[t]``, which is exactly the schedule the
  SE recursion consumes.
- Column layout: ``sigma2_hat[s] = ||g^s||^2 / M`` post-fusion *includes*
  round-s quantization noise and matches ``tau[s]`` from
  ``se_trajectory_col`` directly.

Drift statistic: ``mean_t | ln(realized[t] / predicted[t]) |`` — a
symmetric, scale-free multiplicative error.  Clean solves measure
well under 0.5 (finite-N fluctuation at the paper's sizes); a mis-rated
solve (e.g. the request declares the wrong SNR, or the quantizer's true
MSE is not what the RD table claims) lands decades off on the log scale.

Predictions are memoized on the operating point (prior, shape, SNR,
layout, P, T, erasure rate, rounded quantizer schedule): a request that
repeats one pays a dict hit, not an SE recursion. A BT request's
realized schedule depends on its own signal, so its lookup misses.
Callers that pass ``counts`` (a dict over ``DRIFT_COUNTS``) get them
tallied per answer: a miss is an answer whose prediction the SE
recursion had to compute.

A miss's SE recursion reads the MMSE from a per-prior table
(``mmse_table``), not from a fresh ``denoisers.mmse`` quadrature at every
step. The table is a piecewise Chebyshev interpolant of ln mmse against
ln v over v in [1e-4, 10]: 40 segments of degree 16, whose 680 nodes are
``mmse`` itself (4,001 nodes, unchanged), built on a prior's first miss
and kept process-wide. For eps in {0.03, 0.05, 0.10} it deviates from
``mmse`` by at most 3.4e-5 relative on [1e-4, 1e-3], 4.4e-6 on
[1e-3, 1e-1] and 4e-7 on [1e-1, 10]: the ripple ``mmse`` itself carries
as its two node grids slide past each other with v, which no smooth
interpolant follows. ``mmse`` is further than that from its own
integral: 7.5e-3 to 7.8e-3, 1.6e-3 to 1.8e-3 and 1e-7 on the same bands
against 40,001 nodes. A v outside the domain (a large erasure
amplification, an SNR far above 20 dB) runs ``mmse`` exactly. Each
evaluation counts in ``counts`` as ``table`` or ``quadrature``; building
a table counts as neither. ``make_mmse_interp`` (log-log linear over 400
points) is not reused: it deviates from ``mmse`` by 1.1e-4 to 2.6e-4 on
every band, 3x to 600x more than this table, and evaluates through numpy
on one-element arrays. It is left as it is, so the BT tables and DP
allocation that read it stay bit-identical.
"""
from __future__ import annotations

import math
import threading
from typing import Optional, Tuple

import numpy as np

from ..core.denoisers import BernoulliGauss, mmse
from ..core.state_evolution import (CSProblem, se_trajectory_col,
                                    se_trajectory_erasure)

__all__ = ["se_drift", "se_drift_batch", "se_prediction", "mmse_table",
           "MMSETable", "DRIFT_ALERT", "DRIFT_COUNTS"]

# Above this, flag the request (service increments amp_se_drift_alerts_total).
DRIFT_ALERT = 1.0
# the keys of a drift tail's ``counts``: SE-prediction memo lookups and
# misses, and the MMSE evaluations by path (module docstring)
DRIFT_COUNTS = ("lookups", "misses", "table", "quadrature")

_cache_lock = threading.Lock()
_cache: dict = {}
_CACHE_MAX = 4096
# second-level cache in front of ``se_prediction``: keyed by the raw
# float32 schedule bytes instead of the 5-sig-digit rounded tuple, so a
# repeated schedule pays ~1us of key construction per request instead
# of ~5us of per-element string formatting. Bit-identical schedules —
# lossless and fixed-schedule requests of one operating point — always
# hit. The tail's cost on the chip, hits and misses, is in PERF.md.
_fast_cache: dict = {}


# the per-prior MMSE tables (module docstring), bounded like ``_cache``
_tables: dict = {}
_TABLES_MAX = 64


class MMSETable:
    """``mmse(v, prior)`` for the SE recursion: piecewise Chebyshev in
    ln v -> ln mmse on ``[V_MIN, V_MAX]``, ``mmse`` itself outside. Built
    on the first call, once, whichever thread makes it."""

    V_MIN, V_MAX = 1e-4, 10.0
    SEGMENTS, DEGREE = 40, 16

    def __init__(self, prior: BernoulliGauss):
        self.prior = prior
        self._lo = math.log(self.V_MIN)
        self._inv_h = self.SEGMENTS / (math.log(self.V_MAX) - self._lo)
        self._lock = threading.Lock()
        self._segs: Optional[list] = None

    def _build(self) -> list:
        """Per segment ``(c_0, (c_DEGREE, ..., c_1))``: the Chebyshev
        coefficients of ln mmse at the first-kind nodes, in the order
        Clenshaw's recurrence reads them."""
        n = self.DEGREE + 1
        theta = np.pi * (np.arange(n) + 0.5) / n
        h = 1.0 / self._inv_h
        mids = self._lo + h * (np.arange(self.SEGMENTS) + 0.5)
        v = np.exp(mids[:, None] + 0.5 * h * np.cos(theta))
        ln_m = np.log(mmse(v.ravel(), self.prior)).reshape(v.shape)
        coef = (2.0 / n) * ln_m @ np.cos(np.outer(theta, np.arange(n)))
        coef[:, 0] *= 0.5
        return [(float(c[0]), tuple(c[:0:-1].tolist())) for c in coef]

    def _ready(self) -> list:
        segs = self._segs
        if segs is None:
            with self._lock:
                if self._segs is None:
                    self._segs = self._build()
                segs = self._segs
        return segs

    def __call__(self, v, counts: Optional[dict] = None) -> np.ndarray:
        """MMSE at each channel variance in ``v`` (flattened). The SE
        recursion calls with one value at a time, so each runs on plain
        floats; ``counts`` gains the ``table`` and ``quadrature``
        evaluations."""
        segs = self._ready()
        vals = np.ravel(v).tolist()
        out = np.empty(len(vals))
        lo, inv_h, last = self._lo, self._inv_h, self.SEGMENTS - 1
        n_quad = 0
        for i, x in enumerate(vals):
            if not self.V_MIN <= x <= self.V_MAX:
                out[i] = mmse(x, self.prior)[0]
                n_quad += 1
                continue
            t = (math.log(x) - lo) * inv_h
            s = min(int(t), last)
            c0, rest = segs[s]
            y = 2.0 * (t - s) - 1.0
            y2 = 2.0 * y
            b1 = b2 = 0.0
            for c in rest:
                b1, b2 = y2 * b1 - b2 + c, b1
            out[i] = math.exp(y * b1 - b2 + c0)
        if counts is not None:
            counts["table"] += len(vals) - n_quad
            counts["quadrature"] += n_quad
        return out


def mmse_table(prior: BernoulliGauss) -> MMSETable:
    """The process-wide ``MMSETable`` of ``prior``."""
    key = (prior.eps, prior.mu_s, prior.sigma_s)
    with _cache_lock:
        table = _tables.get(key)
        if table is None:
            if len(_tables) >= _TABLES_MAX:
                _tables.clear()
            table = _tables[key] = MMSETable(prior)
    return table


def _sched_key(extra_var: Optional[np.ndarray], t: int) -> tuple:
    if extra_var is None:
        return (0.0,) * t
    # 5 significant digits: identical requests hit; real schedule changes miss.
    return tuple(float(f"{float(v):.5e}") for v in extra_var[:t])


def se_prediction(prob: CSProblem, t_max: int, extra_var,
                  *, layout: str = "row", n_proc: int = 1,
                  erasure_rate: float = 0.0, n_inner: int = 1,
                  counts: Optional[dict] = None) -> np.ndarray:
    """Predicted per-iteration variance trajectory (length ``t_max``) for
    the operating point, memoized process-wide; a computed one counts as
    a miss in ``counts``, and its MMSE evaluations as ``table`` or
    ``quadrature``."""
    key = (prob.n, prob.m, prob.snr_db,
           prob.prior.eps, prob.prior.mu_s, prob.prior.sigma_s,
           layout, int(n_proc), int(n_inner), float(erasure_rate),
           int(t_max), _sched_key(extra_var, t_max))
    with _cache_lock:
        pred = _cache.get(key)
    if pred is not None:
        return pred
    if counts is not None:
        counts["misses"] += 1
    sq = (np.zeros(t_max) if extra_var is None
          else np.asarray(extra_var, dtype=np.float64)[:t_max] / max(n_proc, 1))
    table = mmse_table(prob.prior)
    mmse_fn = lambda v: table(v, counts)
    if layout == "col":
        tau, _ = se_trajectory_col(prob, n_proc, n_outer=t_max,
                                   n_inner=n_inner, sigma_q2=sq,
                                   mmse_fn=mmse_fn,
                                   erasure_rate=erasure_rate)
        pred = np.asarray(tau[:t_max])
    else:
        pred = se_trajectory_erasure(prob, sq, n_proc, erasure_rate,
                                     mmse_fn=mmse_fn)[:t_max]
    with _cache_lock:
        if len(_cache) >= _CACHE_MAX:
            _cache.clear()
        _cache[key] = pred
    return pred


def _fast_prediction(prob: CSProblem, t_max: int, extra_var, layout: str,
                     n_proc: int, erasure_rate: float, n_inner: int,
                     counts: Optional[dict] = None, answers: int = 1
                     ) -> tuple:
    """Returns ``(pred, log_pred, ok, ok_all)`` — the prediction plus its
    precomputed log and validity mask (``pred > 0`` and finite), so the
    batched drift stat pays only the realized-side numpy ops per call.
    The lookup serves ``answers`` answers in ``counts``."""
    if counts is not None:
        counts["lookups"] += answers
    ev_b = (None if extra_var is None else
            np.ascontiguousarray(extra_var[:t_max],
                                 dtype=np.float32).tobytes())
    key = (prob.n, prob.m, prob.snr_db,
           prob.prior.eps, prob.prior.mu_s, prob.prior.sigma_s,
           layout, int(n_proc), int(n_inner), float(erasure_rate),
           int(t_max), ev_b)
    entry = _fast_cache.get(key)    # GIL-atomic read; no lock on the hit
    if entry is None:
        pred = se_prediction(prob, t_max, extra_var, layout=layout,
                             n_proc=n_proc, erasure_rate=erasure_rate,
                             n_inner=n_inner, counts=counts)
        ok = (pred > 0.0) & np.isfinite(pred)
        with np.errstate(divide="ignore", invalid="ignore"):
            log_pred = np.where(ok, np.log(np.where(ok, pred, 1.0)), 0.0)
        entry = (pred, log_pred, ok, bool(ok.all()))
        with _cache_lock:
            if len(_fast_cache) >= _CACHE_MAX:
                _fast_cache.clear()
            _fast_cache[key] = entry
    return entry


def se_drift(prob: CSProblem, sigma2_hat, extra_var=None,
             *, layout: str = "row", n_proc: int = 1,
             erasure_rate: float = 0.0, n_inner: int = 1,
             counts: Optional[dict] = None) -> Tuple[float, np.ndarray]:
    """Compare a realized ``sigma2_hat`` trajectory against its SE
    prediction.  Returns ``(drift, predicted)`` with
    ``drift = mean_t |ln(realized[t]/predicted[t])|``; NaN when no
    iteration admits a well-defined ratio."""
    s2 = np.asarray(sigma2_hat, dtype=np.float64)
    t_max = len(s2)
    pred = _fast_prediction(prob, t_max, extra_var, layout, n_proc,
                            erasure_rate, n_inner, counts)[0]
    # T is small (<= a few dozen): a scalar loop beats the ~8 numpy-op
    # masked pipeline by an order of magnitude on the hot path
    tot, k = 0.0, 0
    for r, p in zip(s2.tolist(), pred.tolist()):
        if r > 0.0 and p > 0.0 and math.isfinite(r) and math.isfinite(p):
            tot += abs(math.log(r / p))
            k += 1
    if k == 0:
        return float("nan"), pred
    return tot / k, pred


def se_drift_batch(prob: CSProblem, sigma2_hat, extra_var=None,
                   *, layout: str = "row", n_proc: int = 1,
                   erasure_rate: float = 0.0, n_inner: int = 1,
                   counts: Optional[dict] = None) -> np.ndarray:
    """Vectorized ``se_drift`` over a batch sharing one operating point:
    ``sigma2_hat`` is ``(B, T)``; ``extra_var`` is either one length-T
    realized quantizer schedule shared by every row, or a ``(B, T)``
    matrix of per-request schedules (one memoized prediction lookup per
    *distinct* schedule — requests with per-request rate allocations
    stay on the vectorized path instead of degrading to B scalar
    ``se_drift`` calls). One masked log-ratio pass covers every row —
    the batched dispatch path's telemetry tail (DESIGN.md §12). Rows
    with no well-defined ratio come back NaN. A schedule shared by every
    row counts one lookup per row and at most one miss."""
    s2 = np.asarray(sigma2_hat, dtype=np.float64)
    ev = None if extra_var is None else np.asarray(extra_var)
    if ev is not None and ev.ndim == 2:
        t_max = s2.shape[1]
        log_pred = np.empty_like(s2)
        ok_pred = np.empty(s2.shape, dtype=bool)
        ok_all = True
        for i in range(s2.shape[0]):
            _, lp, okp, oa = _fast_prediction(prob, t_max, ev[i], layout,
                                              n_proc, erasure_rate, n_inner,
                                              counts)
            log_pred[i] = lp
            ok_pred[i] = okp
            ok_all = ok_all and oa
    else:
        _, log_pred, ok_pred, ok_all = _fast_prediction(
            prob, s2.shape[1], ev, layout, n_proc, erasure_rate, n_inner,
            counts, s2.shape[0])
    # clean-trace fast path (the steady-state common case): every entry
    # strictly positive and finite on both sides, so the mask machinery
    # — masked ufuncs are markedly slower than plain ones — and the
    # per-row count bookkeeping all collapse away
    if ok_all and s2.size and s2.min() > 0.0 and math.isfinite(s2.max()):
        buf = np.log(s2)
        buf -= log_pred
        np.abs(buf, out=buf)
        return buf.sum(axis=1) / s2.shape[1]
    ok = (s2 > 0.0) & np.isfinite(s2)
    if not ok_all:
        ok &= ok_pred
    # log only where valid (masked entries stay 0), subtract the cached
    # log-prediction in place, zero the masked residue, reduce
    buf = np.log(s2, out=np.zeros_like(s2), where=ok)
    np.subtract(buf, log_pred, out=buf, where=ok)
    np.abs(buf, out=buf)
    k = ok.sum(axis=1)
    tot = buf.sum(axis=1)
    return np.where(k > 0, tot / np.maximum(k, 1), np.nan)
