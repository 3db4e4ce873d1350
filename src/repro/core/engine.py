"""Unified scan-compiled MP-AMP engine (DESIGN.md §3).

The paper's algorithm family — centralized AMP, lossless MP-AMP, ECSQ
MP-AMP with fixed / DP / BT rate schedules, int8/int4 block-quantized
fusion — is one iteration body parameterized by

  * a **Transport**: how the per-processor fusion messages f_t^p are
    compressed before the sum at the fusion center
    (``ExactFusion`` | ``EcsqTransport`` | ``BlockQuantTransport``), and
  * a **RateController**: how the quantizer resolution is chosen per
    iteration (``FixedSchedule`` | ``DPSchedule`` | ``BTRateControl``).

``AmpEngine`` runs the full T-iteration solve as a *single* ``lax.scan``
over that body — including BT back-tracking rate control, re-expressed as a
fixed-count in-graph bisection against precomputed MMSE/rate tables — so
there is no per-iteration host round-trip (the ``float(s2)`` syncs of the
pre-engine ``mp_amp.py`` host loop). A ``vmap``-batched ``solve_many``
solves many CS instances at once (the serving scenario), and the local
computation routes through the ``kernels/amp_fused`` suite (DESIGN.md §8)
on TPU: batched Pallas grids covering the whole (batch, P) stack in one
launch with the sigma2_hat reduction fused, fused column-layout kernels,
tile padding hoisted to solve entry, and optional bf16 A-streaming
(``EngineConfig.a_dtype``) with f32 accumulation.

The mesh is an engine axis, not a separate code path (DESIGN.md §6):
``solve_sharded`` runs the *same* scan body inside ``shard_map`` over a
mesh axis, with the per-processor (A, y) shards as sharded operands and
schedules / BT tables riding replicated. Device-collective transports
(``PsumFusion``, ``CompressedPsumTransport``) make the paper's fusion
``f_t = sum_p Q(f_t^p)`` an actual (optionally lossy-compressed) collective
on the device links, with straggler ``drop`` rescaling folded in.

The partition **layout** is a third engine axis (DESIGN.md §7): the paper's
row-wise scheme (``RowPartition``, each processor owns M/P rows and the
fusion sums denoiser messages) and the column-wise C-MP-AMP of
arXiv:1701.02578 (``ColumnPartition``, each processor owns N/P signal
columns and the fusion sums *residual contributions* ``r^p = A_p x_p``,
length M — the natural layout for tall-N problems where N >> M). Both run
the same scan/transport/controller machinery: a Transport fuses a (P, L)
stack into (L,) either way, so ``ExactFusion``/``EcsqTransport``/
``BlockQuantTransport`` and the device collectives apply to residual
contributions unchanged. Column rate control gets its own in-graph tables
(``ColumnBTRateControl``: the quantized payload is ~Gaussian, so the rate
table is one-dimensional) driven by the column-wise two-stage state
evolution (``state_evolution.se_trajectory_col``).

``core/amp.py`` (centralized), ``core/mp_amp.py`` (emulated multi-processor)
and ``launch/solver.py`` (mesh-distributed) are thin frontends over this
module; arbitrary Python rate-controller callables are still supported via
``solve_host_loop``, which reuses the exact same jitted iteration body one
step at a time.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import warnings
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec, SingleDeviceSharding

from ..kernels.amp_fused.ops import (amp_local_grid, col_inner_step,
                                     col_residual, pad_col_shards,
                                     pad_row_shards)
from .compression import (QuantConfig, compressed_psum, dequantize_blocks,
                          quant_noise_var, quantize_blocks)
from .denoisers import BernoulliGauss, eta, eta_bg
from .quantize import (GaussMixture, dequantize_midtread, ecsq_entropy,
                       message_mixture, quantize_midtread)
from .rate_alloc import BTController, rate_for_sigma_q2
from .rate_distortion import RDModel
from .state_evolution import CSProblem, se_trajectory_col

# every jnp contraction on A runs at full f32 precision: on TPU the default
# rounds matmul operands to bf16, and the jnp path is the f32 reference
# the Pallas kernels are held to
_HI = lax.Precision.HIGHEST

__all__ = [
    "AmpEngine", "EngineConfig", "EngineTrace", "ErasureSpec",
    "RowPartition", "ColumnPartition",
    "Transport", "ExactFusion", "EcsqTransport", "BlockQuantTransport",
    "PsumFusion", "CompressedPsumTransport",
    "RateController", "FixedSchedule", "DPSchedule", "BTRateControl",
    "ColDPSchedule", "ColumnBTRateControl", "ColBTTables", "col_bt_delta_for",
    "BTTables", "HetParams", "bt_delta_for", "stack_bt_tables",
    "pad_bt_tables", "amp_gc_step", "split_problem", "split_problem_cols",
]


# ---------------------------------------------------------------------------
# shared iteration pieces
# ---------------------------------------------------------------------------

def split_problem(a_mat: np.ndarray, y: np.ndarray, n_proc: int):
    """Row-partition (A, y) across processors: (P, M/P, N), (P, M/P)."""
    m, n = a_mat.shape
    assert m % n_proc == 0, f"M={m} not divisible by P={n_proc}"
    mp = m // n_proc
    return a_mat.reshape(n_proc, mp, n), y.reshape(n_proc, mp)


def split_problem_cols(a_mat: np.ndarray, n_proc: int) -> np.ndarray:
    """Column-partition A across processors: (M, N) -> (P, M, N/P).

    Processor p owns the contiguous column block ``A[:, p*N/P:(p+1)*N/P]``
    and the matching slice of the signal (C-MP-AMP, DESIGN.md §7); y is
    shared, not split — the measurements are common to every processor.
    """
    m, n = a_mat.shape
    assert n % n_proc == 0, f"N={n} not divisible by P={n_proc}"
    np_ = n // n_proc
    return np.ascontiguousarray(
        a_mat.reshape(m, n_proc, np_).transpose(1, 0, 2))


@dataclasses.dataclass(frozen=True)
class RowPartition:
    """The source paper's layout: each processor owns M/P measurement rows;
    the fusion sums the per-processor denoiser messages f^p (length N)."""


@dataclasses.dataclass(frozen=True)
class ColumnPartition:
    """C-MP-AMP layout (arXiv:1701.02578): each processor owns N/P signal
    columns; the fusion sums quantized residual contributions A_p x_p
    (length M).  ``EngineConfig.n_iter`` counts *outer rounds* (one fusion
    exchange each); every round runs ``n_inner`` local AMP iterations.

    The Onsager memory must survive the fusion boundary — a bare restart
    (``z <- g`` with no correction) measurably breaks the two-stage state
    evolution (the SE-oracle tests would catch a ~20x drift).  Every
    block jumps *simultaneously* at a fusion, so the joint correction is
    the sum of every processor's final Onsager term: the next round's
    residual starts from

        g^{s+1} + sum_q c_q * z_q^{last},

    where ``z_q^{last}`` is the residual that fed processor q's final
    denoise and ``c_q = sum(eta')/M`` its coefficient (a per-processor
    correction alone — each block treating the others as a frozen
    observation — visibly under-corrects and stalls).  At ``n_inner == 1``
    every ``z_q^{last}`` *is* the previous fused residual, the correction
    collapses to the scalar ``(sum_q c_q) * g^s``, and C-MP-AMP becomes
    *identical* to centralized AMP under exact fusion — which is what the
    layout-parity tests pin; the engine then carries only that scalar
    (one extra number per processor on the wire).  At ``n_inner > 1`` the
    correction is a second length-M exchange riding with the residual
    contributions (uncompressed: it is an Onsager correction, not a
    payload — DESIGN.md §7 discusses the traffic accounting).
    """

    n_inner: int = 1

    @property
    def carry_fused(self) -> bool:
        """Scalar-carry fast path: at one inner iteration per round the
        joint boundary correction is a scalar times the previous fused
        residual (docstring), so nothing vector-valued crosses rounds."""
        return self.n_inner == 1


def amp_gc_step(f, denoise_var, prior: BernoulliGauss, kappa):
    """GC tail shared by every frontend: denoise + Onsager coefficient."""
    eta_fn = lambda v: eta(v, denoise_var, prior, xp=jnp)
    x_new = eta_fn(f)
    onsager_new = jax.grad(lambda v: jnp.sum(eta_fn(v)))(f).mean() / kappa
    return x_new, onsager_new


# ---------------------------------------------------------------------------
# erasure (lossy-wire realism; DESIGN.md §10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ErasureSpec:
    """Per-round, per-processor fusion-packet loss model.

    ``sample_mask`` draws the concrete (T, P) 0/1 drop schedule host-side;
    the engine threads it through the solve as an ordinary scan operand,
    so erasure is *data*, not a recompile — one erasure-enabled program
    serves every loss realization of its shape.

    ``bernoulli``: each packet lost i.i.d. with probability ``rate``.
    ``gilbert``: two-state Gilbert-Elliott channel per processor — a bad
    state drops every packet, mean bad-state sojourn ``burst_len`` rounds,
    transition probabilities chosen so the stationary loss probability is
    ``rate`` (p_bg = 1/burst_len, p_gb = rate*p_bg/(1-rate), clipped to
    1). Chains start in their stationary distribution so the first round
    is already representative.
    """

    rate: float = 0.0
    model: str = "bernoulli"          # "bernoulli" | "gilbert"
    burst_len: float = 4.0            # gilbert: mean bad-state rounds
    seed: int = 0

    def __post_init__(self):
        assert 0.0 <= self.rate < 1.0, self.rate
        assert self.model in ("bernoulli", "gilbert"), self.model
        assert self.burst_len >= 1.0, self.burst_len

    def sample_mask(self, n_iter: int, n_proc: int,
                    seed: int | None = None) -> np.ndarray:
        """Draw a (n_iter, n_proc) float32 drop mask (1 = packet lost)."""
        rng = np.random.default_rng(self.seed if seed is None else seed)
        if self.rate == 0.0:
            return np.zeros((n_iter, n_proc), np.float32)
        if self.model == "bernoulli":
            return (rng.random((n_iter, n_proc))
                    < self.rate).astype(np.float32)
        p_bg = 1.0 / self.burst_len
        p_gb = min(self.rate * p_bg / (1.0 - self.rate), 1.0)
        bad = rng.random(n_proc) < self.rate
        mask = np.zeros((n_iter, n_proc), np.float32)
        for t in range(n_iter):
            mask[t] = bad
            flip = rng.random(n_proc)
            bad = np.where(bad, flip >= p_bg, flip < p_gb)
        return mask


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------

@runtime_checkable
class Transport(Protocol):
    """Fusion-message compression: (P, N) messages -> fused (N,) estimate.

    ``fuse`` must be pure jnp (it runs inside jit/scan/vmap) and returns
    ``(f, extra_var, symbols)`` where ``extra_var`` is the additional
    denoiser variance injected by compression (the paper's P*sigma_Q^2
    accounting) and ``symbols`` the per-processor quantizer indices for
    empirical-rate accounting (all-zeros when not applicable).

    ``drop`` is the erasure/straggler mask: a per-processor (P,) 0/1
    vector for the emulated transports (survivor rescale via
    ``_erasure_rescale``), a per-device scalar for the device collectives
    (``_drop_rescale``). ``None`` (emulated only) compiles the drop-free
    program — byte-identical to the pre-erasure engine.
    """

    def fuse(self, f_p, delta, drop=None): ...  # pragma: no cover - protocol


def _erasure_rescale(f_q, extra_per, drop):
    """Emulated counterpart of ``_drop_rescale``: per-processor erasure of
    the row-layout fusion packets. ``drop`` is a (P,) 0/1 mask; survivors
    are rescaled by P/k so the fusion stays an unbiased estimate of the
    full sum, and their embedded quantization noise (``extra_per`` per
    delivered packet) amplifies by the same scale^2 — exactly the noise
    bookkeeping the erasure-extended SE integrates over k
    (``state_evolution.erasure_amplification``)."""
    keep = 1.0 - drop
    n_surv = jnp.maximum(jnp.sum(keep), 1.0)
    scale = f_q.shape[0] / n_surv
    f = jnp.sum(f_q * keep[:, None], axis=0) * scale
    extra = extra_per * n_surv * scale**2
    return f, extra


@dataclasses.dataclass(frozen=True)
class ExactFusion:
    """Lossless fusion (centralized AMP / the paper's 32-bit baseline)."""

    def fuse(self, f_p, delta, drop=None):
        if drop is None:
            return jnp.sum(f_p, axis=0), jnp.zeros(()), jnp.zeros_like(f_p)
        f, extra = _erasure_rescale(f_p, jnp.zeros(()), drop)
        return f, extra, jnp.zeros_like(f_p)


@dataclasses.dataclass(frozen=True)
class EcsqTransport:
    """Midtread uniform quantizer per message (paper Sec. 3.2).

    ``delta`` is the bin size chosen by the rate controller; non-finite
    delta means lossless fusion at that iteration. Rate accounting is the
    ECSQ entropy H_Q (analytic) plus the empirical entropy of ``symbols``
    — both computed by the frontends from the returned trace.
    """

    def fuse(self, f_p, delta, drop=None):
        n_proc = f_p.shape[0]
        lossless = ~jnp.isfinite(delta)
        safe_delta = jnp.where(lossless, 1.0, delta)
        q = quantize_midtread(f_p, safe_delta)
        f_q = jnp.where(lossless, f_p, dequantize_midtread(q, safe_delta))
        if drop is None:
            f = jnp.sum(f_q, axis=0)
            extra = jnp.where(lossless, 0.0, n_proc * safe_delta**2 / 12.0)
            return f, extra, q
        per = jnp.where(lossless, 0.0, safe_delta**2 / 12.0)
        f, extra = _erasure_rescale(f_q, per, drop)
        return f, extra, q


@dataclasses.dataclass(frozen=True)
class BlockQuantTransport:
    """Per-block max-abs int8/int4 quantization (the compressed_psum wire
    format of core/compression.py, emulated over the leading P axis).

    The rate is fixed by the wire width (``bits`` + bf16 scale per block)
    instead of a controller, so ``delta`` is ignored; noise accounting uses
    the realized per-block bin sizes exactly like ``compressed_psum``.
    """

    bits: int = 8
    block: int = 512

    @property
    def qc(self) -> QuantConfig:
        return QuantConfig(bits=self.bits, block=self.block)

    def fuse(self, f_p, delta, drop=None):
        n_proc, n = f_p.shape
        qc = self.qc
        q, scale = quantize_blocks(f_p, qc)
        deq = dequantize_blocks(q, scale, qc, orig_len=n)
        if drop is None:
            f = jnp.sum(deq, axis=0)
            extra = quant_noise_var(scale, qc) * n_proc
        else:
            f, extra = _erasure_rescale(deq, quant_noise_var(scale, qc),
                                        drop)
        return f, extra, q[..., :n].astype(jnp.float32)


# -- device-collective transports (run inside shard_map; DESIGN.md §6) ------

def _drop_rescale(f_local, drop, axis: str):
    """Straggler mitigation as a transport option: zero this shard when
    ``drop`` is set and rescale the survivors so the fusion stays an
    unbiased estimate of the full sum (the modified SE absorbs the extra
    variance exactly like quantization noise). Returns ``(rescaled, keep,
    scale)`` so callers can apply the matching factors to their own noise
    accounting."""
    keep = 1.0 - drop
    n_dev = lax.axis_size(axis)
    scale = n_dev / jnp.maximum(lax.psum(keep, axis), 1.0)
    return f_local * keep * scale, keep, scale


@dataclasses.dataclass(frozen=True)
class PsumFusion:
    """Exact-wire fusion over a mesh axis: per-device messages are summed
    locally (optionally through an emulated per-processor ``local``
    transport, e.g. ``EcsqTransport`` for the paper's quantize-at-each-
    processor scenario) and psum'd across ``axis``.

    ``fuse`` takes the extra ``drop`` operand (per-iteration straggler flag
    for this shard); device transports always receive it — the engine's
    sharded scan threads it as a sharded scan operand.
    """

    axis: str = "data"
    local: Transport = dataclasses.field(default_factory=ExactFusion)

    def fuse(self, f_p, delta, drop):
        f_loc, extra_loc, _ = self.local.fuse(f_p, delta)
        f_loc, keep, scale = _drop_rescale(f_loc, drop, self.axis)
        f = lax.psum(f_loc, self.axis)
        # local fuse saw only this device's emulated processors: psum turns
        # p_local * sigma_Q^2 into the paper's global P * sigma_Q^2. Under
        # straggler rescale the survivors' embedded quantization noise is
        # amplified by scale^2 (dropped shards contribute none), so the
        # accounting follows the same keep/scale as the messages.
        extra = lax.psum(extra_loc * keep, self.axis) * scale**2
        return f, extra, jnp.zeros(())


@dataclasses.dataclass(frozen=True)
class CompressedPsumTransport:
    """Lossy-compressed wire fusion: the device sum itself runs as the
    two-phase int8/int4 ``compressed_psum`` collective over ``axis``
    (DESIGN.md §2) — wire bytes drop 4x/8x versus a bf16 ring all-reduce,
    visible as s8/u8 collective operands in the lowered HLO."""

    axis: str = "data"
    bits: int = 8
    block: int = 512

    @property
    def qc(self) -> QuantConfig:
        return QuantConfig(bits=self.bits, block=self.block)

    def fuse(self, f_p, delta, drop):
        f_loc, _, _ = _drop_rescale(jnp.sum(f_p, axis=0), drop, self.axis)
        # quantization happens after the rescale, so compressed_psum's
        # realized-scale noise measurement already includes its effect
        f, noise = compressed_psum(f_loc, self.axis, self.qc)
        # each device computed the noise from its own send-side scales;
        # pmean makes the reported accounting a well-defined replicated value
        return f, lax.pmean(noise, self.axis), jnp.zeros(())


# ---------------------------------------------------------------------------
# rate controllers
# ---------------------------------------------------------------------------

@runtime_checkable
class RateController(Protocol):
    """Chooses the quantizer bin size for iteration ``t``.

    ``delta_for`` must be pure jnp; it receives the traced iteration index
    and the post-LC plug-in estimate sigma_hat_{t,D}^2 and returns
    ``(delta, rate_bits)`` (rate = +inf when the controller does not track
    a coding rate, e.g. fixed schedules whose H_Q is computed offline).
    """

    n_iter: int

    def delta_for(self, t, sigma2_hat): ...  # pragma: no cover - protocol


class FixedSchedule:
    """Predetermined per-iteration bin sizes (np.inf = lossless)."""

    def __init__(self, deltas):
        self.deltas = np.asarray(deltas, np.float32)
        self.n_iter = len(self.deltas)

    def delta_for(self, t, sigma2_hat):
        return jnp.asarray(self.deltas)[t], jnp.float32(jnp.inf)


class DPSchedule(FixedSchedule):
    """Offline-optimal DP allocation realized as ECSQ bin sizes.

    Converts a ``dp_allocate`` result to the bin sizes hitting the DP's
    predicted per-iteration distortions (paper's "+0.255 bits" ECSQ
    implementation; mirrors benchmarks/paper_repro.py).
    """

    def __init__(self, dp_result, rd: RDModel, n_proc: int):
        sq2 = np.maximum(
            rd.distortion_msg(dp_result.rates, dp_result.sigma2_d[:-1],
                              n_proc), 1e-30)
        super().__init__(np.sqrt(12.0 * sq2))
        self.rates = np.asarray(dp_result.rates)
        self.sigma2_d = np.asarray(dp_result.sigma2_d)


class BTTables(NamedTuple):
    """The in-graph BT controller's state as a pure array pytree.

    Everything ``bt_delta_for`` needs — MMSE interpolation table, SE
    targets, rate table, r_max cap curve, and the scalar problem
    parameters (sigma_e2, kappa, prior, P) — lives here as jnp arrays, so
    per-request controllers can be *stacked* (leading batch axis via
    ``stack_bt_tables``) and ride through ``vmap`` as ordinary operands.
    This is how one compiled heterogeneous-batch solve serves requests
    with different SNR / sparsity / rate budgets simultaneously.
    """

    log_v: jnp.ndarray        # (400,) MMSE interp grid, log variance
    log_m: jnp.ndarray        # (400,) log mmse values
    targets: jnp.ndarray      # (T,) c_ratio * sigma_{t+1,C}^2
    log_s2_grid: jnp.ndarray  # (n_s2,) rate-table axis 0
    log2u_grid: jnp.ndarray   # (n_u,) rate-table axis 1
    gap_tab: jnp.ndarray      # (n_s2, n_u) G = R + log2(u)
    cap_ls2: jnp.ndarray      # (512,) cap curve axis
    cap_lsq2: jnp.ndarray     # (512,) log sigma_Q^2 at r_max
    sigma_e2: jnp.ndarray     # () problem scalars -------------------
    inv_kappa: jnp.ndarray    # ()
    n_proc: jnp.ndarray       # () float
    eps: jnp.ndarray          # () prior
    mu_s: jnp.ndarray         # ()
    sigma_s2: jnp.ndarray     # ()
    r_max: jnp.ndarray        # () delivered-rate cap (erasure-adjusted)
    amp: jnp.ndarray          # () erasure survivor-rescale amplification
                              #    E[P/max(k,1)]; exactly 1.0 when lossless

    _dummies = {}  # class-level memo for dummy tables (not a field)

    @classmethod
    def dummy(cls, n_iter: int, n_s2: int = 25, n_u: int = 61) -> "BTTables":
        """Benign finite tables for non-BT instances inside a mixed batch.

        When any instance of the batch uses BT, ``bt_delta_for`` is
        evaluated for *every* instance (its output is discarded through
        ``jnp.where`` for fixed-schedule requests), so the tables must
        produce finite values — the actual numbers are irrelevant.
        Memoized: the serving hot path requests one per bucket dispatch.
        """
        key = (n_iter, n_s2, n_u)
        if key in cls._dummies:
            return cls._dummies[key]
        f = lambda v: jnp.asarray(v, jnp.float32)
        lin = np.linspace(-20.0, 7.0, 400).astype(np.float32)
        tb = cls(
            log_v=jnp.asarray(lin), log_m=jnp.asarray(lin),
            targets=jnp.ones(n_iter, jnp.float32),
            log_s2_grid=jnp.asarray(np.linspace(-20.0, 2.0, n_s2),
                                    jnp.float32),
            log2u_grid=jnp.asarray(np.linspace(-12.0, 5.0, n_u), jnp.float32),
            gap_tab=jnp.ones((n_s2, n_u), jnp.float32),
            cap_ls2=jnp.asarray(np.linspace(-20.0, 2.0, 512), jnp.float32),
            cap_lsq2=jnp.zeros(512, jnp.float32),
            sigma_e2=f(1e-3), inv_kappa=f(1.0), n_proc=f(1.0),
            eps=f(0.1), mu_s=f(0.0), sigma_s2=f(1.0), r_max=f(6.0),
            amp=f(1.0),
        )
        cls._dummies[key] = tb
        return tb


def _bt_mmse(tb: BTTables, v):
    lv = jnp.clip(jnp.log(jnp.maximum(v, 1e-30)), tb.log_v[0], tb.log_v[-1])
    return jnp.exp(jnp.interp(lv, tb.log_v, tb.log_m))


def _bt_predict_next(tb: BTTables, sigma2_d, sigma_q2):
    # tb.amp is exactly 1.0 on a lossless link, so the multiply is a
    # bit-exact no-op there (IEEE: 1.0 * x == x)
    eff = tb.amp * (sigma2_d + tb.n_proc * sigma_q2)
    return tb.sigma_e2 + _bt_mmse(tb, eff) * tb.inv_kappa


def _bt_msg_sd(tb: BTTables, sigma2_hat):
    """sqrt(Var F^p) for the message mixture, closed form, in-graph."""
    p = tb.n_proc
    w1, mu1 = tb.eps, tb.mu_s / p
    var1 = (tb.sigma_s2 + p * sigma2_hat) / p**2
    var0 = sigma2_hat / p
    mean = w1 * mu1
    var = (w1 * (var1 + (mu1 - mean) ** 2)
           + (1.0 - w1) * (var0 + mean**2))
    return jnp.sqrt(var)


def _bt_rate_lookup(tb: BTTables, sigma2_hat, sigma_q2):
    """R(s2, sigma_q2) = bilinear G(log s2, log2 u) - log2 u."""
    delta = jnp.sqrt(12.0 * jnp.maximum(sigma_q2, 1e-30))
    lu = jnp.log2(delta / _bt_msg_sd(tb, sigma2_hat))
    ls = jnp.log(sigma2_hat)
    gi, gj = tb.log_s2_grid, tb.log2u_grid
    i = jnp.clip(jnp.searchsorted(gi, ls) - 1, 0, gi.shape[0] - 2)
    j = jnp.clip(jnp.searchsorted(gj, lu) - 1, 0, gj.shape[0] - 2)
    wi = jnp.clip((ls - gi[i]) / (gi[i + 1] - gi[i]), 0.0, 1.0)
    wj = jnp.clip((lu - gj[j]) / (gj[j + 1] - gj[j]), 0.0, 1.0)
    t00 = tb.gap_tab[i, j]
    t01 = tb.gap_tab[i, j + 1]
    t10 = tb.gap_tab[i + 1, j]
    t11 = tb.gap_tab[i + 1, j + 1]
    gap = ((1 - wi) * ((1 - wj) * t00 + wj * t01)
           + wi * ((1 - wj) * t10 + wj * t11))
    return gap - jnp.clip(lu, gj[0], gj[-1])


def _bt_cap_sq2(tb: BTTables, sigma2_hat):
    """sigma_Q^2 achieving rate r_max (dedicated dense 1D curve)."""
    ls = jnp.clip(jnp.log(sigma2_hat), tb.cap_ls2[0], tb.cap_ls2[-1])
    return jnp.exp(jnp.interp(ls, tb.cap_ls2, tb.cap_lsq2))


def bt_delta_for(tb: BTTables, t, sigma2_hat):
    """One in-graph BT decision: (tables, t, sigma2_hat) -> (delta, rate).

    Pure jnp over the ``BTTables`` pytree — the function ``vmap``s over a
    stacked-tables batch axis, which is what lets a heterogeneous batch mix
    per-request BT controllers inside one compiled solve.
    """
    sigma2_hat = jnp.maximum(sigma2_hat, 1e-30)
    target = tb.targets[t]
    base = _bt_predict_next(tb, sigma2_hat, 0.0)

    # bracket growth (host: hi *= 4 while predicted < target, cap 1e6)
    def grow(_, hi):
        ok = (_bt_predict_next(tb, sigma2_hat, hi) < target) & (hi <= 1e6)
        return jnp.where(ok, hi * 4.0, hi)

    hi0 = sigma2_hat / tb.n_proc + 1e-12
    hi = jax.lax.fori_loop(0, 30, grow, hi0)

    # 80-step bisection for the largest admissible sigma_Q^2
    def bisect(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = _bt_predict_next(tb, sigma2_hat, mid) <= target
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 80, bisect, (jnp.zeros_like(hi), hi))
    rate_bis = _bt_rate_lookup(tb, sigma2_hat, lo)

    sq2_cap = _bt_cap_sq2(tb, sigma2_hat)
    use_cap = (base >= target) | (rate_bis > tb.r_max)
    sq2 = jnp.where(use_cap, sq2_cap, lo)
    rate = jnp.where(use_cap, tb.r_max, rate_bis)
    return jnp.sqrt(12.0 * sq2), rate


def stack_bt_tables(tables: "list[BTTables]") -> BTTables:
    """Stack per-request tables into one leading-batch-axis pytree.

    All entries must share ``targets`` length (pad with ``pad_bt_tables``)
    and grid sizes (the constructor defaults). When every entry is the
    same object (the all-dummy / all-same-operating-point fast path) the
    batch axis is a zero-copy broadcast; otherwise the leaves are stacked
    in numpy (one host pass instead of 15*B device ops).
    """
    b = len(tables)
    if all(t is tables[0] for t in tables):
        return jax.tree.map(
            lambda x: np.broadcast_to(np.asarray(x), (b,) + x.shape),
            tables[0])
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *tables)


def pad_bt_tables(tb: BTTables, n_iter: int) -> BTTables:
    """Extend the SE target vector to ``n_iter`` (bucket T_max) by repeating
    the steady-state target; iterations past the request's t_active are
    masked out in the scan, so the padding values are never acted on."""
    cur = tb.targets.shape[0]
    if cur >= n_iter:
        return tb._replace(targets=tb.targets[:n_iter])
    pad = jnp.broadcast_to(tb.targets[-1], (n_iter - cur,))
    return tb._replace(targets=jnp.concatenate([tb.targets, pad]))


class BTRateControl:
    """In-graph BT back-tracking (paper Sec. 3.3), scan/jit/vmap-safe.

    Re-expresses ``rate_alloc.BTController`` as fixed-count jittable loops:

      * the MMSE SE map is a log-log interpolation table (same 400-point
        grid as ``make_mmse_interp``),
      * the bracket-growth ``while`` and the 80-step bisection for the
        largest admissible sigma_Q^2 become ``lax.fori_loop``s,
      * the rate model (ECSQ entropy or RD function) is a bilinear table
        over (log sigma_t^2, log2 u), u = Delta/sd(F^p), built from the
        same ``rate_alloc`` helpers the host controller calls, with a
        fixed-count bisection for the r_max cap inversion.

    Tables are built once at construction (host side) into a ``BTTables``
    pytree (``self.tables``); the per-iteration decision then runs entirely
    inside the solver scan via the pure ``bt_delta_for``.
    """

    def __init__(self, prob: CSProblem, n_proc: int, n_iter: int,
                 c_ratio: float = 1.05, r_max: float = 6.0,
                 rate_model: str = "ecsq", rd: RDModel | None = None,
                 mmse_fn=None, n_s2_grid: int = 25, n_u_grid: int = 61,
                 erasure_rate: float = 0.0, recovery: str = "retransmit"):
        host = BTController(prob, n_proc, n_iter, c_ratio, r_max,
                            rate_model, rd, mmse_fn,
                            erasure_rate=erasure_rate, recovery=recovery)
        self.host = host
        self.prob = prob
        self.n_proc = n_proc
        self.n_iter = n_iter
        self.c_ratio = c_ratio
        self.r_max = r_max
        self.erasure_rate = erasure_rate
        self.recovery = recovery
        # delivered-rate cap under the recovery policy (== r_max when
        # lossless); the in-graph tables work in delivered-rate space and
        # the serving layer applies host._wire_f for wire accounting
        eff_r_max = host._r_cap

        # (1) MMSE interp table — same grid as make_mmse_interp, evaluated
        # through the host controller's own mmse_fn so both agree.
        grid_v = np.geomspace(1e-9, 1e3, 400)
        grid_m = np.maximum(np.asarray(host.mmse_fn(grid_v), np.float64),
                            1e-300)
        log_v = jnp.asarray(np.log(grid_v), jnp.float32)
        log_m = jnp.asarray(np.log(grid_m), jnp.float32)

        # (2) per-iteration targets c * sigma_{t+1,C}^2
        targets = jnp.asarray(c_ratio * host.sigma2_c[1:], jnp.float32)

        # (3) rate table R(log s2, log2 u), u = Delta / sd(F^p | s2)
        s2_lo = max(prob.sigma_e2 * 1e-2, 1e-9)
        s2_hi = prob.sigma0_2 * 8.0
        s2_grid = np.geomspace(s2_lo, s2_hi, n_s2_grid)
        log2u_grid = np.linspace(-12.0, 5.0, n_u_grid)
        tab = np.empty((n_s2_grid, n_u_grid))
        sds = np.empty(n_s2_grid)
        for i, s2 in enumerate(s2_grid):
            sds[i] = math.sqrt(message_mixture(prob.prior, float(s2),
                                               n_proc).variance)
            for j, lu in enumerate(log2u_grid):
                delta = sds[i] * 2.0**lu
                tab[i, j] = rate_for_sigma_q2(delta**2 / 12.0, float(s2),
                                              prob, n_proc, host.rate_model,
                                              host.rd)
        log_s2_grid = jnp.asarray(np.log(s2_grid), jnp.float32)
        log2u_grid_j = jnp.asarray(log2u_grid, jnp.float32)
        # store the excess over the high-rate line, G = R + log2(u): G is
        # nearly flat where the quantizer is fine (R ~ h - log2 Delta), so
        # bilinear interpolation of G is far more accurate than of R itself
        gap_tab = jnp.asarray(tab + log2u_grid[None, :], jnp.float32)

        # (4) dedicated 1D cap curve sigma_Q^2(r_max; s2): per-row inversion
        # of the table (G is ~flat in u, so in-row accuracy ~ the host
        # inverter's own tolerance), cubic-resampled along log s2 — the
        # r_max-binding branch is where BT spends most iterations, so it
        # gets its own high-accuracy path instead of the bilinear lookup.
        from scipy.interpolate import CubicSpline
        cap_lsq2 = np.empty(n_s2_grid)
        for i in range(n_s2_grid):
            g_row = CubicSpline(log2u_grid, tab[i] + log2u_grid)
            lo, hi = log2u_grid[0], log2u_grid[-1]
            for _ in range(60):
                mid = 0.5 * (lo + hi)
                if g_row(mid) - mid > eff_r_max:
                    lo = mid
                else:
                    hi = mid
            lu_star = 0.5 * (lo + hi)
            cap_lsq2[i] = (2.0 * math.log(sds[i] * 2.0**lu_star)
                           - math.log(12.0))
        dense_ls2 = np.linspace(math.log(s2_grid[0]), math.log(s2_grid[-1]),
                                512)
        cap_dense = CubicSpline(np.log(s2_grid), cap_lsq2)(dense_ls2)

        f32 = lambda v: jnp.asarray(v, jnp.float32)
        self.tables = BTTables(
            log_v=log_v, log_m=log_m, targets=targets,
            log_s2_grid=log_s2_grid, log2u_grid=log2u_grid_j,
            gap_tab=gap_tab,
            cap_ls2=jnp.asarray(dense_ls2, jnp.float32),
            cap_lsq2=jnp.asarray(cap_dense, jnp.float32),
            sigma_e2=f32(prob.sigma_e2), inv_kappa=f32(1.0 / prob.kappa),
            n_proc=f32(float(n_proc)), eps=f32(prob.prior.eps),
            mu_s=f32(prob.prior.mu_s), sigma_s2=f32(prob.prior.sigma_s**2),
            r_max=f32(eff_r_max), amp=f32(host._amp),
        )

    def delta_for(self, t, sigma2_hat):
        return bt_delta_for(self.tables, t, sigma2_hat)


# ---------------------------------------------------------------------------
# column-layout rate control (C-MP-AMP, DESIGN.md §7)
# ---------------------------------------------------------------------------

class ColBTTables(NamedTuple):
    """In-graph state of the column-layout BT controller (pure pytree,
    stackable/vmappable exactly like ``BTTables``).

    The quantized payload is the residual contribution r^p = A_p x_p whose
    entries are ~ N(0, v_r) (``quantize.residual_mixture``), so the rate
    model collapses to a *one-dimensional* table: H_Q of a unit Gaussian
    as a function of the normalized bin u = Delta / sd(r^p).
    """

    log_v: jnp.ndarray        # (400,) MMSE interp grid, log variance
    log_m: jnp.ndarray        # (400,) log mmse values
    targets: jnp.ndarray      # (S,) c_ratio * tau_C^{s} (lossless column SE)
    log2u_grid: jnp.ndarray   # (n_u,) rate-table axis
    hq_tab: jnp.ndarray       # (n_u,) H_Q(u) of the unit Gaussian
    u_cap: jnp.ndarray        # () log2 u achieving the delivered-rate cap
    sigma_e2: jnp.ndarray     # () problem scalars -------------------
    inv_kappa: jnp.ndarray    # ()
    n_proc: jnp.ndarray       # () float
    eps: jnp.ndarray          # () prior
    mu_s: jnp.ndarray         # ()
    sigma_s2: jnp.ndarray     # ()
    r_max: jnp.ndarray        # () delivered-rate cap (erasure-adjusted)
    surv: jnp.ndarray         # () survival probability 1 - erasure_rate;
                              #    exactly 1.0 on a lossless link

    _dummies = {}  # class-level memo for dummy tables (not a field)

    @classmethod
    def dummy(cls, n_iter: int, n_u: int = 256) -> "ColBTTables":
        """Benign finite tables for non-BT instances of a mixed column
        bucket (same contract as ``BTTables.dummy``)."""
        key = (n_iter, n_u)
        if key in cls._dummies:
            return cls._dummies[key]
        f = lambda v: jnp.asarray(v, jnp.float32)
        lin = np.linspace(-20.0, 7.0, 400).astype(np.float32)
        tb = cls(
            log_v=jnp.asarray(lin), log_m=jnp.asarray(lin),
            targets=jnp.ones(n_iter, jnp.float32),
            log2u_grid=jnp.asarray(np.linspace(-12.0, 5.0, n_u), jnp.float32),
            hq_tab=jnp.ones(n_u, jnp.float32),
            u_cap=f(0.0), sigma_e2=f(1e-3), inv_kappa=f(1.0), n_proc=f(1.0),
            eps=f(0.1), mu_s=f(0.0), sigma_s2=f(1.0), r_max=f(6.0),
            surv=f(1.0),
        )
        cls._dummies[key] = tb
        return tb


def col_bt_delta_for(tb: ColBTTables, t, v_prev):
    """One in-graph column-BT decision: (tables, round, v̂_{s-1}) -> (delta,
    rate).  Pure jnp over the pytree, vmappable over stacked tables.

    The rule mirrors the row-wise BT (paper Sec. 3.3) through the column
    SE: from the previous round's fused-residual plug-in v̂ the predicted
    block MSE is d = mmse(v̂); pick the largest admissible quantizer MSE
    such that the predicted variance of this round's fused residual,

        sigma_e^2 + d / kappa  +  P * sigma_Q^2,

    stays within the target c * tau_C^{s}.  Quantization noise enters
    *additively outside* the mmse map here (it lands on g itself), so the
    admissible sigma_Q^2 is closed-form — no bisection.  The r_max cap
    inverts the 1-D Gaussian H_Q table.  Round 0 is lossless for free
    (the exchanged contributions are identically zero): delta = inf,
    rate = 0.
    """
    v_prev = jnp.maximum(v_prev, 1e-30)
    d = _bt_mmse(tb, v_prev)
    sm = tb.eps * (tb.mu_s**2 + tb.sigma_s2)
    v_r = jnp.maximum(sm - d, 1e-30) * tb.inv_kappa / tb.n_proc
    sd_r = jnp.sqrt(v_r)

    # erasure reset semantics (tb.surv == 1.0 is a bit-exact no-op): an
    # erased contribution leaves its block at x = 0, so the expected block
    # MSE entering the round is surv*d + (1-surv)*E[S0^2], and only the
    # surviving fraction injects quantization noise onto g
    d_in = tb.surv * d + (1.0 - tb.surv) * sm
    base = tb.sigma_e2 + d_in * tb.inv_kappa
    target = tb.targets[t]
    sq2_adm = jnp.maximum(target - base, 0.0) / (tb.n_proc * tb.surv)
    sq2_cap = (jnp.exp2(tb.u_cap) * sd_r) ** 2 / 12.0
    # the cap binds when the admissible bin is finer than r_max affords
    sq2 = jnp.minimum(jnp.maximum(sq2_adm, sq2_cap), v_r)
    lu = 0.5 * jnp.log2(12.0 * sq2 / v_r)
    lu_c = jnp.clip(lu, tb.log2u_grid[0], tb.log2u_grid[-1])
    rate = jnp.minimum(jnp.interp(lu_c, tb.log2u_grid, tb.hq_tab), tb.r_max)
    first = t == 0
    delta = jnp.where(first, jnp.float32(jnp.inf), jnp.sqrt(12.0 * sq2))
    return delta, jnp.where(first, 0.0, rate)


class ColumnBTRateControl:
    """In-graph BT back-tracking for the column layout, scan/jit/vmap-safe.

    Tables are built once at construction: the MMSE interp grid (same
    400-point log-log grid as ``BTRateControl``), per-round targets from
    the lossless column-wise SE reference (``se_trajectory_col``), and the
    1-D unit-Gaussian ECSQ entropy table H_Q(u) with its r_max inversion.
    Supports ``n_inner == 1`` (the serving default), where the measured
    plug-in v̂_{s-1} determines the predicted block MSE exactly; multi-
    inner-round schedules use offline allocation (``dp_allocate_col``)
    instead.
    """

    def __init__(self, prob: CSProblem, n_proc: int, n_iter: int,
                 c_ratio: float = 1.05, r_max: float = 6.0,
                 n_inner: int = 1, mmse_fn=None, n_u_grid: int = 256,
                 erasure_rate: float = 0.0, recovery: str = "retransmit"):
        assert n_inner == 1, \
            "in-graph column BT tracks the measured plug-in, which pins " \
            "the block MSE only at n_inner=1; use dp_allocate_col for " \
            "multi-inner-round rate schedules"
        from .denoisers import make_mmse_interp
        from .rate_alloc import erasure_rate_factors
        self.prob = prob
        self.n_proc = n_proc
        self.n_iter = n_iter
        self.n_inner = n_inner
        self.c_ratio = c_ratio
        self.r_max = r_max
        self.erasure_rate = erasure_rate
        self.recovery = recovery
        self.mmse_fn = mmse_fn or make_mmse_interp(prob.prior)
        budget_f, boost, wire_f = erasure_rate_factors(erasure_rate, recovery)
        self._wire_f = wire_f
        # delivered-rate cap under the recovery policy (== r_max lossless)
        eff_r_max = r_max * budget_f * boost

        grid_v = np.geomspace(1e-9, 1e3, 400)
        grid_m = np.maximum(np.asarray(self.mmse_fn(grid_v), np.float64),
                            1e-300)

        tau_c, _ = se_trajectory_col(prob, n_proc, n_iter, n_inner,
                                     mmse_fn=self.mmse_fn,
                                     erasure_rate=erasure_rate)
        targets = np.asarray(c_ratio * tau_c, np.float32)

        log2u_grid = np.linspace(-12.0, 5.0, n_u_grid)
        unit = GaussMixture(w=(1.0,), mu=(0.0,), var=(1.0,))
        hq = ecsq_entropy(2.0 ** log2u_grid, unit)
        # H_Q(u) is strictly decreasing: invert for the cap-rate bin
        u_cap = float(np.interp(eff_r_max, hq[::-1], log2u_grid[::-1]))

        f32 = lambda v: jnp.asarray(v, jnp.float32)
        self.tables = ColBTTables(
            log_v=f32(np.log(grid_v)), log_m=f32(np.log(grid_m)),
            targets=jnp.asarray(targets),
            log2u_grid=f32(log2u_grid), hq_tab=f32(hq), u_cap=f32(u_cap),
            sigma_e2=f32(prob.sigma_e2), inv_kappa=f32(1.0 / prob.kappa),
            n_proc=f32(float(n_proc)), eps=f32(prob.prior.eps),
            mu_s=f32(prob.prior.mu_s), sigma_s2=f32(prob.prior.sigma_s**2),
            r_max=f32(eff_r_max), surv=f32(1.0 - erasure_rate),
        )

    def delta_for(self, t, v_prev):
        return col_bt_delta_for(self.tables, t, v_prev)


class ColDPSchedule(FixedSchedule):
    """``dp_allocate_col`` result realized as per-round ECSQ bin sizes for
    the column layout (the column counterpart of ``DPSchedule``)."""

    def __init__(self, dp_result, prob: CSProblem, n_proc: int,
                 ecsq_gap: bool = True):
        from .rate_alloc import col_sigma_q2_for_rate
        sq2 = np.atleast_1d(col_sigma_q2_for_rate(
            dp_result.rates[1:], dp_result.sigma2_d[1:-1], prob, n_proc,
            ecsq_gap))
        super().__init__(np.concatenate([[np.inf], np.sqrt(12.0 * sq2)]))
        self.rates = np.asarray(dp_result.rates)
        self.d_traj = np.asarray(dp_result.sigma2_d)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_proc: int = 30
    n_iter: int = 10                  # iterations (row) / outer rounds (col)
    use_kernel: bool | None = None    # None = Pallas on TPU, jnp elsewhere
    kernel_interpret: bool = False    # Pallas interpret mode (CPU parity/CI)
    collect_symbols: bool = True      # trace quantizer indices (T, P, N|M)
    collect_xs: bool = True           # trace per-iteration estimates (T, N)
    layout: RowPartition | ColumnPartition = RowPartition()
    a_dtype: str = "float32"          # A storage/streaming dtype (DESIGN §8):
                                      # "bfloat16" halves HBM traffic on the
                                      # dominant operand, accumulation stays
                                      # f32 (MXU preferred_element_type)
    donate: bool = False              # donate batch operands (a_b, y_b) into
                                      # the het programs so large buckets stop
                                      # double-buffering HBM (DESIGN §9). Only
                                      # safe when callers pass temporaries —
                                      # the serving layer stacks a fresh batch
                                      # per flush, so it opts in; cached /
                                      # long-lived buffers must stay out of
                                      # donating programs.

    @property
    def is_col(self) -> bool:
        return isinstance(self.layout, ColumnPartition)

    @property
    def a_jdtype(self):
        assert self.a_dtype in ("float32", "bfloat16"), self.a_dtype
        return jnp.bfloat16 if self.a_dtype == "bfloat16" else jnp.float32

    @property
    def kernel_on(self) -> bool:
        """Whether the LC step routes through the Pallas kernel suite
        (compiled on TPU, interpret mode anywhere when asked)."""
        if self.use_kernel is None:
            return jax.default_backend() == "tpu"
        return self.use_kernel


class HetParams(NamedTuple):
    """Per-instance operands of a heterogeneous batch (``solve_het``).

    Every field carries a leading batch axis B when passed to ``solve_het``
    (shapes below are per-instance). Together with the per-instance sensing
    shards, these are the quantities the serving layer varies *inside* one
    compiled solve; everything structural (padded M/N, P, T_max, transport)
    is part of the bucket key instead.
    """

    sched: jnp.ndarray     # (T,) fixed/DP bin sizes (inf = lossless)
    t_active: jnp.ndarray  # () int32: iterations to run (masked early-exit)
    m_real: jnp.ndarray    # () f32: true measurement count (sigma2_hat norm)
    n_real: jnp.ndarray    # () int32: true signal length (column mask)
    eps: jnp.ndarray       # () f32 prior sparsity
    mu_s: jnp.ndarray      # () f32 prior mean
    sigma_s: jnp.ndarray   # () f32 prior std
    use_bt: jnp.ndarray    # () bool: BT controller vs fixed schedule
    bt: BTTables           # stacked in-graph BT tables (dummy when !use_bt)
    drop: jnp.ndarray | None = None
                           # (T, P) erasure mask, 1 = fusion packet lost
                           # (sharded placement: (T, n_dev), replicated).
                           # None is an *empty pytree node*, so drop-free
                           # batches keep the pre-erasure operand avals
                           # and programs byte-identical.


@dataclasses.dataclass
class EngineTrace:
    """Per-iteration record of one engine solve (arrays are numpy on exit)."""

    x: np.ndarray                 # final estimate (N,) / (B, N)
    sigma2_hat: np.ndarray        # plug-in sigma_{t,D}^2, post-LC (T,)
    deltas: np.ndarray            # realized bin sizes (T,)
    extra_var: np.ndarray         # transport-injected variance P*sigma_Q^2 (T,)
    rates: np.ndarray             # controller-chosen rate (T,), inf = untracked
    symbols: np.ndarray | None    # quantizer indices (T, P, N)
    xs: np.ndarray | None         # per-iteration estimates (T, N)

    def mse(self, s0: np.ndarray) -> np.ndarray:
        """Per-iteration MSE against ground truth (batched-aware)."""
        assert self.xs is not None, "solve with collect_xs=True"
        return np.mean((self.xs - np.asarray(s0)[..., None, :]) ** 2, axis=-1)


class AmpEngine:
    """One scan-compiled MP-AMP solver core with pluggable transports and
    in-graph rate control. See module docstring."""

    def __init__(self, prior: BernoulliGauss, cfg: EngineConfig,
                 transport: Transport | None = None,
                 controller=None):
        self.prior = prior
        self.cfg = cfg
        self.transport = transport if transport is not None else ExactFusion()
        if controller is None:
            controller = FixedSchedule(np.full(cfg.n_iter, np.inf))
        self.controller = controller
        self._jit_cache: dict = {}
        # program-builder cache lock: builders nest (solve_many's vmap
        # build calls _scan_fn), hence re-entrant. Background prewarm and
        # foreground flush() race these dicts otherwise — see _cached.
        self._build_lock = threading.RLock()
        # AOT executable cache (DESIGN §9): (program key, operand-aval key)
        # -> jax Compiled. Owning the cache (instead of leaning on jit's
        # internal one) makes compiles *observable* — ``compile_count`` is
        # the serving layer's zero-steady-state-recompile invariant — and
        # lets ``prewarm``/``compile_het`` populate it ahead of traffic.
        self._exec_cache: dict = {}
        self._exec_lock = threading.Lock()
        self.compile_count = 0
        # executed dispatches (compile_only excluded): the per-engine load
        # signal the cluster router's imbalance accounting reads. Guarded
        # by _exec_lock together with compile_count so ``counters()`` can
        # hand out a consistent (compiles, dispatches) pair even while a
        # background prewarm thread is mid-compile.
        self.dispatch_count = 0

    # -- AOT executable cache (DESIGN §9) ------------------------------------

    @staticmethod
    def _exec_key(args) -> tuple:
        """Aval fingerprint of a concrete operand pytree: (shape, dtype,
        weak_type, sharding token) per leaf. numpy operands and default
        single-device jax arrays share the ``None`` sharding token — a
        program compiled from numpy dummies at prewarm serves jnp runtime
        operands of the same avals; explicitly sharded operands (the
        data-parallel placement) key on ``str(sharding)``."""
        toks = []
        for x in jax.tree_util.tree_leaves(args):
            sh = getattr(x, "sharding", None)
            tok = None if sh is None or isinstance(sh, SingleDeviceSharding) \
                else str(sh)
            dt = getattr(x, "dtype", None)
            toks.append((tuple(np.shape(x)),
                         str(dt) if dt is not None else str(np.result_type(x)),
                         bool(getattr(x, "weak_type", False)), tok))
        return tuple(toks)

    def _run(self, base_key, fn, args, compile_only: bool = False):
        """Execute ``fn(*args)`` through the AOT cache: first sight of a
        (program, avals) pair pays ``lower().compile()`` exactly once (and
        bumps ``compile_count``); every later call reuses the Compiled.
        ``compile_only`` returns the executable without running it — the
        prewarm path. Thread-safe: background prewarm and foreground
        dispatch serialize on the compile lock, never duplicate work."""
        key = (base_key, self._exec_key(args))
        ex = self._exec_cache.get(key)
        if ex is None:
            with self._exec_lock:
                ex = self._exec_cache.get(key)
                if ex is None:
                    with warnings.catch_warnings():
                        # donation feasibility is a compile-time XLA note
                        # (e.g. scalar operands can't alias outputs); it
                        # is expected, not actionable
                        warnings.filterwarnings(
                            "ignore", message=".*[Dd]onat.*")
                        ex = fn.lower(*args).compile()
                    self._exec_cache[key] = ex
                    self.compile_count += 1
        if compile_only:
            return ex
        with self._exec_lock:
            self.dispatch_count += 1
        return ex(*args)

    def counters(self) -> dict:
        """Atomic snapshot of the engine's observable counters. Taken
        under the executable-cache lock, so a concurrent compile (e.g. a
        background ``SolveService.prewarm`` thread) can never be observed
        half-way — ``SolveService.stats()`` aggregates through here."""
        with self._exec_lock:
            return {"compiles": self.compile_count,
                    "dispatches": self.dispatch_count}

    def _cached(self, key, build):
        """Double-checked admission into the jit-program cache.

        Every program builder routes here so a background ``prewarm``
        thread and a foreground dispatch can never observe a half-built
        entry, build the same program twice, or drop each other's insert
        (plain ``if key not in dict`` admission loses one of two racing
        builds). The lock is re-entrant because builders nest — the
        vmapped solve builds wrap ``_scan_fn``/``_col_scan_fn``."""
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._build_lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    fn = build()
                    self._jit_cache[key] = fn
        return fn

    # -- shared iteration body ----------------------------------------------

    def _local(self, x, z_p, onsager, a_p, y_p, m_eff=None, axis=None):
        """LC: the whole processor stack through one batched-grid fused op.

        ``a_p`` may be tile-padded (kernel path; ``pad_row_shards`` at
        solve entry) and/or stored in ``cfg.a_dtype``: the carry ``x``
        stays at the true N, so the body pads only the (N,) message vector
        (never the (M, N) operand) and slices ``f_p`` back — padded rows/
        columns are exactly zero end-to-end, so the fused sum-of-squares
        is the true sigma2_hat numerator. ``m_eff`` overrides the
        normalizer (the heterogeneous path passes the *real* measurement
        count); ``axis`` (sharded mode) makes the plug-in a psum over the
        mesh axis — one kernel launch per device covers its P/D emulated
        processors.
        """
        cfg = self.cfg
        m = a_p.shape[0] * a_p.shape[1] if m_eff is None else m_eff
        n, n_pad = x.shape[0], a_p.shape[2]
        x_in = jnp.pad(x, (0, n_pad - n)) if n_pad != n else x
        z_new, f_p, ss = amp_local_grid(
            a_p, x_in, y_p, z_p, onsager, cfg.n_proc,
            use_pallas=cfg.kernel_on, interpret=cfg.kernel_interpret)
        if n_pad != n:
            f_p = f_p[:, :n]
        if axis is not None:
            ss = lax.psum(ss, axis)
        sigma2_hat = ss / m
        return z_new, f_p, sigma2_hat

    def _fuse(self, f_p, delta, drop=None):
        """Transport dispatch. ``drop`` None compiles the drop-free
        program (emulated transports only — byte-identical to the
        pre-erasure engine); non-None it is the erasure/straggler mask:
        per-device scalar for device-collective transports, per-processor
        (P,) for the emulated ones."""
        if drop is None:
            assert not hasattr(self.transport, "axis"), \
                f"{type(self.transport).__name__} is a device-collective " \
                "transport: solve via solve_sharded/solve_sharded_het, " \
                "not the emulated entry points"
            return self.transport.fuse(f_p, delta)
        return self.transport.fuse(f_p, delta, drop)

    def _gc(self, f_p, sigma2_hat, delta, kappa, drop=None):
        """GC: compress + fuse + denoise. Returns (x, onsager, extra, syms)."""
        f, extra, syms = self._fuse(f_p, delta, drop)
        x_new, onsager_new = amp_gc_step(f, sigma2_hat + extra, self.prior,
                                         kappa)
        return x_new, onsager_new, extra, syms

    def _body(self, carry, xs_t, a_p, y_p, kappa, axis=None, m_eff=None):
        if axis is None:
            # erasure-enabled emulated programs thread a (P,) drop mask as
            # a third scan operand; the 2-tuple form is the drop-free
            # program, byte-identical to the pre-erasure engine
            if len(xs_t) == 3:
                t, sched_delta, drop = xs_t
            else:
                (t, sched_delta), drop = xs_t, None
        else:
            t, sched_delta, drop = xs_t
        x, z_p, onsager = carry
        z_p, f_p, s2 = self._local(x, z_p, onsager, a_p, y_p, m_eff=m_eff,
                                   axis=axis)
        if isinstance(self.controller, FixedSchedule):
            # fixed schedules arrive as a scan operand, so one compiled
            # solve serves every schedule of the same length
            delta, rate = sched_delta, jnp.float32(jnp.inf)
        else:
            delta, rate = self.controller.delta_for(t, s2)
        x_new, onsager_new, extra, syms = self._gc(f_p, s2, delta, kappa,
                                                   drop=drop)
        cfg = self.cfg
        out = (s2, delta, extra, rate,
               x_new if cfg.collect_xs else jnp.zeros(()),
               syms if cfg.collect_symbols else jnp.zeros(()))
        return (x_new, z_p, onsager_new), out

    def _sched_operand(self):
        if isinstance(self.controller, FixedSchedule):
            deltas = self.controller.deltas[:self.cfg.n_iter]
            assert len(deltas) == self.cfg.n_iter, \
                f"schedule has {len(self.controller.deltas)} entries, " \
                f"need {self.cfg.n_iter}"
            return jnp.asarray(deltas, jnp.float32)
        return jnp.zeros(self.cfg.n_iter, jnp.float32)

    # -- column-layout iteration body (C-MP-AMP, DESIGN.md §7) ---------------

    def _check_col_controller(self):
        assert isinstance(self.controller,
                          (FixedSchedule, ColumnBTRateControl)), \
            "the column layout takes a FixedSchedule/ColDPSchedule or a " \
            "ColumnBTRateControl (row-wise controllers predict through " \
            f"the wrong SE), got {type(self.controller).__name__}"

    def _col_gather_x(self, x, axis):
        """Local (P, N/P) signal slices -> the flat (N,) estimate; in
        sharded mode the slices are gathered across the mesh axis first."""
        if axis is not None:
            x = lax.all_gather(x, axis)
        return x.reshape(-1)

    def _col_init(self, p_loc: int, np_: int, y, v0):
        """Initial column scan carry ``(x, mem, coef, v_prev)``.

        ``mem``/``coef`` are the Onsager boundary memory: the previous
        fused residual (M,) + summed coefficients () in fused mode, the
        per-processor residuals (P, M) + own coefficients (P,) in
        per-processor mode (``ColumnPartition`` docstring)."""
        x = jnp.zeros((p_loc, np_), jnp.float32)
        if self.cfg.layout.carry_fused:
            return (x, jnp.zeros_like(y), jnp.zeros(()), v0)
        return (x, jnp.zeros((p_loc,) + y.shape, jnp.float32),
                jnp.zeros(p_loc, jnp.float32), v0)

    def _col_prior_params(self, hp: HetParams | None = None):
        """(eps, mu_s, sigma_s^2) as traced/array scalars for the fused
        column kernels — from ``HetParams`` when given, else the engine's
        static prior."""
        if hp is not None:
            return hp.eps, hp.mu_s, hp.sigma_s**2
        pr = self.prior
        # the fused kernel evaluates the BG conditional mean in closed
        # form in-kernel — it cannot honor an arbitrary denoiser, so make
        # the coupling explicit rather than silently diverging from the
        # eta_fn the jnp path would have used
        assert isinstance(pr, BernoulliGauss), \
            f"column kernel path requires a BernoulliGauss prior, got " \
            f"{type(pr).__name__}; solve with use_kernel=False"
        return (jnp.float32(pr.eps), jnp.float32(pr.mu_s),
                jnp.float32(pr.sigma_s**2))

    def _col_inner_kernels(self, x, g, z_p, a_cp, m_eff, pp, n_mask):
        """Kernel-path counterpart of ``_col_inner``: ``layout.n_inner``
        fused ``col_inner_step`` launches (message + in-kernel denoise +
        residual update; DESIGN.md §8). ``pp`` is ``_col_prior_params``;
        ``n_mask`` a (Np,) real-column mask (all-ones when unpadded)."""
        cfg = self.cfg
        n_inner = cfg.layout.n_inner
        x0 = x
        c_p = None
        for t in range(n_inner):
            x, c_p, z_p = col_inner_step(
                a_cp, x, x0, z_p, g, n_mask, m_eff, *pp,
                update_z=t + 1 < n_inner, use_pallas=cfg.kernel_on,
                interpret=cfg.kernel_interpret)
        return x, c_p, z_p

    def _col_inner(self, x, g, z_p, a_cp, m_eff, eta_fn, n_mask=None):
        """``layout.n_inner`` local AMP iterations at each processor on the
        fused residual ``g`` (C-MP-AMP inner stage).

        Per inner step at processor p (all pure per-processor math):
            sigma_p^2 = ||z_p||^2 / M            (plug-in)
            f_p = x_p + A_p^T z_p
            x_p <- eta(f_p, sigma_p^2)
            z_p <- g - A_p (x_p - x_p^0) + c_p z_p,  c_p = sum(eta') / M

        ``z_p`` is the round's starting residual stack (P, M).  Returns
        ``(x, c_p, z_last)`` with ``z_last`` the residual that *fed* the
        final denoise — the quantity AMP's Onsager term multiplies, which
        is what the per-processor boundary carry needs (the fused boundary
        mode discards it).  ``n_inner`` is static, so the loop unrolls
        into the round's scan body.
        """
        n_inner = self.cfg.layout.n_inner
        x0 = x
        for t in range(n_inner):
            s2_p = jnp.sum(z_p * z_p, axis=-1, keepdims=True) / m_eff
            fn = lambda v, s2=s2_p: eta_fn(v, s2)
            f_p = x + jnp.einsum("pmn,pm->pn", a_cp, z_p, precision=_HI)
            if n_mask is None:
                x_new = fn(f_p)
                deriv = jax.grad(lambda v: jnp.sum(fn(v)))(f_p)
            else:
                x_new = fn(f_p) * n_mask
                deriv = jax.grad(lambda v: jnp.sum(fn(v) * n_mask))(f_p)
            c_p = jnp.sum(deriv, axis=-1) / m_eff
            if t + 1 < n_inner:
                z_p = (g[None, :]
                       - jnp.einsum("pmn,pn->pm", a_cp, x_new - x0,
                                    precision=_HI)
                       + c_p[:, None] * z_p)
            x = x_new
        return x, c_p, z_p

    def _col_round(self, x, mem, coef, delta, a_cp, y, m_eff, eta_fn,
                   n_mask=None, drop=None, axis=None, pp=None):
        """Shared round computation: fuse, apply the boundary Onsager
        memory, run the inner stage.  Returns the new carry pieces plus
        the round's trace quantities ``(v_hat, extra, syms)``.

        On the kernel path (``cfg.kernel_on``) the residual contributions
        and the inner stage run as fused Pallas launches (``col_residual``
        / ``col_inner_step``); ``pp`` carries the prior scalars the
        in-kernel denoiser needs and ``n_mask`` must then be a (Np,)
        real-column mask. M may be tile-padded: padded rows of A/y are
        zero, so every padded entry of r/g/z is exactly zero and the
        transports (0 -> 0) and the v_hat sum are unaffected.
        """
        kern = self.cfg.kernel_on
        er_keep = None
        if drop is not None:
            # Column erasure is a *reset*, not a rescale (DESIGN.md §10):
            # an erased contribution leaves its whole signal block
            # unexplained in the fused residual, so zeroing the block's
            # estimate before forming r_p is the only self-consistent
            # round — r_p vanishes exactly, the inner stage restarts the
            # block from x = 0 against the fused residual, and the next
            # round re-fuses it in full. A survivor rescale would be both
            # biased (the r_p are independent zero-mean blocks, not
            # estimates of r/P) and higher-variance than zeroing. The
            # boundary Onsager coefficient scales with the surviving
            # fraction: an erased block's jump correction never crossed
            # the wire.
            er_keep = 1.0 - drop
            if axis is None:
                x = x * er_keep[:, None]
                coef = (coef * jnp.mean(er_keep)
                        if self.cfg.layout.carry_fused else coef * er_keep)
                # the emulated transports' row-style survivor rescale must
                # not trigger on the already-zeroed contributions
                drop = None
            else:
                x = x * er_keep
                if self.cfg.layout.carry_fused:
                    coef = coef * (lax.psum(er_keep, axis)
                                   / lax.axis_size(axis))
                else:
                    coef = coef * er_keep
                # likewise neutralize the device collectives' rescale
                drop = drop * 0.0
        if kern:
            r_p = col_residual(a_cp, x, use_pallas=True,
                               interpret=self.cfg.kernel_interpret)
        else:
            r_p = jnp.einsum("pmn,pn->pm", a_cp.astype(jnp.float32), x,
                             precision=_HI)
        r, extra, syms = self._fuse(r_p, delta, drop)
        if er_keep is not None:
            # only the delivered packets inject quantization noise (an
            # erased processor's zero block quantizes to exactly zero)
            if axis is None:
                extra = extra * (jnp.sum(er_keep) / r_p.shape[0])
            else:
                extra = extra * (lax.psum(er_keep, axis) / lax.axis_size(axis))
        g = y - r
        # boundary Onsager correction sum_q c_q z_q^last (ColumnPartition
        # docstring); scalar * previous-g on the n_inner == 1 fast path
        if self.cfg.layout.carry_fused:
            g = g + coef * mem
        else:
            corr = jnp.einsum("p,pm->m", coef, mem, precision=_HI)
            if axis is not None:
                corr = lax.psum(corr, axis)
            g = g + corr
        # g is replicated across shards post-fusion: no psum needed
        v_hat = jnp.sum(g * g) / m_eff
        z0 = jnp.broadcast_to(g, x.shape[:1] + g.shape)
        if kern:
            km = (jnp.ones(a_cp.shape[2], jnp.float32) if n_mask is None
                  else n_mask.reshape(-1))
            x_new, c_p, z_last = self._col_inner_kernels(
                x, g, z0, a_cp, m_eff,
                self._col_prior_params() if pp is None else pp, km)
        else:
            x_new, c_p, z_last = self._col_inner(x, g, z0, a_cp, m_eff,
                                                 eta_fn, n_mask=n_mask)
        if self.cfg.layout.carry_fused:
            coef_new = jnp.sum(c_p)
            if axis is not None:
                coef_new = lax.psum(coef_new, axis)
            mem_new = g
        else:
            mem_new, coef_new = z_last, c_p
        return x_new, mem_new, coef_new, v_hat, extra, syms

    def _col_body(self, carry, xs_t, a_cp, y, m_eff, axis=None):
        """One C-MP-AMP outer round: fuse quantized residual contributions,
        then run the inner stage.

        The scan carry is ``(x, mem, coef, v_prev)``: the per-processor
        signal slices, the Onsager boundary memory (``_col_init``), and
        the previous round's plug-in ``||g||^2/M`` — the column controller
        input (round 0 is lossless for free, so the controller always has
        a measured variance to act on).
        """
        if axis is None:
            if len(xs_t) == 3:
                s, sched_delta, drop = xs_t
            else:
                (s, sched_delta), drop = xs_t, None
        else:
            s, sched_delta, drop = xs_t
        x, mem, coef, v_prev = carry
        if isinstance(self.controller, FixedSchedule):
            delta, rate = sched_delta, jnp.float32(jnp.inf)
        else:
            delta, rate = self.controller.delta_for(s, v_prev)
        prior = self.prior
        x_new, mem_new, coef_new, v_hat, extra, syms = self._col_round(
            x, mem, coef, delta, a_cp, y, m_eff,
            lambda v, s2: eta(v, s2, prior, xp=jnp), drop=drop, axis=axis)
        # round 0 quantizes all-zero contributions exactly: no noise
        # actually enters g, whatever bin the schedule names — keep the
        # trace's accounting truthful
        extra = jnp.where(s == 0, 0.0, extra)
        cfg = self.cfg
        out = (v_hat, delta, extra, rate,
               self._col_gather_x(x_new, axis) if cfg.collect_xs
               else jnp.zeros(()),
               syms if cfg.collect_symbols else jnp.zeros(()))
        return (x_new, mem_new, coef_new, v_hat), out

    # -- compiled entry points ----------------------------------------------

    def _scan_fn(self, m: int, n: int, erasure: bool = False):
        """Build (once per shape) the jitted full-solve scan. ``m``/``n``
        are the *true* problem dims; operands may arrive tile-padded.
        ``erasure`` programs take a (T, P) drop mask as a fourth operand
        (threaded as a third scan input); the drop-free program stays
        byte-identical to the pre-erasure engine."""

        def build():
            cfg, kappa = self.cfg, m / n

            def solve_core(a_p, y_p, sched, drops=None):
                init = (jnp.zeros(n, jnp.float32), jnp.zeros_like(y_p),
                        jnp.zeros(()))
                body = lambda c, xs: self._body(c, xs, a_p, y_p, kappa,
                                                m_eff=jnp.float32(m))
                xs = (jnp.arange(cfg.n_iter), sched)
                if drops is not None:
                    xs = xs + (drops,)
                (x, _, _), outs = jax.lax.scan(body, init, xs)
                return x, outs

            if erasure:
                return jax.jit(lambda a_p, y_p, sched, drops:
                               solve_core(a_p, y_p, sched, drops))
            return jax.jit(solve_core)

        return self._cached(("scan", m, n, erasure), build)

    def _step_fns(self, m: int, n: int):
        """Jitted single-iteration (LC, GC) pair for host-loop mode — the
        same body as the scan, sliced at the LC/GC boundary so an online
        host-side controller can observe sigma_hat_{t,D}^2."""

        def build():
            kappa = m / n
            local = jax.jit(lambda x, z_p, ons, a_p, y_p: self._local(
                x, z_p, ons, a_p, y_p, m_eff=jnp.float32(m)))
            gc = jax.jit(lambda f_p, s2, delta: self._gc(f_p, s2, delta,
                                                         kappa))
            return (local, gc)

        return self._cached(("step", m, n), build)

    def _split(self, y, a_mat):
        """Row-split (A, y); on the kernel path, tile-align once here —
        host-side, so no pad of the (M, N) operand enters the program."""
        a_p, y_p = split_problem(np.asarray(a_mat, np.float32),
                                 np.asarray(y, np.float32), self.cfg.n_proc)
        if self.cfg.kernel_on:
            a_p, y_p = pad_row_shards(a_p, y_p, self.cfg.a_jdtype)
        return (jnp.asarray(a_p, self.cfg.a_jdtype), jnp.asarray(y_p))

    def _split_col(self, y, a_mat):
        """Column-split A (shared y); kernel path tile-aligns M here."""
        a_cp = split_problem_cols(np.asarray(a_mat, np.float32),
                                  self.cfg.n_proc)
        y = np.asarray(y, np.float32)
        if self.cfg.kernel_on:
            a_cp, y = pad_col_shards(a_cp, y)
        return jnp.asarray(a_cp, self.cfg.a_jdtype), jnp.asarray(y)

    def _col_scan_fn(self, m: int, n: int, erasure: bool = False):
        """Build (once per shape) the jitted full-solve column scan.
        ``erasure`` as in ``_scan_fn`` (mask shape (T, P); column reset
        semantics — ``_col_round``)."""

        def build():
            cfg = self.cfg
            p = cfg.n_proc

            def solve_core(a_cp, y, sched, drops=None):
                np_ = a_cp.shape[2]
                init = self._col_init(p, np_, y, jnp.sum(y * y) / m)
                body = lambda c, xs: self._col_body(c, xs, a_cp, y,
                                                    jnp.float32(m))
                xs = (jnp.arange(cfg.n_iter), sched)
                if drops is not None:
                    xs = xs + (drops,)
                (x, _, _, _), outs = jax.lax.scan(body, init, xs)
                return x.reshape(-1), outs

            if erasure:
                return jax.jit(lambda a_cp, y, sched, drops:
                               solve_core(a_cp, y, sched, drops))
            return jax.jit(solve_core)

        return self._cached(("col", m, n, erasure), build)

    def _solve_col(self, y, a_mat, drop_sched=None) -> EngineTrace:
        self._check_col_controller()
        m, n = np.shape(a_mat)             # true dims; _split_col may pad M
        a_cp, yj = self._split_col(y, a_mat)
        if drop_sched is None:
            x, outs = self._col_scan_fn(m, n)(a_cp, yj,
                                              self._sched_operand())
        else:
            drop_sched = np.asarray(drop_sched, np.float32)
            assert drop_sched.shape == (self.cfg.n_iter, self.cfg.n_proc), \
                drop_sched.shape
            x, outs = self._col_scan_fn(m, n, erasure=True)(
                a_cp, yj, self._sched_operand(), jnp.asarray(drop_sched))
        return self._trace(x, outs)

    def _solve_many_col(self, ys, a_mats) -> EngineTrace:
        self._check_col_controller()
        ys = np.asarray(ys, np.float32)
        a_mats = np.asarray(a_mats, np.float32)
        shared_a = a_mats.ndim == 2
        b = ys.shape[0]
        p = self.cfg.n_proc
        m, n = a_mats.shape[-2:]
        if shared_a:
            a_b = split_problem_cols(a_mats, p)
        else:
            assert a_mats.shape[0] == b
            a_b = np.stack(
                [split_problem_cols(a_mats[i], p) for i in range(b)])
        if self.cfg.kernel_on:
            a_b, ys = pad_col_shards(a_b, ys)
        a_b = jnp.asarray(a_b, self.cfg.a_jdtype)
        y_b = jnp.asarray(ys)
        def build():
            fn = self._col_scan_fn(m, n)
            in_axes = (None, 0, None) if shared_a else (0, 0, None)
            return jax.jit(jax.vmap(fn, in_axes=in_axes))

        vfn = self._cached(("col_vmap", m, n, shared_a), build)
        x, outs = vfn(a_b, y_b, self._sched_operand())
        return self._trace(x, outs)

    def _trace(self, x, outs) -> EngineTrace:
        cfg = self.cfg
        s2, deltas, extra, rates, xs, syms = outs
        return EngineTrace(
            x=np.asarray(x),
            sigma2_hat=np.asarray(s2),
            deltas=np.asarray(deltas),
            extra_var=np.asarray(extra),
            rates=np.asarray(rates),
            symbols=np.asarray(syms) if cfg.collect_symbols else None,
            xs=np.asarray(xs) if cfg.collect_xs else None,
        )

    def dispatch_single(self, a_p, y_p, m: int, n: int, sched=None,
                        drop_sched=None, compile_only: bool = False):
        """Launch one plain (row-layout, homogeneous) solve from pre-split
        operands, returning raw ``(x, outs)`` — the serving layer's
        singleton fast path: a lone request skips batch padding and
        het-operand assembly entirely and runs the true-dims ``_scan_fn``
        program through the AOT executable cache. ``sched`` overrides the
        engine controller's schedule operand (lossless/fixed/DP deltas ride
        here); ``drop_sched`` a (T, P) erasure mask (``ErasureSpec``),
        routed to the erasure-enabled program variant; ``a_p`` may be a
        long-lived cached device buffer — this path never donates."""
        assert not self.cfg.is_col, \
            "dispatch_single is a row-layout entry point"
        # keep host operands as numpy: the compiled call's shard_args path
        # uploads them cheaper than an eager device_put per operand, and
        # an already-resident cached a_p passes through untouched
        if getattr(a_p, "dtype", None) != self.cfg.a_jdtype:
            a_p = np.asarray(a_p, np.float32) \
                if isinstance(a_p, np.ndarray) and self.cfg.a_dtype == "float32" \
                else jnp.asarray(a_p, self.cfg.a_jdtype)
        y_p = np.asarray(y_p, np.float32)
        if sched is None:
            sched = self._sched_operand()
        sched = np.asarray(sched, np.float32)
        assert sched.shape == (self.cfg.n_iter,), \
            (sched.shape, self.cfg.n_iter)
        erasure = drop_sched is not None
        args = (a_p, y_p, sched)
        if erasure:
            drop_sched = np.asarray(drop_sched, np.float32)
            assert drop_sched.shape == (self.cfg.n_iter, self.cfg.n_proc), \
                drop_sched.shape
            args = args + (drop_sched,)
        return self._run(("scan", m, n, erasure),
                         self._scan_fn(m, n, erasure), args, compile_only)

    def solve(self, y, a_mat, drop_sched=None) -> EngineTrace:
        """Full T-iteration solve as one scan-compiled call (no host sync).

        Under a ``ColumnPartition`` layout this is the full outer-round
        C-MP-AMP solve (``cfg.n_iter`` fusion exchanges).

        ``drop_sched`` (T, P) optionally marks erased fusion packets per
        iteration (sample one with ``ErasureSpec.sample_mask``): the row
        layout rescales the survivors unbiasedly, the column layout resets
        the erased signal blocks (DESIGN.md §10). ``None`` runs the
        pre-erasure program unchanged."""
        if self.cfg.is_col:
            return self._solve_col(y, a_mat, drop_sched)
        m, n = np.shape(a_mat)             # true dims; _split may tile-pad
        a_p, y_p = self._split(y, a_mat)
        return self._trace(*self.dispatch_single(a_p, y_p, m, n,
                                                 drop_sched=drop_sched))

    def solve_many(self, ys, a_mats) -> EngineTrace:
        """vmap-batched solve of B independent CS instances.

        ys (B, M); a_mats (B, M, N) or a single shared (M, N) matrix.
        Symbol collection is typically disabled for batches (memory).
        """
        if self.cfg.is_col:
            return self._solve_many_col(ys, a_mats)
        ys = np.asarray(ys, np.float32)
        a_mats = np.asarray(a_mats, np.float32)
        shared_a = a_mats.ndim == 2
        b = ys.shape[0]
        p = self.cfg.n_proc
        m, n = a_mats.shape[-2:]
        assert m % p == 0, f"M={m} not divisible by P={p}"
        mp_ = m // p
        if shared_a:
            a_b = a_mats.reshape(p, mp_, n)
        else:
            assert a_mats.shape[0] == b
            a_b = a_mats.reshape(b, p, mp_, n)
        y_b = ys.reshape(b, p, mp_)
        if self.cfg.kernel_on:
            a_b, _ = pad_row_shards(a_b, None, self.cfg.a_jdtype)
            if a_b.shape[-2] != mp_:
                y_b = np.pad(y_b,
                             ((0, 0), (0, 0), (0, a_b.shape[-2] - mp_)))
        a_b = jnp.asarray(a_b, self.cfg.a_jdtype)
        y_b = jnp.asarray(y_b)

        def build():
            fn = self._scan_fn(m, n)
            in_axes = (None, 0, None) if shared_a else (0, 0, None)
            return jax.jit(jax.vmap(fn, in_axes=in_axes))

        vfn = self._cached(("vmap", m, n, shared_a), build)
        x, outs = vfn(a_b, y_b, self._sched_operand())
        return self._trace(x, outs)

    # -- heterogeneous batches (the serving path) -----------------------------

    def _body_het(self, carry, xs_t, a_p, y_p, hp: HetParams, n_mask,
                  has_bt: bool, axis=None):
        """One masked iteration with per-instance (traced) problem params.

        Same LC/GC split as ``_body``; differences: sigma2_hat normalizes by
        the real M, the denoiser runs with traced prior parameters, the
        Onsager mean covers only real columns, the quantizer bin comes from
        either the per-instance schedule operand or the per-instance BT
        tables, and the carry freezes once ``t >= t_active`` (masked
        early-exit: short requests return their own T-iteration fixpoint
        regardless of the bucket's T_max). ``has_bt`` is static: batches
        with no BT request compile without the in-graph controller.
        ``axis`` runs the body processor-sharded (the same shard_map mode as
        ``_body``; HetParams ride replicated).
        """
        if axis is None:
            if len(xs_t) == 3:
                t, sched_delta, drop = xs_t
            else:
                (t, sched_delta), drop = xs_t, None
        else:
            t, sched_delta, drop = xs_t
        x, z_p, onsager = carry
        z_new, f_p, s2 = self._local(x, z_p, onsager, a_p, y_p,
                                     m_eff=hp.m_real, axis=axis)

        if has_bt:
            bt_delta, bt_rate = bt_delta_for(hp.bt, t, s2)
            delta = jnp.where(hp.use_bt, bt_delta, sched_delta)
            rate = jnp.where(hp.use_bt, bt_rate, jnp.float32(jnp.inf))
        else:
            delta, rate = sched_delta, jnp.float32(jnp.inf)

        f, extra, syms = self._fuse(f_p, delta, drop)
        v = s2 + extra
        eta_fn = lambda g: eta_bg(g, v, hp.eps, hp.mu_s, hp.sigma_s**2)
        x_new = eta_fn(f) * n_mask
        # Onsager: mean(eta') over real columns / kappa == sum(eta'*mask)/M
        deriv = jax.grad(lambda g: jnp.sum(eta_fn(g) * n_mask))(f)
        onsager_new = jnp.sum(deriv) / hp.m_real

        act = t < hp.t_active
        x1 = jnp.where(act, x_new, x)
        z1 = jnp.where(act, z_new, z_p)
        ons1 = jnp.where(act, onsager_new, onsager)
        cfg = self.cfg
        out = (jnp.where(act, s2, 0.0), jnp.where(act, delta, 0.0),
               jnp.where(act, extra, 0.0),
               jnp.where(act, rate, jnp.float32(jnp.inf)),
               x1 if cfg.collect_xs else jnp.zeros(()),
               syms if cfg.collect_symbols else jnp.zeros(()))
        return (x1, z1, ons1), out

    def _scan_fn_het(self, mp_: int, n: int, has_bt: bool,
                     has_er: bool = False):
        """Jitted vmapped heterogeneous-batch solve for one padded shape.

        On the kernel path the bucket-shaped operands are tile-aligned
        *once here* — one pad at solve entry, outside the vmapped scan —
        and ``A`` is cast to ``cfg.a_dtype``. The carry rides at the
        bucket's n, so results keep their bucket shapes. ``has_er``
        (static, derived from ``params.drop is not None``) threads the
        per-instance (T, P) erasure masks as a third scan operand; the
        drop-free program is byte-identical to the pre-erasure engine."""

        def build():
            cfg = self.cfg

            def solve_one(a_p, y_p, hp: HetParams):
                n_mask = (jnp.arange(n) < hp.n_real).astype(jnp.float32)
                init = (jnp.zeros(n, jnp.float32), jnp.zeros_like(y_p),
                        jnp.zeros(()))
                body = lambda c, xs: self._body_het(c, xs, a_p, y_p, hp,
                                                    n_mask, has_bt)
                xs = (jnp.arange(cfg.n_iter), hp.sched)
                if has_er:
                    xs = xs + (hp.drop,)
                (x, _, _), outs = jax.lax.scan(body, init, xs)
                return x, outs

            def solve_batch(a_b, y_b, hp: HetParams):
                if cfg.kernel_on:
                    a_b, y_b = pad_row_shards(a_b, y_b, cfg.a_jdtype)
                return jax.vmap(solve_one)(a_b.astype(cfg.a_jdtype), y_b,
                                           hp)

            return jax.jit(
                solve_batch, donate_argnums=(0, 1) if cfg.donate else ())

        return self._cached(("het", mp_, n, has_bt, has_er), build)

    def _col_body_het(self, carry, xs_t, a_cp, y, hp: HetParams, n_mask,
                      has_bt: bool, axis=None):
        """One masked C-MP-AMP outer round with per-instance (traced)
        problem params — the column counterpart of ``_body_het``.  Same
        carry as ``_col_body`` plus the ``t_active`` freeze; ``hp.bt``
        holds stacked ``ColBTTables`` for column buckets."""
        if axis is None:
            if len(xs_t) == 3:
                s, sched_delta, drop = xs_t
            else:
                (s, sched_delta), drop = xs_t, None
        else:
            s, sched_delta, drop = xs_t
        x, mem, coef, v_prev = carry
        if has_bt:
            bt_delta, bt_rate = col_bt_delta_for(hp.bt, s, v_prev)
            delta = jnp.where(hp.use_bt, bt_delta, sched_delta)
            rate = jnp.where(hp.use_bt, bt_rate, jnp.float32(jnp.inf))
        else:
            delta, rate = sched_delta, jnp.float32(jnp.inf)
        x_new, mem_new, coef_new, v_hat, extra, syms = self._col_round(
            x, mem, coef, delta, a_cp, y, hp.m_real,
            lambda v, s2: eta_bg(v, s2, hp.eps, hp.mu_s, hp.sigma_s**2),
            n_mask=n_mask, drop=drop, axis=axis,
            pp=self._col_prior_params(hp))
        extra = jnp.where(s == 0, 0.0, extra)   # zero round-0 payload
        act = s < hp.t_active
        x1 = jnp.where(act, x_new, x)
        mem1 = jnp.where(act, mem_new, mem)
        coef1 = jnp.where(act, coef_new, coef)
        v1 = jnp.where(act, v_hat, v_prev)
        cfg = self.cfg
        out = (jnp.where(act, v_hat, 0.0), jnp.where(act, delta, 0.0),
               jnp.where(act, extra, 0.0),
               jnp.where(act, rate, jnp.float32(jnp.inf)),
               self._col_gather_x(x1, axis) if cfg.collect_xs
               else jnp.zeros(()),
               syms if cfg.collect_symbols else jnp.zeros(()))
        return (x1, mem1, coef1, v1), out

    def _col_scan_fn_het(self, m_pad: int, np_pad: int, has_bt: bool,
                         has_er: bool = False):
        """Jitted vmapped heterogeneous column-batch solve for one padded
        shape: a (B, P, M_pad, Np_pad) column shards, y (B, M_pad)."""

        def build():
            cfg = self.cfg
            p = cfg.n_proc

            def solve_one(a_cp, y, hp: HetParams):
                # every processor owns n_real/P real columns of its slice
                n_mask = (jnp.arange(np_pad) < hp.n_real // p
                          ).astype(jnp.float32)[None, :]
                init = self._col_init(p, np_pad, y,
                                      jnp.sum(y * y) / hp.m_real)
                body = lambda c, xs: self._col_body_het(c, xs, a_cp, y, hp,
                                                        n_mask, has_bt)
                xs = (jnp.arange(cfg.n_iter), hp.sched)
                if has_er:
                    xs = xs + (hp.drop,)
                (x, _, _, _), outs = jax.lax.scan(body, init, xs)
                return x.reshape(-1), outs

            def solve_batch(a_b, y_b, hp: HetParams):
                if cfg.kernel_on:
                    a_b, y_b = pad_col_shards(a_b, y_b)
                return jax.vmap(solve_one)(a_b.astype(cfg.a_jdtype), y_b,
                                           hp)

            return jax.jit(
                solve_batch, donate_argnums=(0, 1) if cfg.donate else ())

        return self._cached(("col_het", m_pad, np_pad, has_bt, has_er),
                            build)

    def dispatch_het(self, a_b, y_b, params: HetParams,
                     has_bt: bool | None = None,
                     compile_only: bool = False):
        """Launch the compiled het solve, returning raw ``(x, outs)`` device
        arrays without materializing them on host. jax dispatch is async, so
        a caller (the serving dispatcher) can prepare the next batch while
        this one computes; build the trace later with ``trace_of``.

        When the operands arrive batch-sharded over a mesh (leading-axis
        ``NamedSharding``), jit partitions the same vmapped program across
        the devices — the serving layer's data-parallel placement.

        Runs through the AOT executable cache: the first (shape, sharding)
        sighting compiles once, everything after is a cached-Compiled call.
        ``compile_only=True`` (the prewarm path) stops after populating the
        cache and returns the executable.

        With ``cfg.donate`` the batch operands are donated into the
        program: a_b/y_b are **consumed** — pass per-flush temporaries, not
        buffers you intend to reuse.
        """
        # cast A at the entry boundary so a bf16 a_dtype transfers (and
        # stays resident) at half width; the in-graph astype is then a no-op
        a_b = jnp.asarray(a_b, self.cfg.a_jdtype)
        y_b = jnp.asarray(y_b, jnp.float32)
        if has_bt is None:
            has_bt = bool(np.any(np.asarray(params.use_bt)))
        has_er = params.drop is not None
        if self.cfg.is_col:
            # column layout: a_b (B, P, M_pad, Np_pad), y_b (B, M_pad) —
            # y is shared across processors, not row-split
            b, p, m_pad, np_pad = a_b.shape
            assert p == self.cfg.n_proc, (p, self.cfg.n_proc)
            assert y_b.shape == (b, m_pad), (y_b.shape, (b, m_pad))
            return self._run(
                ("col_het", m_pad, np_pad, has_bt, has_er),
                self._col_scan_fn_het(m_pad, np_pad, has_bt, has_er),
                (a_b, y_b, params), compile_only)
        b, p, mp_, n = a_b.shape
        assert p == self.cfg.n_proc, (p, self.cfg.n_proc)
        assert y_b.shape == (b, p, mp_)
        return self._run(("het", mp_, n, has_bt, has_er),
                         self._scan_fn_het(mp_, n, has_bt, has_er),
                         (a_b, y_b, params), compile_only)

    def lower_het(self, a_b, y_b, params: HetParams,
                  has_bt: bool | None = None):
        """AOT entry: trace + lower the het program for these operands
        without compiling or executing (inspection / offline compile).
        Does not touch the executable cache; pair with ``compile_het`` for
        the cached pipeline."""
        a_b = jnp.asarray(a_b, self.cfg.a_jdtype)
        y_b = jnp.asarray(y_b, jnp.float32)
        if has_bt is None:
            has_bt = bool(np.any(np.asarray(params.use_bt)))
        has_er = params.drop is not None
        if self.cfg.is_col:
            _, _, m_pad, np_pad = a_b.shape
            fn = self._col_scan_fn_het(m_pad, np_pad, has_bt, has_er)
        else:
            _, _, mp_, n = a_b.shape
            fn = self._scan_fn_het(mp_, n, has_bt, has_er)
        return fn.lower(a_b, y_b, params)

    def compile_het(self, a_b, y_b, params: HetParams,
                    has_bt: bool | None = None):
        """AOT entry: compile the het program for these operand avals into
        the executable cache (idempotent) and return the executable.
        Subsequent ``dispatch_het`` calls with matching shapes/shardings
        run with zero new compiles."""
        return self.dispatch_het(a_b, y_b, params, has_bt,
                                 compile_only=True)

    def trace_of(self, x_outs) -> EngineTrace:
        """Materialize a ``dispatch_het``/``dispatch_sharded`` result."""
        return self._trace(*x_outs)

    def solve_het(self, a_b, y_b, params: HetParams,
                  has_bt: bool | None = None) -> EngineTrace:
        """Solve a heterogeneous batch of B padded CS instances.

        a_b (B, P, M_pad/P, N_pad) — per-processor shards, each processor's
        real rows padded with zero rows *within its own shard* (so the
        row->processor partition matches the unpadded single solve exactly);
        y_b (B, P, M_pad/P) zero-padded the same way. ``params`` carries the
        per-instance operands with a leading B axis. Results for instance i
        are valid on the first ``n_real[i]`` columns / ``t_active[i]``
        iterations of the trace. ``has_bt`` (static) may be passed by
        callers that know no instance uses BT; None derives it from
        ``params.use_bt``.
        """
        return self._trace(*self.dispatch_het(a_b, y_b, params, has_bt))

    # -- device-sharded solves (the mesh as an engine axis, DESIGN.md §6) ----

    def _sharded_axis(self, mesh):
        axis = getattr(self.transport, "axis", None)
        assert axis is not None, \
            "solve_sharded needs a device-collective transport " \
            "(PsumFusion / CompressedPsumTransport), got " \
            f"{type(self.transport).__name__}"
        assert not self.cfg.collect_symbols, \
            "symbols are per-device in sharded mode; build the engine with " \
            "collect_symbols=False"
        n_dev = mesh.shape[axis]
        assert self.cfg.n_proc % n_dev == 0, \
            f"P={self.cfg.n_proc} must be a multiple of the mesh " \
            f"'{axis}' axis ({n_dev})"
        return axis, n_dev

    def _sharded_fn(self, m: int, n: int, mesh, axis: str):
        """Jitted full-solve scan under shard_map: the same iteration body
        as ``_scan_fn``, with (A, y) row-sharded over ``axis`` (each device
        carries P/D emulated processors) and the schedule replicated."""

        def build():
            cfg, kappa = self.cfg, m / n

            def solve_fn(a_p, y_p, sched, drops):
                # local: a_p (P/D, M/P, N), y_p (P/D, M/P), drops (T, 1)
                init = (jnp.zeros(n, jnp.float32), jnp.zeros_like(y_p),
                        jnp.zeros(()))
                body = lambda c, xs: self._body(c, xs, a_p, y_p, kappa,
                                                axis=axis,
                                                m_eff=jnp.float32(m))
                (x, _, _), outs = jax.lax.scan(
                    body, init, (jnp.arange(cfg.n_iter), sched, drops[:, 0]))
                return x, outs

            fn = jax.shard_map(
                solve_fn, mesh=mesh,
                in_specs=(PartitionSpec(axis, None, None),
                          PartitionSpec(axis, None), PartitionSpec(),
                          PartitionSpec(None, axis)),
                out_specs=PartitionSpec(), axis_names={axis}, check_vma=False)
            return jax.jit(fn)

        return self._cached(("sharded", m, n, mesh, axis), build)

    def _col_sharded_fn(self, m: int, n: int, mesh, axis: str):
        """Jitted column-layout solve under shard_map: each device owns P/D
        column blocks; the fusion psums residual contributions (length M)
        and the boundary Onsager scalar across the mesh axis; y and the
        fused residual are replicated. ``drops`` (T, n_dev) marks erased
        device shards per round — column reset semantics
        (``_col_round``); an all-zeros schedule is bit-exact with the
        drop-free solve (every adjustment multiplies by exactly 1.0)."""

        def build():
            cfg = self.cfg

            def solve_fn(a_cp, y, sched, drops):
                # local: a_cp (P/D, M, N/P); y (M,) replicated
                p_loc, _, np_ = a_cp.shape
                init = self._col_init(p_loc, np_, y, jnp.sum(y * y) / m)
                body = lambda c, xs: self._col_body(c, xs, a_cp, y,
                                                    jnp.float32(m),
                                                    axis=axis)
                (x, _, _, _), outs = jax.lax.scan(
                    body, init, (jnp.arange(cfg.n_iter), sched, drops[:, 0]))
                return self._col_gather_x(x, axis), outs

            fn = jax.shard_map(
                solve_fn, mesh=mesh,
                in_specs=(PartitionSpec(axis, None, None), PartitionSpec(),
                          PartitionSpec(), PartitionSpec(None, axis)),
                out_specs=PartitionSpec(), axis_names={axis}, check_vma=False)
            return jax.jit(fn)

        return self._cached(("col_sharded", m, n, mesh, axis), build)

    def _solve_sharded_col(self, y, a_mat, mesh, drop_sched=None
                           ) -> EngineTrace:
        axis, n_dev = self._sharded_axis(mesh)
        self._check_col_controller()
        m, n = np.shape(a_mat)
        a_cp, yj = self._split_col(y, a_mat)
        if drop_sched is None:
            drop_sched = np.zeros((self.cfg.n_iter, n_dev), np.float32)
        drop_sched = np.asarray(drop_sched, np.float32)
        assert drop_sched.shape == (self.cfg.n_iter, n_dev), drop_sched.shape
        x, outs = self._col_sharded_fn(m, n, mesh, axis)(
            a_cp, yj, self._sched_operand(), jnp.asarray(drop_sched))
        return self._trace(x, outs)

    def solve_sharded(self, y, a_mat, mesh, drop_sched=None) -> EngineTrace:
        """Device-sharded solve: row-partitioned (A, y) across the mesh axis
        of the engine's device-collective transport, fusion on the wire.

        The iteration body, controller, and trace semantics are identical to
        ``solve`` — only the fusion sum (and the sigma2_hat reduction) cross
        device links. ``drop_sched`` (T, n_dev) optionally marks straggler/
        erased shards per iteration; the transport rescales the survivors
        unbiasedly instead of stalling the solve.

        Under a ``ColumnPartition`` layout the mesh axis carries the column
        blocks and the fusion psums residual contributions; a dropped shard
        there is handled by *reset*, not rescale — its signal blocks
        restart from zero and re-fuse next round (``_col_round``,
        DESIGN.md §10), since rescaling the other blocks cannot stand in
        for the missing one.
        """
        if self.cfg.is_col:
            return self._solve_sharded_col(y, a_mat, mesh, drop_sched)
        axis, n_dev = self._sharded_axis(mesh)
        m, n = np.shape(a_mat)
        a_p, y_p = self._split(y, a_mat)
        if drop_sched is None:
            drop_sched = np.zeros((self.cfg.n_iter, n_dev), np.float32)
        drop_sched = np.asarray(drop_sched, np.float32)
        assert drop_sched.shape == (self.cfg.n_iter, n_dev), drop_sched.shape
        x, outs = self._sharded_fn(m, n, mesh, axis)(
            a_p, y_p, self._sched_operand(), jnp.asarray(drop_sched))
        return self._trace(x, outs)

    def _sharded_het_fn(self, mp_: int, n: int, has_bt: bool, mesh,
                        axis: str, has_er: bool = False):

        def build():
            cfg = self.cfg

            def solve_one(a_p, y_p, hp: HetParams):
                n_mask = (jnp.arange(n) < hp.n_real).astype(jnp.float32)
                init = (jnp.zeros(n, jnp.float32), jnp.zeros_like(y_p),
                        jnp.zeros(()))
                # hp.drop rides replicated as (T, n_dev); each device
                # slices its own column of the mask
                drops = (hp.drop[:, lax.axis_index(axis)] if has_er
                         else jnp.zeros(cfg.n_iter, jnp.float32))
                body = lambda c, xs: self._body_het(c, xs, a_p, y_p, hp,
                                                    n_mask, has_bt,
                                                    axis=axis)
                (x, _, _), outs = jax.lax.scan(
                    body, init, (jnp.arange(cfg.n_iter), hp.sched, drops))
                return x, outs

            fn = jax.shard_map(
                solve_one, mesh=mesh,
                in_specs=(PartitionSpec(axis, None, None),
                          PartitionSpec(axis, None), PartitionSpec()),
                out_specs=PartitionSpec(), axis_names={axis}, check_vma=False)

            def solve_padded(a_p, y_p, hp: HetParams):
                # tile-align the global operands once, before shard_map
                if cfg.kernel_on:
                    a_p, y_p = pad_row_shards(a_p, y_p, cfg.a_jdtype)
                return fn(a_p.astype(cfg.a_jdtype), y_p, hp)

            # donate y only: the sharded A may be a long-lived cached
            # device buffer (serving operand cache) and must survive
            return jax.jit(
                solve_padded, donate_argnums=(1,) if cfg.donate else ())

        return self._cached(("sharded_het", mp_, n, has_bt, has_er, mesh,
                             axis), build)

    def _col_sharded_het_fn(self, m_pad: int, np_pad: int, has_bt: bool,
                            mesh, axis: str, has_er: bool = False):

        def build():
            cfg = self.cfg
            p = cfg.n_proc

            def solve_one(a_cp, y, hp: HetParams):
                n_mask = (jnp.arange(np_pad) < hp.n_real // p
                          ).astype(jnp.float32)[None, :]
                p_loc = a_cp.shape[0]
                init = self._col_init(p_loc, np_pad, y,
                                      jnp.sum(y * y) / hp.m_real)
                drops = (hp.drop[:, lax.axis_index(axis)] if has_er
                         else jnp.zeros(cfg.n_iter, jnp.float32))
                body = lambda c, xs: self._col_body_het(c, xs, a_cp, y, hp,
                                                        n_mask, has_bt,
                                                        axis=axis)
                (x, _, _, _), outs = jax.lax.scan(
                    body, init, (jnp.arange(cfg.n_iter), hp.sched, drops))
                return self._col_gather_x(x, axis), outs

            fn = jax.shard_map(
                solve_one, mesh=mesh,
                in_specs=(PartitionSpec(axis, None, None), PartitionSpec(),
                          PartitionSpec()),
                out_specs=PartitionSpec(), axis_names={axis}, check_vma=False)

            def solve_padded(a_cp, y, hp: HetParams):
                # tile-align the global operands once, before shard_map
                if cfg.kernel_on:
                    a_cp, y = pad_col_shards(a_cp, y)
                return fn(a_cp.astype(cfg.a_jdtype), y, hp)

            # donate y only (see _sharded_het_fn): A may be cache-resident
            return jax.jit(
                solve_padded, donate_argnums=(1,) if cfg.donate else ())

        return self._cached(("col_sharded_het", m_pad, np_pad, has_bt,
                             has_er, mesh, axis), build)

    def dispatch_sharded(self, a_p, y_p, params: HetParams, mesh,
                         has_bt: bool | None = None,
                         compile_only: bool = False):
        """Processor-sharded het solve of ONE padded instance (no batch
        axis): a_p (P, M_pad/P, N_pad), y_p (P, M_pad/P), ``params`` the
        per-instance operands *without* a leading batch axis (replicated
        into the shard_map). This is the serving layer's placement for
        large single requests: the mesh axis is the paper's P, the fusion a
        (possibly compressed) collective. Returns raw (x, outs); see
        ``dispatch_het`` for the async rationale.

        Column layout: a_p (P, M_pad, Np_pad) column shards, y_p the
        shared (M_pad,) measurements."""
        axis, n_dev = self._sharded_axis(mesh)
        a_p = jnp.asarray(a_p, self.cfg.a_jdtype)
        y_p = jnp.asarray(y_p, jnp.float32)
        if has_bt is None:
            has_bt = bool(np.any(np.asarray(params.use_bt)))
        has_er = params.drop is not None
        if has_er:
            # per-*device* mask here: the mesh axis is the processor axis
            assert np.shape(params.drop) == (self.cfg.n_iter, n_dev), \
                (np.shape(params.drop), (self.cfg.n_iter, n_dev))
        if self.cfg.is_col:
            p, m_pad, np_pad = a_p.shape
            assert p == self.cfg.n_proc, (p, self.cfg.n_proc)
            assert y_p.shape == (m_pad,), (y_p.shape, m_pad)
            return self._run(
                ("col_sharded_het", m_pad, np_pad, has_bt, has_er, mesh,
                 axis),
                self._col_sharded_het_fn(m_pad, np_pad, has_bt, mesh, axis,
                                         has_er),
                (a_p, y_p, params), compile_only)
        p, mp_, n = a_p.shape
        assert p == self.cfg.n_proc, (p, self.cfg.n_proc)
        assert y_p.shape == (p, mp_)
        return self._run(("sharded_het", mp_, n, has_bt, has_er, mesh,
                          axis),
                         self._sharded_het_fn(mp_, n, has_bt, mesh, axis,
                                              has_er),
                         (a_p, y_p, params), compile_only)

    def solve_sharded_het(self, a_p, y_p, params: HetParams, mesh,
                          has_bt: bool | None = None) -> EngineTrace:
        return self._trace(*self.dispatch_sharded(a_p, y_p, params, mesh,
                                                  has_bt))

    def solve_host_loop(self, y, a_mat, host_schedule=None) -> EngineTrace:
        """Per-iteration host loop over the same jitted body.

        Exists for (a) arbitrary Python rate-controller callables and
        (b) the engine benchmark's host-sync baseline. ``host_schedule``
        is ``(t, sigma2_hat) -> delta``; defaults to the engine's
        controller evaluated on host.
        """
        assert not self.cfg.is_col, \
            "solve_host_loop is a row-layout entry point; column solves " \
            "are scan-only (their controllers are in-graph by design)"
        cfg = self.cfg
        m, n = np.shape(a_mat)
        a_p, y_p = self._split(y, a_mat)
        local, gc = self._step_fns(m, n)

        if host_schedule is None:
            ctrl = self.controller
            if isinstance(ctrl, FixedSchedule):
                host_schedule = lambda t, s2: float(ctrl.deltas[t])
            else:
                host_schedule = lambda t, s2: float(
                    ctrl.delta_for(jnp.asarray(t), jnp.asarray(s2, jnp.float32))[0])

        x = jnp.zeros(n, jnp.float32)
        z_p = jnp.zeros_like(y_p)
        onsager = jnp.zeros(())
        s2s, deltas, extras, xs, syms = [], [], [], [], []
        for t in range(cfg.n_iter):
            z_p, f_p, s2 = local(x, z_p, onsager, a_p, y_p)
            delta_t = float(host_schedule(t, float(s2)))   # the host sync
            x, onsager, extra, q = gc(f_p, s2, jnp.asarray(delta_t))
            s2s.append(float(s2))
            deltas.append(delta_t)
            extras.append(float(extra))
            if cfg.collect_xs:
                xs.append(np.asarray(x))
            if cfg.collect_symbols:
                syms.append(np.asarray(q))
        return EngineTrace(
            x=np.asarray(x), sigma2_hat=np.asarray(s2s),
            deltas=np.asarray(deltas), extra_var=np.asarray(extras),
            rates=np.full(cfg.n_iter, np.inf, np.float32),
            symbols=np.asarray(syms) if cfg.collect_symbols else None,
            xs=np.asarray(xs) if cfg.collect_xs else None,
        )
