"""AMP solve service: heterogeneous requests -> bucketed batched engine
calls -> per-request results with realized-rate accounting (DESIGN.md §5).

One ``SolveService`` owns a compile cache of ``AmpEngine``s (one per
``BucketKey``), a table cache of per-operating-point BT controllers, and a
``Batcher``. Requests may differ in *everything* the paper varies — shape
(N, M), processor count P, prior sparsity, SNR, iteration budget T, and
rate policy (lossless / fixed schedule / offline DP / online BT) — and the
service still executes them as a handful of vmapped ``solve_het`` calls:
structural parameters select the bucket, everything else rides as
per-instance operands (``HetParams``).

On a multi-device mesh (pass ``mesh=make_serve_mesh()``) the service places
buckets across the devices (DESIGN.md §6): small-request buckets run
*data-parallel* (batch axis sharded over the mesh, processors emulated
per-device), large single requests run *processor-sharded* (the mesh axis
is the paper's P; fusion is a compressed collective on the wire) and
dispatch immediately instead of queuing behind a batch. Dispatch is
ahead-of-results: engine calls launch asynchronously and materialize only
when a consumer pulls, so host-side padding/prep of the next batch overlaps
device compute.

Usage::

    svc = SolveService()
    results = svc.solve([SolveRequest(y=y, a=a, prior=prior, policy="bt"),
                         SolveRequest(y=y2, a=a2, n_iter=6, policy="fixed",
                                      deltas=np.full(6, 0.05)), ...])

or streaming (continuous batching)::

    for res in svc.stream(request_iter):
        ...  # results arrive per request as each bucket batch completes
"""
from __future__ import annotations

import dataclasses
import math
import operator
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core.denoisers import BernoulliGauss
from ..core.engine import (AmpEngine, BlockQuantTransport, BTRateControl,
                           BTTables, ColBTTables, ColDPSchedule,
                           ColumnBTRateControl, ColumnPartition,
                           CompressedPsumTransport, EcsqTransport,
                           EngineConfig, ErasureSpec, HetParams, PsumFusion,
                           RowPartition, pad_bt_tables, split_problem_cols,
                           stack_bt_tables)
from ..core.quantize import ecsq_entropy, message_mixture, residual_mixture
from ..core.rate_alloc import (dp_allocate, dp_allocate_col,
                               erasure_rate_factors, stack_schedules)
from ..core.rate_distortion import RDModel
from ..core.state_evolution import CSProblem
from ..telemetry import (DRIFT_ALERT, DRIFT_BUCKETS, DRIFT_COUNTS,
                         MetricsRegistry, prometheus_text, se_drift,
                         se_drift_batch)
from ..telemetry.spans import phase as _phase
from .batcher import Batcher
from .buckets import (BucketKey, BucketPolicy, batch_width_ladder,
                      bucket_for, pad_batch_size, placement_for, round_up)
from .operand_cache import OperandCache, fingerprint
from .wire import WireModel, measure_wire

__all__ = ["SolveRequest", "SolveResult", "SolveService", "PrewarmSpec"]


@dataclasses.dataclass
class SolveRequest:
    """One CS recovery request: y = A s0 + e, recover s0.

    ``policy`` selects the rate control:
      * ``"lossless"`` — exact fusion (the paper's 32-bit baseline),
      * ``"fixed"``    — caller-provided per-iteration bin sizes ``deltas``,
      * ``"dp"``       — offline-optimal DP allocation for ``dp_total_bits``
                         (paper Sec. 3.4); ``deltas`` may be pre-computed,
                         otherwise the service runs ``dp_allocate`` (the
                         RD model table is disk-cached per prior),
      * ``"bt"``       — online back-tracking (paper Sec. 3.3); in-graph
                         tables are built once per operating point
                         (prior, SNR, kappa, P, T) and cached.

    ``layout`` selects the partition scheme (DESIGN.md §7): ``None``
    routes by aspect ratio (``placement_for``), ``"row"``/``"col"``
    force one.  Column requests need N divisible by P (each processor
    owns an equal signal slice); every policy above works in either
    layout — the service builds the matching controller family
    (``dp_allocate_col`` / ``ColumnBTRateControl`` for column buckets).

    ``erasure_rate`` > 0 subjects the request's fusion packets to
    per-round, per-processor loss (``erasure_model``: i.i.d.
    ``"bernoulli"`` or bursty ``"gilbert"`` with mean burst
    ``erasure_burst``; the mask is drawn deterministically from
    ``erasure_seed``).  ``recovery`` selects the bit-accounting
    discipline the allocators plan for — ``"retransmit"`` (lost bits are
    re-sent, shrinking the payload budget) or ``"rate_up"`` (survivors
    spend the dropped share) — see ``rate_alloc``.  Erasure requests run
    the het program family (no singleton fast path).

    ``measure_wire`` opts the request into measured-bytes accounting:
    the engine traces the quantizer symbol streams and the service
    rANS-codes them host-side (``serving.wire``), reporting
    ``bytes_on_wire`` / ``time_on_air_s`` / ``energy_j`` on the result.
    Unsupported on the processor-sharded placement (symbols live
    per-device there).
    """

    y: np.ndarray
    a: np.ndarray
    prior: BernoulliGauss = dataclasses.field(default_factory=BernoulliGauss)
    snr_db: float = 20.0
    n_proc: int = 10
    n_iter: int = 8
    policy: str = "lossless"
    deltas: np.ndarray | None = None      # fixed / precomputed dp
    dp_total_bits: float | None = None    # dp (default 2.0 * n_iter)
    bt_c_ratio: float = 1.005
    bt_r_max: float = 6.0
    transport: str = "ecsq"               # "ecsq" | "block8" | "block4"
    layout: str | None = None             # None = auto | "row" | "col"
    erasure_rate: float = 0.0             # per-packet loss probability
    erasure_model: str = "bernoulli"      # "bernoulli" | "gilbert"
    erasure_burst: float = 4.0            # mean burst length (gilbert)
    erasure_seed: int = 0                 # mask draw (deterministic)
    recovery: str = "retransmit"          # "retransmit" | "rate_up"
    measure_wire: bool = False            # rANS-code symbol streams and
    #                                       report measured wire bytes
    a_id: str | None = None               # stable caller-managed identity of
    #                                       ``a`` for the operand cache; when
    #                                       set it replaces the content hash
    #                                       (the caller vouches the bytes
    #                                       behind one id never change)
    request_id: int = -1                  # assigned at submit
    spans: list | None = None             # telemetry trace spans
    #                                       ([name, host, t0, t1] lists,
    #                                       telemetry/spans.py); the
    #                                       cluster frontend stamps
    #                                       admit/route here and the
    #                                       backend appends its own

    @property
    def n(self) -> int:
        return self.a.shape[1]

    @property
    def m(self) -> int:
        return self.a.shape[0]

    def problem(self) -> CSProblem:
        return CSProblem(n=self.n, m=self.m, prior=self.prior,
                         snr_db=self.snr_db)


@dataclasses.dataclass
class SolveResult:
    """Per-request output, unpadded back to the request's own (N, T).

    ``rates`` is the per-iteration coding rate *per processor, in the
    layout's own wire unit* — bits per signal element for row buckets
    (the fusion exchanges length-N messages), bits per *measurement* for
    column buckets (length-M residual contributions; ``bucket.layout``
    disambiguates, and mixed-stream consumers must not sum across
    layouts).  The value is the BT controller's in-graph decision for
    ``policy="bt"``, the analytic ECSQ entropy H_Q of the model payload
    distribution (message mixture row-wise, residual Gaussian
    column-wise) for finite fixed/DP bins, the fixed wire width (bits +
    amortized bf16 scale) for block transports, and +inf for
    lossless-fusion iterations (untracked, excluded from ``total_bits`` —
    same convention as ``MPAMPResult``).
    """

    request_id: int
    x: np.ndarray             # (N,) final estimate
    sigma2_hat: np.ndarray    # (T,) plug-in variances: post-LC (row) /
    #                           post-fusion ||g||^2/M incl. quant (col)
    deltas: np.ndarray        # (T,) realized bin sizes (inf = lossless)
    extra_var: np.ndarray     # (T,) transport-injected variance P*sigma_Q^2
    rates: np.ndarray         # (T,) bits/elem (row) | bits/meas (col), /proc
    #                           on-the-wire under the recovery policy
    #                           (== delivered when erasure_rate = 0)
    total_bits: float         # sum of finite per-iteration rates
    bucket: BucketKey         # where this request was executed
    batch_size: int           # real requests in the executed batch
    bytes_on_wire: float | None = None   # measured rANS bytes incl. table/
    #                                      header/retransmit (measure_wire)
    payload_bytes: float | None = None   # measured rANS payload only — the
    #                                      number comparable to model H_Q
    time_on_air_s: float | None = None   # bytes_on_wire / link rate
    energy_j: float | None = None        # time_on_air * tx power
    se_drift: float | None = None        # mean |ln(realized/SE predicted)|
    #                                      per-iteration variance drift
    #                                      (telemetry/drift.py); None when
    #                                      telemetry is off
    spans: list | None = None            # completed trace spans
    #                                      (admit..drift) for this
    #                                      request

    def mse(self, s0: np.ndarray) -> float:
        return float(np.mean((self.x - np.asarray(s0)) ** 2))

    @property
    def tracked(self) -> bool:
        """Whether ``total_bits`` is a real measurement: False when no
        iteration reported a finite rate (all-lossless fusion), in which
        case the 0.0 total means "untracked", not "zero bits"."""
        return bool(np.isfinite(self.rates).any())


@dataclasses.dataclass(frozen=True)
class PrewarmSpec:
    """One entry of a prewarm menu (DESIGN.md §9): the structural shape of
    expected traffic. ``SolveService.prewarm`` expands each spec into its
    bucket x batch-width grid and AOT-compiles every program so steady-state
    requests never block on XLA.

    ``policy`` picks the compiled program family: "lossless"/"fixed"/"dp"
    share the has_bt=False program, "bt" compiles the in-graph-controller
    variant (and warms the BT table cache for (prior, snr_db) — streams
    mixing BT and non-BT traffic should list both). "dp" additionally warms
    the DP/RD allocation caches, which builds an RD table on first sight of
    a prior — only list it when that cost belongs in startup.

    ``batch_widths=None`` compiles the full ``batch_width_ladder`` of the
    service policy; pass an explicit tuple to narrow startup cost."""

    n: int
    m: int
    n_proc: int = 10
    n_iter: int = 8
    policy: str = "lossless"
    transport: str = "ecsq"
    layout: str | None = None
    snr_db: float = 20.0
    prior: BernoulliGauss = dataclasses.field(default_factory=BernoulliGauss)
    batch_widths: tuple | None = None


_TRANSPORTS = {
    "ecsq": EcsqTransport,
    "block8": lambda: BlockQuantTransport(bits=8, block=512),
    "block4": lambda: BlockQuantTransport(bits=4, block=512),
}

# processor-sharded engines fuse on the device links instead: the same wire
# format, executed as a collective (DESIGN.md §6)
_SHARDED_TRANSPORTS = {
    "ecsq": lambda axis: PsumFusion(axis=axis, local=EcsqTransport()),
    "block8": lambda axis: CompressedPsumTransport(axis=axis, bits=8,
                                                   block=512),
    "block4": lambda axis: CompressedPsumTransport(axis=axis, bits=4,
                                                   block=512),
}


# a dispatched-but-unmaterialized engine call (dispatch-ahead): calling it
# materializes the device results into SolveResults
_Pending = Callable[[], "list[SolveResult]"]

# the operating-point fields that must agree across a bucket group for the
# vectorized drift path (one C-level multi-attr fetch per request beats six
# Python attribute reads on the hot path)
_DRIFT_ATTRS = operator.attrgetter("n_iter", "n", "m", "snr_db",
                                   "erasure_rate")


class SolveService:
    """Shape-bucketed continuous batching over ``AmpEngine.solve_het``,
    with mesh-aware bucket placement when a device mesh is provided."""

    def __init__(self, policy: BucketPolicy | None = None,
                 collect_xs: bool = False, rate_accounting: bool = True,
                 use_kernel: bool | None = None,
                 kernel_interpret: bool = False,
                 mesh=None, mesh_axis: str = "data",
                 operand_cache_bytes: int = 256 << 20,
                 singleton_fastpath: bool = True,
                 donate: bool = True,
                 wire_model: WireModel | None = None,
                 telemetry: bool = True,
                 col_inner: int = 1):
        self.policy = policy or BucketPolicy()
        # local AMP iterations per fusion round of every column bucket
        # (ColumnPartition.n_inner); the column rate controllers plan for
        # one, so lossy policies at col_inner > 1 are not rate-optimal
        self.col_inner = col_inner
        self.collect_xs = collect_xs
        self.rate_accounting = rate_accounting
        self.use_kernel = use_kernel
        self.kernel_interpret = kernel_interpret
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.n_devices = 1 if mesh is None else mesh.shape[mesh_axis]
        if self.n_devices > 1:
            # data-parallel dispatch pads batches to a device multiple, so
            # max_batch must be one too or the documented compile-width cap
            # would be silently exceeded
            assert self.policy.max_batch % self.n_devices == 0, \
                f"max_batch={self.policy.max_batch} must be a multiple of " \
                f"the mesh device count ({self.n_devices})"
        self.wire_model = wire_model or WireModel()
        self._batcher = Batcher(self.policy)
        self._engines: dict[BucketKey, AmpEngine] = {}
        # symbol-tracing twins of the bucket engines for measured-wire
        # requests: a different trace pytree means a different compiled
        # program family, so they must not share the plain engines' caches
        self._wire_engines: dict[BucketKey, AmpEngine] = {}
        self._bt_cache: dict = {}
        self._rd_cache: dict = {}
        self._completed: list[SolveResult] = []
        self._pending: list[_Pending] = []
        self._next_id = 0
        # hot-path state (DESIGN.md §9): device-resident A shards keyed by
        # content fingerprint (0 bytes disables), plain-dispatch routing for
        # lone row requests, and operand donation on the batched engines
        self._opcache = (OperandCache(operand_cache_bytes)
                         if operand_cache_bytes > 0 else None)
        self.singleton_fastpath = singleton_fastpath
        self.donate = donate
        self._single_engines: dict = {}
        self._singleton_dispatches = 0
        self._prewarm_report: dict | None = None
        self._prewarm_thread: threading.Thread | None = None
        # a background prewarm's failure, re-raised by the next
        # stats()/flush()/poll()/stream() collection instead of dying
        # with its daemon thread
        self._prewarm_error: BaseException | None = None
        # guards id assignment and engine-map mutation against a background
        # prewarm thread racing foreground submits
        self._lock = threading.RLock()
        # telemetry plane (DESIGN.md §12): event-driven histograms/counters
        # on the request path plus a pull-time collector over the sources
        # that already keep their own atomic counters (engine, operand
        # cache, batcher). ``telemetry=False`` strips every hot-path write,
        # span and profiler annotation.
        self.telemetry = telemetry
        self._registry = None
        # running totals of the drift tails' SE-prediction lookups and
        # of the MMSE evaluations their SE recursions made
        self._se_lookups = {"hit": 0, "miss": 0}
        self._se_mmse = {"table": 0, "quadrature": 0}
        # per-layout label-bound metric children (metrics._Child): the
        # dispatch tails bump these without re-resolving label keys
        self._children: dict = {}
        if telemetry:
            reg = self._registry = MetricsRegistry()
            self._m_requests = reg.counter(
                "amp_requests_total",
                "Requests admitted (counted at group dispatch)",
                ("layout",))
            self._h_latency = reg.histogram(
                "amp_request_latency_seconds",
                "Admit -> result-finalized latency", ("layout",))
            self._h_batch_wait = reg.histogram(
                "amp_batch_wait_seconds",
                "Admit -> bucket batch dispatch wait", ("layout",))
            self._h_drift = reg.histogram(
                "amp_se_drift",
                "Per-request SE drift: mean |ln(realized/predicted)| "
                "per-iteration variance", ("layout",),
                buckets=DRIFT_BUCKETS)
            self._m_drift_alerts = reg.counter(
                "amp_se_drift_alerts_total",
                f"Requests whose SE drift exceeded {DRIFT_ALERT}",
                ("layout",))
            reg.collect(self._collect_metrics)

    # -- request intake ------------------------------------------------------

    def submit(self, req: SolveRequest) -> int:
        """Queue one request; a full bucket group dispatches immediately
        (results buffered until ``flush``/``stream`` hands them out).
        Processor-sharded requests dispatch at once — they consume the
        whole mesh, so queuing them behind a batch buys nothing."""
        tel = self.telemetry
        with _phase("admit", tel) as adm:
            req = self._prepare(req)
            key = self._key_for(req)
            if tel:
                # the request carries its admit span into the queue; the
                # phase stamps its end on exit. Forwarded requests (cluster
                # handoff) get it appended to an own copy of their list —
                # the frontend's decoded request must not see backend
                # appends. Local requests keep it beside the fields, and
                # the dispatch tails put it first in the result's list.
                if req.spans:
                    req.spans = [*req.spans, adm]
                else:
                    req._admit = adm
            full = (None if key.placement == "proc"
                    else self._batcher.add(key, req))
        if key.placement == "proc":
            self._pending.append(self._dispatch_bucket(key, [req]))
        elif full is not None:
            self._pending.append(self._dispatch_bucket(*full))
        return req.request_id

    def _raise_prewarm_error(self):
        """Surface (once) a failure of a background ``prewarm`` thread."""
        with self._lock:
            err, self._prewarm_error = self._prewarm_error, None
        if err is not None:
            raise RuntimeError("background prewarm failed") from err

    def _collect_pending(self):
        """Materialize every dispatched batch into ``_completed`` (FIFO)."""
        self._raise_prewarm_error()
        pending, self._pending = self._pending, []
        for finalize in pending:
            self._completed.extend(finalize())

    def poll(self) -> list[SolveResult]:
        """Materialize every *already dispatched* batch and hand back all
        buffered results — without forcing partially-filled bucket groups
        to dispatch (unlike ``flush``). The cluster frontend's per-submit
        collection hook: full batches stream out as they complete while
        stragglers keep accumulating toward their batch width."""
        self._collect_pending()
        out, self._completed = self._completed, []
        return out

    def flush(self) -> list[SolveResult]:
        """Dispatch all pending groups; return every buffered result."""
        # dispatch everything first, then materialize: the engine calls
        # overlap on device while the host pads the next group's operands
        for key, group in self._batcher.drain():
            self._pending.append(self._dispatch_bucket(key, group))
        self._collect_pending()
        out, self._completed = self._completed, []
        return out

    def solve(self, reqs) -> list[SolveResult]:
        """Submit + flush; results in submission order. Results belonging
        to earlier ``submit`` calls that this flush happened to complete
        stay buffered for their own ``flush``/``stream`` consumer."""
        ids = [self.submit(r) for r in reqs]
        own = set(ids)
        by_id = {}
        for r in self.flush():
            if r.request_id in own:
                by_id[r.request_id] = r
            else:
                self._completed.append(r)
        return [by_id[i] for i in ids]

    def stream(self, reqs):
        """Continuous batching: yield results per request as each bucket
        batch completes; stragglers flush when the input is exhausted.
        Like ``solve``, results belonging to other consumers' earlier
        ``submit`` calls stay buffered for them."""
        own = set()

        def take_own():
            keep = []
            for r in self._completed:
                if r.request_id in own:
                    yield r
                else:
                    keep.append(r)
            self._completed = keep

        for r in reqs:
            own.add(self.submit(r))
            # materialize whatever submit dispatched: stream's contract is
            # per-batch yield timing, so collection here is blocking (the
            # dispatch itself already ran async during submit)
            self._collect_pending()
            if self._completed:
                yield from take_own()
        for key, group in self._batcher.drain():
            self._pending.append(self._dispatch_bucket(key, group))
        self._collect_pending()
        yield from take_own()

    # -- internals -----------------------------------------------------------

    def _prepare(self, req: SolveRequest,
                 assign_id: bool = True) -> SolveRequest:
        if req.request_id >= 0:
            # template reuse: resubmitting an already-served request object
            # must not alias two queue entries onto one id (cold path) —
            # and must not inherit the previous serve's trace spans. A
            # span list ending in "route" is not stale: it's a cluster
            # frontend's in-flight handoff (admit+route stamped just
            # before forwarding), which the backend must extend.
            fwd = bool(req.spans) and req.spans[-1][0] == "route"
            req = dataclasses.replace(
                req, spans=req.spans if fwd else None)
        # id assignment mutates in place: dataclasses.replace would copy the
        # request row on the hot path for no benefit; prewarm's dummy
        # requests skip it so the id sequence stays a pure submission
        # counter (callers index their own bookkeeping by it)
        if assign_id:
            with self._lock:
                req.request_id = self._next_id
                self._next_id += 1
        assert req.policy in ("lossless", "fixed", "dp", "bt"), req.policy
        assert req.transport in _TRANSPORTS, req.transport
        if req.transport != "ecsq":
            # block transports fix the rate by wire width and ignore the
            # controller's bin size — an ECSQ rate policy would be silently
            # unenforced (and its rate accounting fiction)
            assert req.policy == "lossless", \
                f"policy={req.policy!r} has no effect under " \
                f"transport={req.transport!r}; use policy='lossless'"
        assert req.layout in (None, "row", "col"), req.layout
        assert 0.0 <= req.erasure_rate < 1.0, req.erasure_rate
        assert req.erasure_model in ("bernoulli", "gilbert"), \
            req.erasure_model
        assert req.recovery in ("retransmit", "rate_up"), req.recovery
        if req.layout is None:
            # pin the auto-routed layout on our copy so every later stage
            # (bucket key, operands, rate accounting) agrees — via replace,
            # not mutation: the caller's template must stay layout=None
            # (another service with a different col_aspect may route it
            # differently)
            req = dataclasses.replace(
                req, layout=placement_for(req.n, req.m, req.n_proc,
                                          self.n_devices, self.policy)[1])
        if req.layout == "col":
            assert req.n % req.n_proc == 0, \
                f"N={req.n} not divisible by P={req.n_proc} (column layout)"
        else:
            assert req.m % req.n_proc == 0, \
                f"M={req.m} not divisible by P={req.n_proc}"
        if req.policy == "fixed":
            assert req.deltas is not None, "fixed policy needs deltas"
            assert len(req.deltas) == req.n_iter
        if req.policy == "dp" and req.deltas is None:
            with _phase("dp_allocate", self.telemetry):
                deltas = self._dp_deltas(req)
            req = dataclasses.replace(req, deltas=deltas)
        return req

    def _key_for(self, req: SolveRequest) -> BucketKey:
        placement, _ = placement_for(req.n, req.m, req.n_proc,
                                     self.n_devices, self.policy)
        return bucket_for(req.n, req.m, req.n_proc, req.n_iter,
                          req.transport, self.policy, placement, req.layout)

    def _engine(self, key: BucketKey, wire: bool = False) -> AmpEngine:
        # data-parallel buckets reuse the local engine object: the sharding
        # lives on the operands, and jit re-specializes the same callable
        ekey = (key if key.placement == "proc"
                else dataclasses.replace(key, placement="local"))
        assert not (wire and key.placement == "proc"), \
            "measured-wire accounting needs host-visible symbol streams; " \
            "the processor-sharded placement keeps them per-device " \
            "(engine.py collect_symbols contract)"
        cache = self._wire_engines if wire else self._engines
        with self._lock:
            eng = cache.get(ekey)
            if eng is None:
                cfg = EngineConfig(
                    n_proc=key.n_proc, n_iter=key.t_max,
                    use_kernel=self.use_kernel,
                    kernel_interpret=self.kernel_interpret,
                    collect_symbols=wire, collect_xs=self.collect_xs,
                    layout=(ColumnPartition(n_inner=self.col_inner)
                            if key.layout == "col"
                            else RowPartition()),
                    # batched operands are per-flush temporaries -> donate;
                    # the proc placement's jit donates only y (engine.py):
                    # its A may be a cache-resident buffer
                    donate=self.donate)
                if ekey.placement == "proc":
                    transport = _SHARDED_TRANSPORTS[key.transport](
                        self.mesh_axis)
                else:
                    transport = _TRANSPORTS[key.transport]()
                eng = AmpEngine(BernoulliGauss(), cfg, transport)
                cache[ekey] = eng
        return eng

    def _single_engine(self, req: SolveRequest) -> AmpEngine:
        """True-dims plain engine for the singleton fast path. Keyed on
        everything ``_scan_fn`` closes over (the prior lives on the engine
        here, unlike the het path where it rides as an operand)."""
        skey = (req.n, req.m, req.n_proc, req.n_iter, req.transport,
                req.prior)
        with self._lock:
            eng = self._single_engines.get(skey)
            if eng is None:
                cfg = EngineConfig(
                    n_proc=req.n_proc, n_iter=req.n_iter,
                    use_kernel=self.use_kernel,
                    kernel_interpret=self.kernel_interpret,
                    collect_symbols=False, collect_xs=self.collect_xs)
                # donate=False: this path runs on cache-resident operands
                eng = AmpEngine(req.prior, cfg,
                                _TRANSPORTS[req.transport]())
                self._single_engines[skey] = eng
        return eng

    def _dp_deltas(self, req: SolveRequest) -> np.ndarray:
        """Offline DP allocation realized as ECSQ bin sizes (DPSchedule /
        ColDPSchedule for column requests).

        Under erasure the allocators plan for the request's recovery
        policy; the realized bins then encode the *delivered* per-survivor
        rates (allocated * survivor_boost), which is what the quantizers
        on the surviving packets actually spend."""
        from ..core.engine import DPSchedule
        prob = req.problem()
        r_total = (req.dp_total_bits if req.dp_total_bits is not None
                   else 2.0 * req.n_iter)
        _, boost, _ = erasure_rate_factors(req.erasure_rate, req.recovery)
        if req.layout == "col":
            dp = dp_allocate_col(prob, req.n_proc, req.n_iter, r_total,
                                 erasure_rate=req.erasure_rate,
                                 recovery=req.recovery)
            if boost != 1.0:
                dp = dataclasses.replace(dp, rates=dp.rates * boost)
            return ColDPSchedule(dp, prob, req.n_proc).deltas
        rd = self._rd_cache.get(req.prior)
        if rd is None:
            rd = self._rd_cache[req.prior] = RDModel(req.prior)
        dp = dp_allocate(prob, req.n_proc, req.n_iter, r_total, rd=rd,
                         erasure_rate=req.erasure_rate,
                         recovery=req.recovery)
        if boost != 1.0:
            dp = dataclasses.replace(dp, rates=dp.rates * boost)
        return DPSchedule(dp, rd, req.n_proc).deltas

    def _bt_tables(self, req: SolveRequest, t_max: int):
        """Padded in-graph tables for one operating point, memoized per
        (operating point, t_max) so repeated/pad-slot requests share one
        object — which keeps ``stack_bt_tables``'s zero-copy fast path.
        Column requests get ``ColumnBTRateControl`` tables."""
        key = (req.prior, round(req.snr_db, 6), req.n, req.m, req.n_proc,
               req.n_iter, req.bt_c_ratio, req.bt_r_max, req.layout,
               req.erasure_rate, req.recovery)
        padded = self._bt_cache.get((key, t_max))
        if padded is None:
            ctrl = self._bt_cache.get(key)
            if ctrl is None:
                if req.layout == "col":
                    ctrl = ColumnBTRateControl(
                        req.problem(), req.n_proc, req.n_iter,
                        req.bt_c_ratio, req.bt_r_max,
                        erasure_rate=req.erasure_rate,
                        recovery=req.recovery)
                else:
                    ctrl = BTRateControl(req.problem(), req.n_proc,
                                         req.n_iter, req.bt_c_ratio,
                                         req.bt_r_max, "ecsq",
                                         erasure_rate=req.erasure_rate,
                                         recovery=req.recovery)
                self._bt_cache[key] = ctrl
            padded = pad_bt_tables(ctrl.tables, t_max)
            self._bt_cache[(key, t_max)] = padded
        return padded

    def _drop_mask(self, req: SolveRequest,
                   n_proc: int | None = None) -> np.ndarray | None:
        """The (n_iter, P) erasure mask of one request, or None when the
        link is lossless. Deterministic in the request's erasure fields,
        so dispatch (operand build) and result finalization (retransmit
        byte accounting) independently reconstruct the same draw."""
        if req.erasure_rate == 0.0:
            return None
        spec = ErasureSpec(rate=req.erasure_rate, model=req.erasure_model,
                           burst_len=req.erasure_burst,
                           seed=req.erasure_seed)
        return spec.sample_mask(req.n_iter, n_proc or req.n_proc)

    def _fingerprint(self, req: SolveRequest):
        """Operand-cache identity of a request's A: the caller-vouched
        ``a_id`` when set, else the content hash (in-place mutation of a
        caller's array is then a miss, never a stale hit)."""
        return req.a_id if req.a_id is not None else fingerprint(req.a)

    def _pad_a_one(self, key: BucketKey, r: SolveRequest) -> np.ndarray:
        """Host-side pad of one request's A into its bucket shard shape:
        (P, mp_pad, n_pad) row / (P, m_pad, np_pad) col (docstring of
        ``_het_operands`` for the padding semantics)."""
        p, mp_pad, n_pad = key.n_proc, key.mp_pad, key.n_pad
        if key.layout == "col":
            buf = np.zeros((p, mp_pad, n_pad // p), np.float32)
            buf[:, :r.m, :r.n // p] = split_problem_cols(
                np.asarray(r.a, np.float32), p)
        else:
            mp = r.m // p
            buf = np.zeros((p, mp_pad, n_pad), np.float32)
            buf[:, :mp, :r.n] = np.asarray(r.a, np.float32).reshape(
                p, mp, r.n)
        return buf

    def _a_slice(self, key: BucketKey, r: SolveRequest, eng: AmpEngine):
        """Device-resident padded A shards for one request: built (pad +
        dtype cast + upload) once per (fingerprint, bucket shard shape) and
        reused across batches and streams. The cached buffer is never
        donated (engine.py wires donation onto the stacked temporaries
        only), so reuse is safe."""
        ck = (key.layout, self._fingerprint(r), key.n_proc, key.mp_pad,
              key.n_pad, eng.cfg.a_dtype)
        build = lambda: self._put_a(key, self._pad_a_one(key, r), eng)
        if self._opcache is None:
            return build()
        return self._opcache.get(ck, build)

    def _put_a(self, key: BucketKey, a_pad: np.ndarray, eng: AmpEngine):
        """Upload one request's padded A shards. A processor-sharded
        bucket places each device's processors on that device straight
        from the host, so the whole matrix never lands on one device."""
        if key.placement == "proc":
            return jax.device_put(
                a_pad.astype(eng.cfg.a_jdtype),
                NamedSharding(self.mesh, PartitionSpec(self.mesh_axis)))
        return jnp.asarray(a_pad, eng.cfg.a_jdtype)

    def _a_batch(self, key: BucketKey, batch: list, eng: AmpEngine,
                 use_cache: bool = True):
        """Batch A operand: a device-side stack over cache-resident shards
        (a pad slot repeating a real request hits the same entry), or the
        legacy host-assembled numpy block when the cache is off —
        including prewarm, whose all-zero dummies must not pollute it."""
        if self._opcache is not None and use_cache:
            return jnp.stack([self._a_slice(key, r, eng) for r in batch])
        return np.stack([self._pad_a_one(key, r) for r in batch])

    def _y_and_params(self, key: BucketKey, batch: list):
        """Per-flush (small) operands: padded y and the per-instance
        ``HetParams``. Unlike A these change with every request, so they
        are host-built fresh and donated into the program."""
        p, mp_pad, t_max = key.n_proc, key.mp_pad, key.t_max
        b = len(batch)
        is_col = key.layout == "col"
        if is_col:
            y_b = np.zeros((b, mp_pad), np.float32)
        else:
            y_b = np.zeros((b, p, mp_pad), np.float32)
        scheds, tacts, mreals, nreals = [], [], [], []
        eps, mus, sss, use_bt, tables = [], [], [], [], []
        for i, r in enumerate(batch):
            if is_col:
                y_b[i, :r.m] = np.asarray(r.y, np.float32)
            else:
                mp = r.m // p
                y_b[i, :, :mp] = np.asarray(r.y, np.float32).reshape(p, mp)
            if r.policy in ("fixed", "dp"):
                scheds.append(np.asarray(r.deltas, np.float32))
            else:  # lossless / bt: schedule operand unused or all-lossless
                scheds.append(np.full(r.n_iter, np.inf, np.float32))
            tacts.append(r.n_iter)
            mreals.append(r.m)
            nreals.append(r.n)
            eps.append(r.prior.eps)
            mus.append(r.prior.mu_s)
            sss.append(r.prior.sigma_s)
            if r.policy == "bt":
                use_bt.append(True)
                tables.append(self._bt_tables(r, t_max))
            else:
                use_bt.append(False)
                tables.append(ColBTTables.dummy(t_max) if is_col
                              else BTTables.dummy(t_max))

        # erasure masks ride as a (B, T, P) operand only when some request
        # in the batch actually loses packets — drop=None keeps the
        # pre-erasure operand avals and compiled programs byte-identical.
        # Lossless co-batched requests get all-zero masks (a numeric no-op
        # through the survivor-rescale/reset paths). On the
        # processor-sharded placement the mask axis is the mesh device.
        drops = None
        if any(r.erasure_rate > 0.0 for r in batch):
            p_mask = self.n_devices if key.placement == "proc" else p
            drops = np.zeros((b, t_max, p_mask), np.float32)
            for i, r in enumerate(batch):
                m = self._drop_mask(r, p_mask)
                if m is not None:
                    drops[i, :r.n_iter] = m

        params = HetParams(
            sched=stack_schedules(scheds, t_max),
            t_active=np.asarray(tacts, np.int32),
            m_real=np.asarray(mreals, np.float32),
            n_real=np.asarray(nreals, np.int32),
            eps=np.asarray(eps, np.float32),
            mu_s=np.asarray(mus, np.float32),
            sigma_s=np.asarray(sss, np.float32),
            use_bt=np.asarray(use_bt),
            bt=stack_bt_tables(tables),
            drop=drops,
        )
        return y_b, params, any(use_bt)

    def _het_operands(self, key: BucketKey, batch: list,
                      use_cache: bool = True):
        """Pad one request group into the engine's het operands.

        Row buckets: a (B, P, mp_pad, n_pad) row shards + y (B, P, mp_pad).
        Column buckets: a (B, P, m_pad, np_pad) column shards (each
        processor's real columns padded within its own slice, mirroring
        the row layout's per-shard row padding) + the shared y (B, m_pad).
        """
        a_b = self._a_batch(key, batch, self._engine(key), use_cache)
        y_b, params, has_bt = self._y_and_params(key, batch)
        return a_b, y_b, params, has_bt

    def _dispatch_bucket(self, key: BucketKey, reqs: list) -> _Pending:
        """Launch one bucket group on its placement; materialization is
        deferred to the returned ``_Pending.finalize``."""
        if key.placement == "proc":
            (r,) = reqs     # proc requests dispatch alone, from submit
            return self._dispatch_proc(key, r)
        if len(reqs) == 1 and self._singleton_ok(key, reqs[0]):
            return self._dispatch_singleton(key, reqs[0])

        b_real = len(reqs)
        b_pad = pad_batch_size(b_real, self.policy)
        if key.placement == "data":
            # the batch axis shards over the mesh: pad to a device multiple
            b_pad = round_up(b_pad, self.n_devices)
        # fill pad slots by repeating real requests (their results are
        # dropped); keeps every instance numerically benign — and on the
        # cached path a pad slot is an operand-cache hit, not a rebuild
        batch = [reqs[i % b_real] for i in range(b_pad)]
        # a measured-wire request anywhere in the group routes the whole
        # batch onto the symbol-tracing engine twin (same math, bigger
        # trace); pure streams of either kind never double-compile
        wire = any(r.measure_wire for r in reqs)
        eng = self._engine(key, wire)
        tel = self.telemetry
        with _phase("operands", tel) as op:
            with _phase("a_stack", tel):
                a_b = self._a_batch(key, batch, eng)
            with _phase("params", tel):
                y_b, params, has_bt = self._y_and_params(key, batch)
            if key.placement == "data":
                shard = NamedSharding(self.mesh,
                                      PartitionSpec(self.mesh_axis))
                a_b, y_b, params = jax.device_put((a_b, y_b, params), shard)
        # a_b/y_b are per-flush temporaries: the donating engine consumes
        # them (the cached per-request shards behind the stack survive)
        with _phase("dispatch", tel):
            x_outs = eng.dispatch_het(a_b, y_b, params, has_bt=has_bt)

        def finalize() -> list[SolveResult]:
            with _phase("pull", tel) as pl:
                trace = eng.trace_of(x_outs)
            shared = self._batch_spans(op, pl)
            if shared is not None and not wire:
                return self._batch_tail(key, reqs, trace, shared)
            # measured-wire groups keep the per-request tail (each
            # request's wire_measure span nests in its own results span)
            return [self._request_tail(key, r, trace, i, b_real, shared)
                    for i, r in enumerate(reqs)]

        return finalize

    def _layout_children(self, layout: str) -> dict:
        """Label-bound metric handles for one layout, resolved once."""
        ch = self._children.get(layout)
        if ch is None:
            ch = self._children[layout] = {
                "requests": self._m_requests.labels(layout=layout),
                "latency": self._h_latency.labels(layout=layout),
                "batch_wait": self._h_batch_wait.labels(layout=layout),
                "drift": self._h_drift.labels(layout=layout),
                "alerts": self._m_drift_alerts.labels(layout=layout),
            }
        return ch

    @staticmethod
    def _admit_spans(r: SolveRequest) -> list:
        """The spans a request brought to dispatch: a forwarded request's
        list (frontend admit/route, then this service's admit), or a local
        request's admit span."""
        if r.spans:
            return r.spans
        adm = getattr(r, "_admit", None)
        return [] if adm is None else [adm]

    def _batch_tail(self, key: BucketKey, reqs: list, trace,
                    shared: list) -> list[SolveResult]:
        """Results and telemetry tail of one batched group. Its
        finalization is one ``complete`` span, shared verbatim by every
        request like the operands/compute/pull spans before it (the batch
        is the unit of execution), with two children: ``results``, each
        request's slice-out and rate accounting, and ``drift``, the SE
        drift of the group (``_drift_tail``). Spans are assembled in one
        pass, histograms fed by one bulk observe per metric; the latency
        ends where ``complete`` ends, drift tail included."""
        ch = self._layout_children(key.layout)
        b_real = len(reqs)
        with _phase("complete") as co:
            with _phase("results") as rs:
                out = [self._result_one(key, r, trace, i, b_real)
                       for i, r in enumerate(reqs)]
            with _phase("drift") as dr:
                dr.append(self._drift_tail(key, reqs, out, trace, ch))
            tail = [*shared, co, rs, dr]
            sh0 = shared[0][2]
            starts: list = []
            waits: list = []
            for r, res in zip(reqs, out):
                head = self._admit_spans(r)
                t_a = head[-1][3] if head else sh0
                res.spans = [*head, ["batch_wait", None, t_a, sh0], *tail]
                waits.append(sh0 - t_a)
                if head and head[0][0] == "admit":
                    starts.append(head[0][2])
        ch["requests"].inc(b_real)
        ch["batch_wait"].observe_many(waits)
        if starts:
            t_end = co[3]
            ch["latency"].observe_many([t_end - t for t in starts])
        return out

    def _tally(self, counts: dict) -> None:
        """Add one tail's SE-prediction lookups and MMSE evaluations to
        the running totals."""
        tot = self._se_lookups
        tot["hit"] += counts["lookups"] - counts["misses"]
        tot["miss"] += counts["misses"]
        ev = self._se_mmse
        ev["table"] += counts["table"]
        ev["quadrature"] += counts["quadrature"]

    def _drift_tail(self, key: BucketKey, reqs: list, results: list,
                    trace, ch: dict) -> dict:
        """SE drift for a whole bucket group (DESIGN.md §12), written
        onto the already-built results; returns the group's
        SE-prediction lookups and misses and its MMSE evaluations
        (``DRIFT_COUNTS``). A group uniform in operating
        point pays one vectorized masked log-ratio pass (one memoized
        prediction lookup per distinct realized schedule, see
        ``se_drift_batch``); mixed groups fall back to the per-request
        memoized path. A BT answer's schedule is its own, so its lookup
        misses and the SE recursion runs: the tail's cost on the chip is
        in PERF.md."""
        layout = "col" if key.layout == "col" else "row"
        r0 = reqs[0]
        v0 = _DRIFT_ATTRS(r0)
        p0 = r0.prior
        uniform = all(r.prior is p0 and _DRIFT_ATTRS(r) == v0 for r in reqs)
        counts = dict.fromkeys(DRIFT_COUNTS, 0)
        dr: list = []
        dr_add = dr.append
        isfin = math.isfinite
        try:
            if uniform:
                t = r0.n_iter
                s2 = np.asarray(trace.sigma2_hat)[:len(reqs), :t]
                ev = np.asarray(trace.extra_var)[:len(reqs), :t]
                sched = ev[0] if np.array_equiv(ev[:1], ev) else ev
                drifts = se_drift_batch(
                    r0.problem(), s2, sched, layout=layout,
                    n_proc=r0.n_proc, erasure_rate=r0.erasure_rate,
                    counts=counts)
                for res, d in zip(results, drifts.tolist()):
                    if isfin(d):
                        res.se_drift = d
                        dr_add(d)
            else:
                s2_all = np.asarray(trace.sigma2_hat)
                ev_all = np.asarray(trace.extra_var)
                for i, (r, res) in enumerate(zip(reqs, results)):
                    try:
                        d, _ = se_drift(r.problem(), s2_all[i, :r.n_iter],
                                        ev_all[i, :r.n_iter], layout=layout,
                                        n_proc=r.n_proc,
                                        erasure_rate=r.erasure_rate,
                                        counts=counts)
                    except Exception:
                        continue
                    if isfin(d):
                        res.se_drift = d
                        dr_add(d)
        except Exception:
            # the monitor is advisory: a drift failure never fails a solve
            dr.clear()
        self._tally(counts)
        if dr:
            ch["drift"].observe_many(dr)
            n_alert = sum(1 for d in dr if d > DRIFT_ALERT)
            if n_alert:
                ch["alerts"].inc(n_alert)
        return counts

    @staticmethod
    def _batch_spans(op: list | None, pl: list | None) -> list | None:
        """Batch-level spans, shared verbatim by every request in the
        batch (the batch is the unit of execution): operand build/upload,
        device compute (dispatch -> results materialized, i.e. the end of
        the pull) and the pull itself. None with telemetry off."""
        if op is None:
            return None
        return [op, ["compute", None, op[3], pl[3]], pl]

    def _singleton_ok(self, key: BucketKey, r: SolveRequest) -> bool:
        """Whether a lone request may skip batch padding + het-operand
        assembly and run the plain true-dims ``dispatch_single`` program
        (DESIGN.md §9). BT stays on the het path (its controller is the
        in-graph het table machinery); col stays batched (no plain
        single-dispatch entry point); erasure and measured-wire requests
        stay on the het path too (drop operands and symbol tracing are
        het-program features)."""
        return (self.singleton_fastpath and key.placement == "local"
                and key.layout == "row" and r.policy != "bt"
                and r.erasure_rate == 0.0 and not r.measure_wire)

    def _dispatch_singleton(self, key: BucketKey, r: SolveRequest) \
            -> _Pending:
        """Singleton fast path: true-dims solve on a plain engine, A from
        the operand cache, schedule riding as the ``sched`` operand. No
        bucket padding, no HetParams stack, no donation (A is
        cache-resident)."""
        eng = self._single_engine(r)
        self._singleton_dispatches += 1
        tel = self.telemetry
        with _phase("operands", tel) as op:
            with _phase("a_stack", tel):
                ck = ("single", self._fingerprint(r), r.n_proc,
                      eng.cfg.kernel_on, eng.cfg.a_dtype)
                # _split row-splits + tile-aligns + casts; cache the
                # result so a repeated-A stream pays it once
                build = lambda: eng._split(np.zeros(r.m, np.float32), r.a)[0]
                a_p = build() if self._opcache is None \
                    else self._opcache.get(ck, build)
            with _phase("params", tel):
                p = r.n_proc
                mp = r.m // p
                y_p = np.asarray(r.y, np.float32).reshape(p, mp)
                mp_pad = a_p.shape[1]
                if mp_pad != mp:   # kernel-path tile alignment
                    y_p = np.pad(y_p, ((0, 0), (0, mp_pad - mp)))
                if r.policy in ("fixed", "dp"):
                    sched = np.asarray(r.deltas, np.float32)
                else:
                    sched = np.full(r.n_iter, np.inf, np.float32)
        with _phase("dispatch", tel):
            x_outs = eng.dispatch_single(a_p, y_p, r.m, r.n, sched=sched)

        def finalize() -> list[SolveResult]:
            with _phase("pull", tel) as pl:
                trace = eng.trace_of(x_outs)
            return [self._request_tail(key, r, trace, None, 1,
                                       self._batch_spans(op, pl))]

        return finalize

    def _dispatch_proc(self, key: BucketKey, r: SolveRequest) -> _Pending:
        """Processor-sharded placement: the request owns the whole mesh
        for one ``dispatch_sharded`` call (still padded to the bucket
        shape, so the compile cache stays bounded). A rides from the
        operand cache — for these mesh-sized matrices the
        once-per-fingerprint pad+upload is the dominant saving; the
        sharded jit donates only y."""
        eng = self._engine(key)
        assert not r.measure_wire, \
            "measure_wire is unsupported on the processor-sharded " \
            "placement (symbols stay per-device); pin layout/shape " \
            "to a local or data-parallel bucket"
        tel = self.telemetry
        with _phase("operands", tel) as op:
            with _phase("a_stack", tel):
                a_p = self._a_slice(key, r, eng)
            with _phase("params", tel):
                y_b, params, has_bt = self._y_and_params(key, [r])
                hp = jax.tree.map(lambda v: np.asarray(v)[0], params)
        with _phase("dispatch", tel):
            x_outs = eng.dispatch_sharded(a_p, y_b[0], hp, self.mesh,
                                          has_bt=has_bt)

        def finalize() -> list[SolveResult]:
            with _phase("pull", tel) as pl:
                trace = eng.trace_of(x_outs)
            return [self._request_tail(key, r, trace, None, 1,
                                       self._batch_spans(op, pl))]

        return finalize

    def _result_one(self, key: BucketKey, r: SolveRequest, trace,
                    i: int | None, batch_size: int) -> SolveResult:
        """Unpad one request's slice of a trace (``i=None``: unbatched
        singleton / processor-sharded trace) and account its rates. With
        telemetry on, a measured-wire result carries its
        ``wire_measure`` span for the tail to place; the tails fill
        ``se_drift`` and the span list."""
        t = r.n_iter
        sel = (lambda a: a[:t]) if i is None else (lambda a: a[i, :t])
        x_pad = trace.x if i is None else trace.x[i]
        if key.layout == "col":
            # per-slice column padding: real columns are the leading
            # n/P entries of each processor's slice
            p = key.n_proc
            x = x_pad.reshape(p, key.n_pad // p)[:, :r.n // p].reshape(-1)
        else:
            x = x_pad[:r.n]
        s2 = sel(trace.sigma2_hat)
        deltas = sel(trace.deltas)
        extra_var = sel(trace.extra_var)
        rates = self._rates(r, s2, deltas, sel(trace.rates), extra_var)
        finite = np.isfinite(rates)
        wire = None
        wire_span = None
        if r.measure_wire and trace.symbols is not None:
            syms = trace.symbols if i is None else trace.symbols[i]
            # payload = length-N messages (row) / length-M residual
            # contributions (col); padding columns quantize zeros
            n_elem = r.m if key.layout == "col" else r.n
            with _phase("wire_measure", self.telemetry) as wire_span:
                wire = measure_wire(syms[:t, :, :n_elem], deltas, n_elem,
                                    drop=self._drop_mask(r),
                                    recovery=r.recovery,
                                    model=self.wire_model)
        return SolveResult(
            request_id=r.request_id,
            x=x.copy(),
            sigma2_hat=s2.copy(), deltas=deltas.copy(),
            extra_var=extra_var.copy(), rates=rates,
            total_bits=float(rates[finite].sum()),
            bucket=key, batch_size=batch_size,
            bytes_on_wire=None if wire is None else wire["bytes_on_wire"],
            payload_bytes=None if wire is None else wire["payload_bytes"],
            time_on_air_s=None if wire is None else wire["time_on_air_s"],
            energy_j=None if wire is None else wire["energy_j"],
            spans=None if wire_span is None else [wire_span],
        )

    def _request_tail(self, key: BucketKey, r: SolveRequest, trace,
                      i: int | None, batch_size: int,
                      shared: list | None) -> SolveResult:
        """One request's result and telemetry tail, for the singleton,
        processor-sharded and measured-wire paths (the batched hot path
        uses ``_batch_tail``): the same ``complete`` span with its
        ``results`` and ``drift`` children, per request, and the
        latency/drift histograms. ``shared`` is None with telemetry off."""
        if shared is None:
            return self._result_one(key, r, trace, i, batch_size)
        ch = self._layout_children(key.layout)
        sh0 = shared[0][2]
        with _phase("complete") as co:
            with _phase("results") as rs:
                res = self._result_one(key, r, trace, i, batch_size)
            with _phase("drift") as dr:
                counts = dict.fromkeys(DRIFT_COUNTS, 0)
                try:
                    d, _ = se_drift(
                        r.problem(), res.sigma2_hat, res.extra_var,
                        layout="col" if key.layout == "col" else "row",
                        n_proc=r.n_proc, erasure_rate=r.erasure_rate,
                        counts=counts)
                except Exception:
                    # a drift failure must never fail the solve: the
                    # monitor is advisory (NaN drift shows up in the
                    # histogram's absence)
                    d = math.nan
                self._tally(counts)
                dr.append(counts)
            if math.isfinite(d):
                res.se_drift = d
                ch["drift"].observe(d)
                if d > DRIFT_ALERT:
                    ch["alerts"].inc()
            head = self._admit_spans(r)
            t_a = head[-1][3] if head else sh0
            res.spans = [*head, ["batch_wait", None, t_a, sh0], *shared,
                         co, rs, *(res.spans or ()), dr]
        ch["requests"].inc()
        ch["batch_wait"].observe(sh0 - t_a)
        if head and head[0][0] == "admit":
            ch["latency"].observe(co[3] - head[0][2])
        return res

    def _rates(self, req: SolveRequest, s2, deltas, bt_rates,
               extra_var) -> np.ndarray:
        """Realized-rate accounting for one request (see SolveResult).

        Column requests model the quantized payload as the residual
        contribution's Gaussian (``residual_mixture``): the payload of
        round t is built from the estimate after round t-1, whose block
        MSE reads off *this* round's plug-in,
        d^{t-1} = kappa * (v̂_t - sigma_e^2 - P sigma_Q^2_t).  Round 0
        exchanges all-zero contributions — 0 bits at any bin size — and
        is counted as 0.0 whenever the request is rate-tracked at all
        (a fully lossless request stays untracked, all-inf).

        Under erasure the reported rates are *on-the-wire*: the delivered
        model rate times the recovery policy's wire factor (retransmit
        re-sends dropped packets, rate_up's allocated slot rate is what
        each slot transmits) — ``erasure_rate_factors``. Exactly the
        delivered rate on a lossless link.
        """
        rates = self._rates_delivered(req, s2, deltas, bt_rates, extra_var)
        if req.erasure_rate > 0.0:
            _, _, wire_f = erasure_rate_factors(req.erasure_rate,
                                                req.recovery)
            fin = np.isfinite(rates)
            rates = np.where(fin, rates * wire_f, rates)
        return rates

    def _rates_delivered(self, req: SolveRequest, s2, deltas, bt_rates,
                         extra_var) -> np.ndarray:
        if req.policy == "bt":
            return np.asarray(bt_rates, np.float64)
        if req.transport != "ecsq":
            # block transports spend a fixed wire rate every iteration:
            # `bits` per element plus a bf16 scale per block
            tp = _TRANSPORTS[req.transport]()
            rates = np.full(req.n_iter, tp.bits + 16.0 / tp.block)
            if req.layout == "col":
                rates[0] = 0.0   # zero contributions: nothing on the wire
            return rates
        rates = np.full(req.n_iter, np.inf)
        if not self.rate_accounting:
            return rates
        prob = req.problem() if req.layout == "col" else None
        sm = req.prior.second_moment
        for t in range(1 if req.layout == "col" else 0, req.n_iter):
            d = float(deltas[t])
            if not math.isfinite(d):
                continue
            if req.layout == "col":
                d_blk = prob.kappa * (float(s2[t]) - prob.sigma_e2
                                      - float(extra_var[t]))
                mix = residual_mixture(req.prior,
                                       min(max(d_blk, 1e-12), sm),
                                       prob.kappa, req.n_proc)
            else:
                mix = message_mixture(req.prior, float(s2[t]), req.n_proc)
            rates[t] = float(ecsq_entropy(d, mix)[0])
        if req.layout == "col" and np.isfinite(rates[1:]).any():
            rates[0] = 0.0
        return rates

    # -- AOT prewarm + observability (DESIGN.md §9) --------------------------

    def _spec_request(self, spec: PrewarmSpec) -> SolveRequest:
        """Dummy request with the spec's structural shape (zero operands:
        compilation keys on avals, not values)."""
        deltas = (np.full(spec.n_iter, np.inf, np.float32)
                  if spec.policy == "fixed" else None)
        return SolveRequest(
            y=np.zeros(spec.m, np.float32),
            a=np.zeros((spec.m, spec.n), np.float32),
            prior=spec.prior, snr_db=spec.snr_db, n_proc=spec.n_proc,
            n_iter=spec.n_iter, policy=spec.policy, deltas=deltas,
            transport=spec.transport, layout=spec.layout)

    def prewarm(self, menu, background: bool = False):
        """AOT-compile the bucket x batch-width grid for a traffic menu of
        ``PrewarmSpec``s, so steady-state requests never block on XLA.

        Blocking by default (returns the report dict; a compile error
        raises here); with ``background=True`` compilation runs on a
        daemon thread (returns the ``Thread``; traffic may flow
        immediately and converges to zero-compile as programs land —
        per-engine compile locks serialize against foreground dispatches
        of the same program), and a compile error there is raised by the
        next ``stats()``/``flush()``/``poll()``/``stream()`` collection.
        The report is surfaced on ``stats()["prewarm"]`` either way.

        Dummy operands bypass the operand cache (zero-A entries would
        poison it) and compiled programs key on operand avals, so runtime
        traffic of the same structural shape reuses them exactly.
        """
        menu = list(menu)
        if background:
            th = threading.Thread(target=self._prewarm_background,
                                  args=(menu,), name="solve-prewarm",
                                  daemon=True)
            self._prewarm_thread = th
            th.start()
            return th
        return self._prewarm_run(menu)

    def _prewarm_background(self, menu: list) -> None:
        try:
            self._prewarm_run(menu)
        except Exception as e:   # thread boundary: hand it to the caller
            with self._lock:
                self._prewarm_error = e

    def _prewarm_run(self, menu: list) -> dict:
        t0 = time.perf_counter()
        programs, buckets = 0, set()
        for spec in menu:
            req = self._prepare(self._spec_request(spec), assign_id=False)
            key = self._key_for(req)
            buckets.add(str(key))
            eng = self._engine(key)
            if key.placement == "proc":
                a_b, y_b, params, has_bt = self._het_operands(
                    key, [req], use_cache=False)
                hp = jax.tree.map(lambda v: np.asarray(v)[0], params)
                # placed like runtime A, so the program keys alike
                eng.dispatch_sharded(self._put_a(key, a_b[0], eng), y_b[0],
                                     hp, self.mesh, has_bt=has_bt,
                                     compile_only=True)
                programs += 1
                continue
            widths = spec.batch_widths
            if widths is None:
                widths = batch_width_ladder(
                    self.policy,
                    self.n_devices if key.placement == "data" else 1)
            for w in widths:
                w = pad_batch_size(min(int(w), self.policy.max_batch),
                                   self.policy)
                if key.placement == "data":
                    w = round_up(w, self.n_devices)
                a_b, y_b, params, has_bt = self._het_operands(
                    key, [req] * w, use_cache=False)
                if key.placement == "data":
                    shard = NamedSharding(self.mesh,
                                          PartitionSpec(self.mesh_axis))
                    a_b, y_b, params = jax.device_put((a_b, y_b, params),
                                                      shard)
                eng.dispatch_het(a_b, y_b, params, has_bt=has_bt,
                                 compile_only=True)
                programs += 1
            if self._singleton_ok(key, req):
                seng = self._single_engine(req)
                a_p, y_p = seng._split(req.y, req.a)
                sched = (req.deltas if req.policy in ("fixed", "dp")
                         else np.full(req.n_iter, np.inf, np.float32))
                seng.dispatch_single(a_p, y_p, req.m, req.n, sched=sched,
                                     compile_only=True)
                programs += 1
        report = {"programs": programs, "buckets": sorted(buckets),
                  "seconds": time.perf_counter() - t0}
        self._prewarm_report = report
        return report

    def compile_count(self) -> int:
        """Total XLA compiles across every engine this service owns (het
        bucket engines and singleton fast-path engines). Flat after
        prewarm under steady-state traffic — the zero-recompile
        invariant tests pin. Each engine's count is read through its
        ``counters()`` snapshot (taken under the engine's compile lock),
        so a background prewarm thread mid-compile is counted either
        fully or not at all — never half."""
        with self._lock:
            engines = (list(self._engines.values())
                       + list(self._wire_engines.values())
                       + list(self._single_engines.values()))
        return sum(e.counters()["compiles"] for e in engines)

    def _collect_metrics(self, reg: MetricsRegistry) -> None:
        """Snapshot-time collector: mirror the sources that already keep
        their own atomic counters into the registry (no hot-path writes;
        the telemetry plane's cost on the chip is in PERF.md)."""
        st = self.stats()
        lk = reg.counter("amp_se_prediction_lookups_total",
                         "SE-prediction lookups of the drift tails, by "
                         "memo result (a miss runs the SE recursion)",
                         ("result",))
        for result, v in self._se_lookups.items():
            lk.set_total(v, result=result)
        ev = reg.counter("amp_se_mmse_evaluations_total",
                         "MMSE evaluations of the drift tails' SE "
                         "recursions, by path (the per-prior table, or "
                         "the quadrature outside its domain)", ("path",))
        for path, v in self._se_mmse.items():
            ev.set_total(v, path=path)
        comp = reg.counter("amp_engine_compiles_total",
                           "XLA compiles per bucket engine", ("bucket",))
        disp = reg.counter("amp_engine_dispatches_total",
                           "Engine dispatches per bucket", ("bucket",))
        for label, v in st["compiles"]["by_bucket"].items():
            comp.set_total(v, bucket=label)
        for label, v in st["dispatches"]["by_bucket"].items():
            disp.set_total(v, bucket=label)
        reg.counter("amp_singleton_dispatches_total",
                    "Singleton fast-path dispatches").set_total(
                        st["singleton_dispatches"])
        dem = reg.counter("amp_bucket_demand_total",
                          "Requests ever admitted per bucket", ("bucket",))
        for k, v in st["bucket_demand"].items():
            dem.set_total(v, bucket=k)
        oc = st["operand_cache"]
        if oc is not None:
            for name in ("hits", "misses", "evictions"):
                reg.counter(f"amp_operand_cache_{name}_total",
                            f"Operand cache {name}").set_total(oc[name])
            reg.gauge("amp_operand_cache_bytes",
                      "Operand cache resident bytes").set(oc["bytes"])
            reg.gauge("amp_operand_cache_entries",
                      "Operand cache entries").set(oc["entries"])

    def metrics(self) -> dict:
        """Atomic JSON-able metrics snapshot (DESIGN.md §12): event-driven
        request/latency/drift series plus the pulled engine/cache/demand
        counters. Empty when constructed with ``telemetry=False``."""
        if self._registry is None:
            return {"metrics": []}
        return self._registry.snapshot()

    def metrics_text(self) -> str:
        """``metrics()`` rendered as Prometheus text exposition format."""
        return prometheus_text(self.metrics())

    def demand(self) -> dict:
        """Lifetime per-bucket admission counts (``Batcher.demand``)."""
        return self._batcher.demand()

    def take_demand(self) -> dict:
        """Per-bucket admissions since the previous take — the cluster
        autoscaler's scrape window (``Batcher.take_demand``)."""
        return self._batcher.take_demand()

    def stats(self) -> dict:
        """Hot-path observability: operand-cache counters, per-bucket
        compile/dispatch counts, singleton fast-path traffic, per-bucket
        demand (requests ever admitted), and the last prewarm report.

        The whole aggregation runs under the service lock and reads each
        engine through its atomic ``counters()`` snapshot: a concurrent
        background ``prewarm`` thread (which mutates the engine maps and
        bumps compile counters mid-flight) can therefore never produce a
        torn report where ``compiles.total`` disagrees with the engines
        that exist or demand counts reflect a different instant than the
        compile counts they are read next to."""
        self._raise_prewarm_error()
        with self._lock:
            engines = ([(k, e, "") for k, e in self._engines.items()]
                       + [(k, e, "/wire")
                          for k, e in self._wire_engines.items()])
            singles = list(self._single_engines.items())
            by_bucket = {}
            dispatches = {}
            for key, eng, tag in engines:
                label = (f"{key.layout}/{key.placement}/n{key.n_pad}"
                         f"/mp{key.mp_pad}/p{key.n_proc}/t{key.t_max}"
                         f"/{key.transport}{tag}")
                c = eng.counters()
                by_bucket[label] = c["compiles"]
                dispatches[label] = c["dispatches"]
            for (n, m, p, t, transport, _prior), eng in singles:
                label = f"single/n{n}/m{m}/p{p}/t{t}/{transport}"
                c = eng.counters()
                by_bucket[label] = c["compiles"]
                dispatches[label] = c["dispatches"]
            demand = self._batcher.demand()
            singleton_dispatches = self._singleton_dispatches
            prewarm_report = self._prewarm_report
            opstats = (self._opcache.stats()
                       if self._opcache is not None else None)
        return {
            "operand_cache": opstats,
            "compiles": {"total": sum(by_bucket.values()),
                         "by_bucket": by_bucket},
            "dispatches": {"total": sum(dispatches.values()),
                           "by_bucket": dispatches},
            "singleton_dispatches": singleton_dispatches,
            "bucket_demand": {str(k): v for k, v in demand.items()},
            "prewarm": prewarm_report,
        }
