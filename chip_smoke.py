#!/usr/bin/env python3
"""Bring-up smoke of the MP-AMP solve service on a TPU.

Drives the serving main path once — ``SolveService.prewarm`` (blocking),
then ``SolveService.stream`` into bucketed ``AmpEngine`` het programs that
run the Pallas LC kernels — at the paper's Sec. 4 operating point, and
checks every answer against centralized f32 AMP (``Precision.HIGHEST``,
the engine's jnp path) solved on the same chip.

  python3 chip_smoke.py [--seed S]           # one chip: row + column phases
  python3 chip_smoke.py --four-chips         # 4 chips: proc placement only

Phases (one process, one chip unless ``--four-chips``):

* row: N=10,000, M=3,000 (kappa=0.3), P=30, SNR 20 dB, Bernoulli-Gauss
  eps in {0.03, 0.05, 0.10} at the paper's horizons T in {8, 10, 20};
  32 requests, each with its own A from ``sample_problem``, policies
  lossless / fixed / DP / BT, ``max_batch=16``. The DP and BT rate models
  read the committed ``.cache/rd_*.npz`` tables; a missing table is an
  error, never a build.
* col: N=12,000, M=3,000, P=4 (N/M = 4 routes to the column layout),
  ``n_inner`` 1 and 2, lossless and fixed policies.
* four-chips: N=10,000, M=3,000, P=40 over a 4-device serve mesh
  (processor-sharded ``proc`` placement, 10 processors per chip) with
  exact psum and int8 / int4 compressed psum, each against the one-device
  solve of the same problem.

Envelopes (each result against its reference):

* ``EXACT_REL``: lossless results differ from the f32 reference by a mean
  squared difference below this fraction of the reference's own MSE;
* lossy row policies lose, against the lossless reference, the SDR that
  state evolution predicts for their realized quantizer noise: each
  request within ``SE_DB_REQ``, the mean over a policy's requests within
  ``SE_DB``; DP and BT at eps=0.05 land within ``GOLDEN_DB`` of the
  committed golden operating point (tests/golden/operating_point.json);
* compressed psum loses what state evolution predicts for its reported
  noise, within ``SE_DB`` (``SE_DB_BY_TRANSPORT`` for int4);
* fixed-bin column solves and int8 compressed psum stay within
  ``QUANT_MSE_RATIO`` of the lossless MSE (the tests' own envelopes).

Every line before the last reports a phase or a check. The last line is
one JSON object ``{"ok": true, "device": {...}}``, printed only when every
check passed; anything else exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

EPS = (0.03, 0.05, 0.10)
SNR_DB = 20.0
ROW = {"n": 10_000, "m": 3_000, "p": 30, "requests": 32, "max_batch": 16}
COL = {"n": 12_000, "m": 3_000, "p": 4, "t": 10, "requests": 8,
       "eps": (0.03, 0.05)}
PROC = {"n": 10_000, "m": 3_000, "p": 40, "eps": 0.05}
ROW_POLICIES = ("lossless", "fixed", "dp", "bt")
FIXED_DELTA = 0.005     # row fixed-policy ECSQ bin (iteration 0 lossless)
COL_FIXED_DELTA = 0.02  # column fixed-policy bin (round 0 lossless)

EXACT_REL = 1e-4
SE_DB = 1.0
# one draw's loss scatters around the SE prediction: over ten draws of DP
# at eps=0.1, T=20 (N=10,000) the gap had a spread of 0.42 dB and a worst
# case of 1.06 dB (jnp path on the CPU), so a single request gets twice
# the SE_DB that bounds a policy's mean
SE_DB_REQ = 2.0
# int4 compressed psum injects noise on the order of the AMP noise floor
# at the paper point; state evolution under-predicts its loss there (a
# 2.06 dB gap at N=4000 on CPU), so its SE envelope is wider
SE_DB_BY_TRANSPORT = {"block4": 3.0}
GOLDEN_DB = 1.5
QUANT_MSE_RATIO = {"fixed": 1.5, "block8": 1.25}
KERNEL_REL = 1e-4   # one LC step, kernel vs jnp reference (f32 at HIGHEST)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class Checks:
    """Collects pass/fail lines; a failed check never stops the run, so
    one chip call reports every envelope at once."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, phase: str, ok: bool, what: str) -> bool:
        log(phase, f"{'PASS' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(f"{phase}: {what}")
        return ok


class CompileClock:
    """Backend compiles (persistent-cache loads included) and cache hits,
    read off JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.count, self.hits

    def since(self, snap) -> str:
        s, c, h = snap
        return (f"{self.count - c} compiles in {self.seconds - s:.2f}s "
                f"({self.hits - h} persistent-cache hits)")


def mse(x, s0) -> float:
    import numpy as np
    return float(np.mean((np.asarray(x, np.float64) - s0) ** 2))


def sdr_db(prior, err: float) -> float:
    import numpy as np
    return float(10.0 * np.log10(prior.second_moment / max(err, 1e-30)))


def rel_msd(x, x_ref, mse_ref: float) -> float:
    """Mean squared difference to the reference, over its own MSE."""
    return mse(x, x_ref) / max(mse_ref, 1e-30)


def se_gap_db(prior, n: int, m: int, p: int, res, sdr_ref: float,
              sdr_req: float) -> float:
    """Measured SDR loss against the lossless reference, less the loss
    state evolution predicts for the result's realized per-iteration
    quantizer noise (``extra_var`` = P * sigma_Q^2)."""
    import numpy as np

    from repro.core.state_evolution import (CSProblem, sdr, se_trajectory,
                                            se_trajectory_quantized)
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=SNR_DB)
    extra = np.asarray(res.extra_var, np.float64)
    se_cen = sdr(se_trajectory(prob, len(extra))[-1], prob)
    se_q = sdr(se_trajectory_quantized(prob, extra / p, p)[-1], prob)
    return float((sdr_ref - sdr_req) - (se_cen - se_q))


def make_problems(seed: int, n: int, m: int, priors) -> list:
    """(prior, s0, A, y) per request, each A its own draw under ``seed``."""
    import jax

    from repro.core.amp import sample_problem
    from repro.core.state_evolution import CSProblem
    out = []
    for i, prior in enumerate(priors):
        prob = CSProblem(n=n, m=m, prior=prior, snr_db=SNR_DB)
        s0, a, y = sample_problem(jax.random.fold_in(
            jax.random.PRNGKey(seed), i), n, m, prior, prob.sigma_e2)
        out.append((prior, s0, a, y))
    return out


class Reference:
    """Plain f32 solves on the chip: the engine's jnp path (no Pallas) at
    ``Precision.HIGHEST``; one engine per (prior, T, P, layout) so each
    shape compiles once."""

    def __init__(self):
        self._engines: dict = {}

    def solve(self, prior, a, y, t: int, p: int = 1, n_inner: int = 0):
        from repro.core.engine import (AmpEngine, ColumnPartition,
                                       EngineConfig, ExactFusion,
                                       RowPartition)
        key = (prior, t, p, n_inner)
        eng = self._engines.get(key)
        if eng is None:
            layout = (ColumnPartition(n_inner=n_inner) if n_inner
                      else RowPartition())
            eng = self._engines[key] = AmpEngine(
                prior, EngineConfig(n_proc=p, n_iter=t, use_kernel=False,
                                    collect_symbols=False, collect_xs=False,
                                    layout=layout), ExactFusion())
        return eng.solve(y, a).x


def check_programs(checks: Checks, phase: str, svc, compiled: bool) -> None:
    """Kernel placement: every engine runs the Pallas path, not interpret
    mode; on the chip every compiled program holds Mosaic custom calls."""
    engines = (list(svc._engines.values()) + list(svc._wire_engines.values())
               + list(svc._single_engines.values()))
    checks(phase, all(e.cfg.kernel_on and not e.cfg.kernel_interpret
                      for e in engines) or not compiled,
           f"{len(engines)} engines: kernel_on and kernel_interpret=False")
    if not compiled:
        return
    texts = [ex.as_text() for e in engines for ex in e._exec_cache.values()]
    n_kernel = sum("tpu_custom_call" in t for t in texts)
    checks(phase, bool(texts) and n_kernel == len(texts),
           f"tpu_custom_call in {n_kernel}/{len(texts)} compiled programs")


def prewarm_and_stream(checks: Checks, phase: str, svc, menu, reqs):
    """Blocking prewarm of the traffic menu, then the stream; returns the
    results by request id and the seconds each step took."""
    t0 = time.perf_counter()
    rep = svc.prewarm(menu)
    t1 = time.perf_counter()
    results = {r.request_id: r for r in svc.stream(iter(reqs))}
    t2 = time.perf_counter()
    st = svc.stats()
    steady = svc.compile_count() - rep["programs"]
    log(phase, f"prewarm {rep['programs']} programs over "
               f"{len(rep['buckets'])} buckets in {t1 - t0:.2f}s; "
               f"stream {len(results)} requests in {t2 - t1:.2f}s "
               f"({st['dispatches']['total']} dispatches)")
    checks(phase, steady == 0, f"{steady} steady-state compiles after prewarm")
    checks(phase, len(results) == len(reqs),
           f"{len(results)}/{len(reqs)} results")
    return results, t1 - t0, t2 - t1


def row_phase(checks: Checks, seed: int, ref: Reference, shape=ROW,
              compiled: bool = True, **svc_kw) -> None:
    import numpy as np

    from repro.core.denoisers import BernoulliGauss
    from repro.core.state_evolution import PAPER_T
    from repro.serving import (BucketPolicy, PrewarmSpec, SolveRequest,
                               SolveService)

    n, m, p = shape["n"], shape["m"], shape["p"]
    t0 = time.perf_counter()
    priors = [BernoulliGauss(eps=EPS[i % len(EPS)])
              for i in range(shape["requests"])]
    probs = make_problems(seed, n, m, priors)
    reqs = []
    for i, (prior, s0, a, y) in enumerate(probs):
        t = PAPER_T[prior.eps]
        policy = ROW_POLICIES[(i // len(EPS)) % len(ROW_POLICIES)]
        kw = {}
        if policy == "fixed":
            deltas = np.full(t, FIXED_DELTA, np.float32)
            deltas[0] = np.inf
            kw["deltas"] = deltas
        reqs.append(SolveRequest(y=y, a=a, prior=prior, snr_db=SNR_DB,
                                 n_proc=p, n_iter=t, policy=policy,
                                 a_id=f"row{i}", **kw))
    log("row", f"{len(reqs)} problems N={n} M={m} P={p} generated in "
               f"{time.perf_counter() - t0:.2f}s")

    # one bucket for every horizon: T pads to the longest (masked early
    # exit), so the 32 requests fill two batches of 16
    t_max = max(PAPER_T[e] for e in EPS)
    svc = SolveService(policy=BucketPolicy(max_batch=shape["max_batch"],
                                           t_quantum=t_max), **svc_kw)
    # every batch holds BT requests: one program, the in-graph controller
    menu = [PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t_max, policy="bt",
                        prior=BernoulliGauss(eps=0.05), snr_db=SNR_DB,
                        batch_widths=(shape["max_batch"],))]
    results, _, _ = prewarm_and_stream(checks, "row", svc, menu, reqs)
    check_programs(checks, "row", svc, compiled)
    batches = sorted({r.batch_size for r in results.values()})
    checks("row", batches == [shape["max_batch"]],
           f"batch sizes {batches}")

    t0 = time.perf_counter()
    with open(os.path.join(ROOT, "tests", "golden",
                           "operating_point.json")) as fh:
        golden = json.load(fh)
    worst: dict = {}
    gaps: dict = {}
    at_golden: dict = {}
    for i, (prior, s0, a, y) in enumerate(probs):
        req, res = reqs[i], results[i]
        x_ref = np.asarray(ref.solve(prior, a, y, req.n_iter))
        mse_ref, mse_req = mse(x_ref, s0), mse(res.x, s0)
        sdr_ref, sdr_req = sdr_db(prior, mse_ref), sdr_db(prior, mse_req)
        tag = (f"req {i} eps={prior.eps} T={req.n_iter} {req.policy}: "
               f"SDR {sdr_req:.3f} dB (reference {sdr_ref:.3f} dB)")
        if req.policy == "lossless":
            d = rel_msd(res.x, x_ref, mse_ref)
            worst["lossless"] = max(worst.get("lossless", 0.0), d)
            checks("row", d <= EXACT_REL,
                   f"{tag}, msd/mse_ref {d:.3e} <= {EXACT_REL:g}")
            continue
        gap = se_gap_db(prior, n, m, p, res, sdr_ref, sdr_req)
        worst[req.policy] = max(worst.get(req.policy, 0.0), abs(gap))
        gaps.setdefault(req.policy, []).append(gap)
        checks("row", abs(gap) <= SE_DB_REQ and np.isfinite(sdr_req),
               f"{tag}, loss vs SE prediction {gap:+.3f} dB "
               f"(|.| <= {SE_DB_REQ}), {res.total_bits:.2f} bits")
        if prior.eps == 0.05 and req.policy in ("dp", "bt"):
            at_golden.setdefault(req.policy, []).append(sdr_req)
    for policy, got in sorted(gaps.items()):
        mean = float(np.mean(got))
        checks("row", abs(mean) <= SE_DB,
               f"{policy}: mean loss vs SE prediction {mean:+.3f} dB over "
               f"{len(got)} requests (|.| <= {SE_DB})")
    for policy, got in sorted(at_golden.items()):
        # the golden pins one draw at eps=0.05, T=10: compare the mean
        want = golden[f"{policy}_final_sdr_db"]
        mean = float(np.mean(got))
        checks("row", abs(mean - want) <= GOLDEN_DB,
               f"{policy} at eps=0.05: mean SDR {mean:.3f} dB over "
               f"{len(got)} requests vs golden {want:.3f} dB "
               f"(|.| <= {GOLDEN_DB})")
    log("row", f"references in {time.perf_counter() - t0:.2f}s; worst "
               f"envelope use {json.dumps(worst)}")


def col_phase(checks: Checks, seed: int, ref: Reference, shape=COL,
              compiled: bool = True, **svc_kw) -> None:
    import numpy as np

    from repro.core.denoisers import BernoulliGauss
    from repro.serving import (BucketPolicy, PrewarmSpec, SolveRequest,
                               SolveService)

    n, m, p, t = shape["n"], shape["m"], shape["p"], shape["t"]
    priors = [BernoulliGauss(eps=shape["eps"][i % len(shape["eps"])])
              for i in range(shape["requests"])]
    probs = make_problems(seed + 1, n, m, priors)
    for n_inner in (1, 2):
        phase = f"col n_inner={n_inner}"
        reqs = []
        for i, (prior, s0, a, y) in enumerate(probs):
            policy = ("lossless", "fixed")[(i // len(shape["eps"])) % 2]
            kw = {}
            if policy == "fixed":
                deltas = np.full(t, COL_FIXED_DELTA, np.float32)
                deltas[0] = np.inf
                kw["deltas"] = deltas
            reqs.append(SolveRequest(y=y, a=a, prior=prior, snr_db=SNR_DB,
                                     n_proc=p, n_iter=t, policy=policy,
                                     a_id=f"col{i}", **kw))
        svc = SolveService(policy=BucketPolicy(max_batch=16),
                           col_inner=n_inner, **svc_kw)
        menu = [PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t, policy="lossless",
                            prior=priors[0], snr_db=SNR_DB,
                            batch_widths=(len(reqs),))]
        results, _, _ = prewarm_and_stream(checks, phase, svc, menu, reqs)
        check_programs(checks, phase, svc, compiled)
        layouts = sorted({r.bucket.layout for r in results.values()})
        checks(phase, layouts == ["col"], f"layouts {layouts}")
        lossless_mse: dict = {}
        for i, (prior, s0, a, y) in enumerate(probs):
            req, res = reqs[i], results[i]
            # n_inner=1 column fusion is centralized AMP exactly; at
            # n_inner=2 the reference is the same C-MP-AMP on the jnp path
            x_ref = np.asarray(ref.solve(prior, a, y, t) if n_inner == 1
                               else ref.solve(prior, a, y, t, p, n_inner))
            mse_ref, mse_req = mse(x_ref, s0), mse(res.x, s0)
            tag = (f"req {i} eps={prior.eps} {req.policy}: SDR "
                   f"{sdr_db(prior, mse_req):.3f} dB (reference "
                   f"{sdr_db(prior, mse_ref):.3f} dB)")
            if req.policy == "lossless":
                d = rel_msd(res.x, x_ref, mse_ref)
                lossless_mse[prior.eps] = mse_ref
                checks(phase, d <= EXACT_REL,
                       f"{tag}, msd/mse_ref {d:.3e} <= {EXACT_REL:g}")
            else:
                ratio = mse_req / mse_ref
                lim = QUANT_MSE_RATIO["fixed"]
                checks(phase, ratio <= lim,
                       f"{tag}, mse/mse_lossless {ratio:.3f} <= {lim}")


def kernel_phase(checks: Checks, seed: int, interpret: bool = False,
                 row=ROW, col=COL) -> None:
    """Each LC kernel once against its jnp reference (``kernels/amp_fused/
    ref.py``, ``Precision.HIGHEST``) on the same random operands, at the
    shapes the row and column buckets run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.amp_fused import ops, ref

    def rel(got, want) -> float:
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                      1e-30))

    def report(name: str, got, want) -> None:
        errs = [rel(g, w) for g, w in zip(got, want)]
        checks("kernels", max(errs) <= KERNEL_REL,
               f"{name}: max |kernel - ref| / max |ref| per output "
               f"{', '.join(f'{e:.2e}' for e in errs)} <= {KERNEL_REL:g}")

    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    p, mp, n = row["p"], row["m"] // row["p"], row["n"]
    a0 = jax.random.normal(k[0], (p, mp, n)) / np.sqrt(row["m"])
    y0 = jax.random.normal(k[1], (p, mp))
    x0 = jax.random.normal(k[2], (n,)) * 0.3
    for dt in (jnp.float32, jnp.bfloat16):
        # the tiles, and so N's padding, depend on the dtype A streams in
        ad, y = ops.pad_row_shards(a0.astype(dt), y0)
        x = jnp.pad(x0, (0, ad.shape[2] - n))
        z = jax.random.normal(k[3], y.shape) * 0.1
        got = jax.jit(lambda *v: ops.amp_local_grid(
            *v, 0.4, p, use_pallas=True, interpret=interpret))(ad, x, y, z)
        want = jax.jit(lambda *v: ref.amp_local_ref_grid(*v, 0.4, p))(
            ad, x, y, z)
        report(f"row LC {tuple(ad.shape)} A {jnp.dtype(dt).name}", got, want)

    p, m, np_ = col["p"], col["m"], col["n"] // col["p"]
    a = jax.random.normal(k[4], (p, m, np_)) / np.sqrt(m)
    a, g = ops.pad_col_shards(a, jax.random.normal(k[5], (m,)) * 0.1)
    x = jax.random.normal(k[6], (p, np_)) * 0.3
    zp = jax.random.normal(k[7], (p, a.shape[1])) * 0.1
    mask = jnp.ones(np_)
    scal = (float(m), 0.05, 0.0, 1.0)
    report(f"col residual {tuple(a.shape)}",
           [jax.jit(lambda a, x: ops.col_residual(
               a, x, use_pallas=True, interpret=interpret))(a, x)],
           [jax.jit(ref.col_residual_ref)(a, x)])
    for update_z in (True, False):
        got = jax.jit(lambda *v: ops.col_inner_step(
            *v, *scal, update_z, use_pallas=True, interpret=interpret))(
            a, x, x * 0.5, zp, g, mask)
        want = jax.jit(lambda *v: ref.col_inner_step_ref(
            *v, *scal, update_z))(a, x, x * 0.5, zp, g, mask)
        report(f"col inner update_z={update_z} {tuple(a.shape)}", got, want)


def proc_phase(checks: Checks, seed: int, shape=PROC,
               compiled: bool = True, **svc_kw) -> None:
    import jax
    import numpy as np

    from repro.core.denoisers import BernoulliGauss
    from repro.core.state_evolution import PAPER_T
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import (BucketPolicy, PrewarmSpec, SolveRequest,
                               SolveService)

    n, m, p = shape["n"], shape["m"], shape["p"]
    prior = BernoulliGauss(eps=shape["eps"])
    t = PAPER_T[prior.eps]
    (_, s0, a, y), = make_problems(seed, n, m, [prior])
    mesh = make_serve_mesh(4)
    svc = SolveService(policy=BucketPolicy(max_batch=4), mesh=mesh, **svc_kw)
    local = SolveService(**svc_kw)
    transports = ("ecsq", "block8", "block4")
    req = lambda tr: SolveRequest(y=y, a=a, prior=prior, snr_db=SNR_DB,
                                  n_proc=p, n_iter=t, transport=tr,
                                  a_id="proc")
    menu = [PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t, transport=tr,
                        prior=prior, snr_db=SNR_DB) for tr in transports]
    results, _, _ = prewarm_and_stream(checks, "proc", svc, menu,
                                       [req(tr) for tr in transports])
    check_programs(checks, "proc", svc, compiled)
    placements = sorted({r.bucket.placement for r in results.values()})
    checks("proc", placements == ["proc"], f"placements {placements}")

    resident = [v for v, _ in svc._opcache._entries.values()]
    devs = [sorted(s.device.id for s in v.addressable_shards)
            for v in resident]
    checks("proc", len(resident) == 1 and len(set(devs[0])) == 4,
           f"A shards on devices {devs} ({jax.device_count()} visible)")

    t0 = time.perf_counter()
    base, = local.solve([req("ecsq")])
    log("proc", f"one-device solve ({base.bucket.placement}) in "
                f"{time.perf_counter() - t0:.2f}s")
    mse_base = mse(base.x, s0)
    for i, tr in enumerate(transports):
        res = results[i]
        tag = (f"{tr}: SDR {sdr_db(prior, mse(res.x, s0)):.3f} dB "
               f"(one device {sdr_db(prior, mse_base):.3f} dB)")
        if tr == "ecsq":
            d = rel_msd(res.x, base.x, mse_base)
            checks("proc", d <= EXACT_REL,
                   f"exact psum {tag}, msd/mse_ref {d:.3e} <= {EXACT_REL:g}")
            continue
        sdr_base, sdr_req = sdr_db(prior, mse_base), sdr_db(prior, mse(res.x, s0))
        gap = se_gap_db(prior, n, m, p, res, sdr_base, sdr_req)
        tol = SE_DB_BY_TRANSPORT.get(tr, SE_DB)
        # iteration 0 fuses exactly; every later one reports its noise
        checks("proc", abs(gap) <= tol
               and bool(np.all(res.extra_var[1:] > 0)),
               f"compressed psum {tag}, loss vs SE prediction {gap:+.3f} dB "
               f"(|.| <= {tol}), noise accounted")
        lim = QUANT_MSE_RATIO.get(tr)
        if lim is not None:
            ratio = mse(res.x, s0) / mse_base
            checks("proc", ratio <= lim,
                   f"compressed psum {tr}: mse ratio {ratio:.3f} <= {lim}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="problem draws (every A, s0 and noise)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device processor-sharded phase "
                         "and its one-device comparison")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    n_chips = len(jax.devices())
    if args.four_chips and n_chips < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {n_chips}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.core.denoisers import BernoulliGauss
    from repro.core.rate_distortion import RDModel

    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    log("setup", f"device {dev.device_kind!r} x{n_chips}, jax "
                 f"{jax.__version__}, compile cache {cache_dir}")
    for eps in EPS:   # the DP/BT rate models must never build a table
        RDModel(BernoulliGauss(eps=eps), build=False)

    checks = Checks()
    ref = Reference()
    phases = ([("proc", lambda: proc_phase(checks, args.seed))]
              if args.four_chips else
              [("kernels", lambda: kernel_phase(checks, args.seed)),
               ("row", lambda: row_phase(checks, args.seed, ref)),
               ("col", lambda: col_phase(checks, args.seed, ref))])
    t_all = time.perf_counter()
    for name, run in phases:
        t0, snap = time.perf_counter(), clock.snapshot()
        run()
        log(name, f"phase {time.perf_counter() - t0:.2f}s; "
                  f"{clock.since(snap)}")
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log("total", f"{time.perf_counter() - t_all:.2f}s; "
                 f"{clock.since((0.0, 0, 0))}; peak_bytes_in_use "
                 f"{peak} ({peak / 2**30:.2f} GiB)")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed:",
              *checks.failed, sep="\n  ", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
