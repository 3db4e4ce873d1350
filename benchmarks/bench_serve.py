"""Serving-layer benchmark: batching, placement, and hot-path latency.

Measurements (DESIGN.md §5-§6, hot path §9):

  * batched vs sequential — the same B CS requests solved one
    ``AmpEngine.solve`` at a time vs one ``SolveService`` dispatch
    (>=2x at B=32 on CPU under honest interleaved timing — the historic
    5x figure compared against an under-warmed sequential baseline;
    ISSUE 6 acceptance: >=1x at B=1 with prewarm), and
  * request latency percentiles — a prewarmed continuous-batching stream
    timed per request (submit -> result), p50/p95/p99 plus the service's
    operand-cache / compile counters, and
  * data-parallel placement — the same bucket load through a service
    whose batch axis is sharded across ``--devices`` mesh devices
    (compare req/s against a ``--devices 1`` run; ISSUE 3 acceptance:
    >=3x at 8 devices on a multi-core host), and
  * processor-sharded placement — one large single request whose P maps
    onto the mesh axis, exact wire vs int8 compressed wire, and
  * measured wire bytes — a ``measure_wire`` bucket whose per-round
    symbol streams are actually rANS-coded host-side (DESIGN.md §10):
    measured payload vs the model entropy H_Q, bytes-on-wire /
    time-on-air / energy columns, and (with ``--erasure``) the same load
    over a lossy link under both recovery policies, and
  * telemetry plane (DESIGN.md §12) — SE-drift percentiles +
    incomplete-span-tree counts on the latency stream, and per-frame TCP
    round-trips over a loopback ``BackendServer`` leg in the cluster
    section. (The telemetry plane's cost is measured on the chip, PERF.md.)

Timing methodology (shared with ``bench_kernels.py``): explicit warmup
first (compiles and cache fills excluded), then min over ``--reps``
rounds with the compared variants interleaved round-robin inside each
round — noisy-neighbor phases on shared CI boxes hit every variant
equally, which is what the pre-overhaul single-shot loop got wrong
(seq req/s swung 5x between rows of one config).

Results print as a table and are written machine-readable to
``BENCH_serve.json`` (req/s, latency percentiles, cache/compile
counters, per-placement timings) so CI can archive the perf trajectory
and diff p50 against the committed baseline.

  PYTHONPATH=src python benchmarks/bench_serve.py [--smoke] [--devices 8]
                                                  [--no-prewarm]

``--devices K`` forces K host-platform devices (set XLA_FLAGS before the
first jax import; run once with K=1 and once with K=8 to compare).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))


def make_load(n: int, m: int, p: int, t: int, b: int, eps: float = 0.1,
              layout: str | None = "row"):
    import jax
    import numpy as np
    from repro.core.amp import sample_problem
    from repro.core.denoisers import BernoulliGauss
    from repro.core.state_evolution import CSProblem
    from repro.serving import SolveRequest

    prior = BernoulliGauss(eps=eps)
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=20.0)
    deltas = np.full(t, 0.05, np.float32)
    deltas[0] = np.inf
    reqs, s0s = [], []
    for i in range(b):
        s0, a, y = sample_problem(jax.random.PRNGKey(i), n, m, prior,
                                  prob.sigma_e2)
        reqs.append(SolveRequest(y=y, a=a, prior=prior, n_proc=p, n_iter=t,
                                 policy="fixed", deltas=deltas,
                                 layout=layout))
        s0s.append(s0)
    return prior, deltas, reqs, s0s


def time_variants(ops: dict, reps: int, inner: int = 1) -> dict:
    """Seconds per call per variant: explicit warmup, then min over
    ``reps`` rounds with variants interleaved round-robin within each
    round (same methodology as ``bench_kernels.py``)."""
    results = {k: fn() for k, fn in ops.items()}   # warmup / compile
    best = {k: float("inf") for k in ops}
    for _ in range(reps):
        for k, fn in ops.items():
            t0 = time.perf_counter()
            for _ in range(inner):
                results[k] = fn()
            best[k] = min(best[k], (time.perf_counter() - t0) / inner)
    return best, results


def best_of(fn, reps: int):
    """Single-variant min-over-reps (placement benches: nothing to
    interleave against). Callers warm up explicitly first."""
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        res = fn()
        best = min(best, time.perf_counter() - t0)
        out = res
    return best, out


def bench_width(n: int, m: int, p: int, t: int, b: int, reps: int,
                prewarm: bool):
    """Batched service vs one-solve-at-a-time, single device,
    interleaved round-robin timing."""
    import numpy as np
    from repro.core.engine import (AmpEngine, EcsqTransport, EngineConfig,
                                   FixedSchedule)
    from repro.serving import BucketPolicy, PrewarmSpec, SolveService

    prior, deltas, reqs, s0s = make_load(n, m, p, t, b)

    # sequential baseline: one engine (compile shared across requests),
    # one dispatch per request
    eng = AmpEngine(prior,
                    EngineConfig(n_proc=p, n_iter=t, collect_symbols=False,
                                 collect_xs=False),
                    EcsqTransport(), FixedSchedule(deltas))

    # batched service: everything lands in one bucket -> one solve_het call
    # (quanta sized to the load so the bucket pads nothing; the default
    # 256-element quantum would double the padded compute at N=128)
    svc = SolveService(policy=BucketPolicy(max_batch=max(b, 1),
                                           n_quantum=64, mp_quantum=8),
                       rate_accounting=False)
    if prewarm:
        svc.prewarm([PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t,
                                 policy="fixed", prior=prior,
                                 batch_widths=(b,))])

    times, results = time_variants(
        {"seq": lambda: [eng.solve(r.y, r.a) for r in reqs],
         "svc": lambda: svc.solve(reqs)}, reps)

    # correctness spot check: batched == sequential estimates
    max_mse_diff = max(
        float(np.mean((sr.x - br.x) ** 2))
        for sr, br in zip(results["seq"], results["svc"]))
    return times["seq"], times["svc"], max_mse_diff


def bench_latency(n: int, m: int, p: int, t: int, n_req: int, reps: int,
                  prewarm: bool):
    """End-to-end request latency (submit -> result) through a prewarmed
    continuous-batching stream; percentiles over all reps pooled, plus
    the service's hot-path counters and the telemetry plane's health
    columns (SE-drift percentile, incomplete span trees — DESIGN.md
    §12)."""
    import numpy as np
    from repro.serving import BucketPolicy, PrewarmSpec, SolveService
    from repro.telemetry import DRIFT_ALERT, missing_spans, spans_monotonic

    prior, _, reqs, _ = make_load(n, m, p, t, n_req)
    svc = SolveService(policy=BucketPolicy(max_batch=16, n_quantum=64,
                                           mp_quantum=8),
                       rate_accounting=False)
    if prewarm:
        svc.prewarm([PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t,
                                 policy="fixed", prior=prior)])
    list(svc.stream(iter(reqs)))          # warmup (compiles + cache fill)
    compiles_warm = svc.compile_count()

    lats, steady = [], []
    for _ in range(reps):
        base = svc._next_id
        tsub = []

        def feed():
            for r in reqs:
                tsub.append(time.perf_counter())
                yield dataclass_replace(r)

        for res in svc.stream(feed()):
            lats.append(time.perf_counter() - tsub[res.request_id - base])
            steady.append(res)

    lats_ms = np.asarray(lats) * 1e3
    drifts = [r.se_drift for r in steady
              if r.se_drift is not None and np.isfinite(r.se_drift)]
    incomplete = sum(1 for r in steady
                     if missing_spans(r.spans)
                     or not spans_monotonic(r.spans))
    stats = svc.stats()
    return {
        "n": n, "m": m, "p": p, "t": t, "n_req": n_req, "reps": reps,
        "prewarm": prewarm,
        "p50_ms": float(np.percentile(lats_ms, 50)),
        "p95_ms": float(np.percentile(lats_ms, 95)),
        "p99_ms": float(np.percentile(lats_ms, 99)),
        "mean_ms": float(lats_ms.mean()),
        "steady_state_compiles": svc.compile_count() - compiles_warm,
        # telemetry health (DESIGN.md §12): drift is advisory at this
        # small N (heavy-tailed finite-size realization noise, see
        # tests/test_telemetry.py), incomplete span trees must be 0
        "se_drift_p95": (float(np.percentile(drifts, 95))
                         if drifts else None),
        "se_drift_median": (float(np.median(drifts)) if drifts else None),
        "se_drift_alerts": int(sum(1 for d in drifts if d > DRIFT_ALERT)),
        "monitored_requests": len(drifts),
        "incomplete_spans": int(incomplete),
    }, stats


def bench_tcp_rtt(n: int, m: int, p: int, t: int, b: int, prewarm: bool):
    """Per-frame TCP round-trips over a loopback ``BackendServer`` leg
    (DESIGN.md §12): the codec + socket overhead a remote host adds per
    frame kind, measured on the same prewarmed submit/flush path the
    cluster section routes. Two passes; the window holds both, so the
    percentiles cover warm steady state plus the cold first submit."""
    from repro.serving import BucketPolicy, PrewarmSpec, SolveService
    from repro.serving.frontend import (BackendServer, LocalBackend,
                                        TcpBackend)

    prior, _, reqs, _ = make_load(n, m, p, t, b)
    policy = BucketPolicy(max_batch=max(b, 1), n_quantum=64, mp_quantum=8)
    server = BackendServer(LocalBackend(
        "loop0", SolveService(policy=policy, rate_accounting=False)))
    server.start()
    tcp = TcpBackend((server.host, server.port), "loop0")
    try:
        if prewarm:
            tcp.prewarm([PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t,
                                     policy="fixed", prior=prior,
                                     batch_widths=(b,))])
        for _ in range(2):     # pass 2 is warm: compiles + cache filled
            for r in reqs:
                tcp.submit(dataclass_replace(r))
            tcp.flush()
        tcp.metrics()          # exercise the metrics frame kind too
        return tcp.rtt_stats()
    finally:
        tcp.shutdown_server()
        tcp.close()
        server.stop()


def bench_data_parallel(n: int, m: int, p: int, t: int, b: int, reps: int,
                        devices: int):
    """One bucket of B small requests through the placement dispatcher:
    batch axis sharded over the mesh when devices > 1, local otherwise."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import BucketPolicy, SolveService
    from repro.serving.buckets import round_up

    _, _, reqs, _ = make_load(n, m, p, t, b)
    # pin the mesh to the requested device count even if the host exposes
    # more (a pre-set XLA_FLAGS would otherwise mislabel the measurement);
    # max_batch must be a device multiple for data-parallel dispatch
    mesh = make_serve_mesh(devices) if devices > 1 else None
    svc = SolveService(policy=BucketPolicy(max_batch=round_up(max(b, devices),
                                                              devices),
                                           n_quantum=64, mp_quantum=8),
                       rate_accounting=False, mesh=mesh)
    res = svc.solve(reqs)  # warmup/compile
    placement = res[0].bucket.placement
    dt, _ = best_of(lambda: svc.solve(reqs), reps)
    return dt, placement, len(svc._engines)


def bench_proc_sharded(n: int, m: int, p: int, t: int, reps: int,
                       devices: int):
    """One large single request: processor-sharded over the mesh (exact
    and int8-compressed wire) when devices > 1, local otherwise."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import BucketPolicy, SolveService
    from repro.serving.buckets import round_up

    _, _, reqs, _ = make_load(n, m, p, t, 1)
    req = reqs[0]
    mesh = make_serve_mesh(devices) if devices > 1 else None
    max_batch = round_up(128, devices)
    out = {}
    for transport in ("ecsq", "block8"):
        svc = SolveService(policy=BucketPolicy(shard_elems=1,
                                               max_batch=max_batch),
                           rate_accounting=False, mesh=mesh)
        r = dataclass_replace(req, transport=transport,
                              policy="lossless", deltas=None)
        res, = svc.solve([r])  # warmup/compile
        dt, _ = best_of(lambda: svc.solve([r]), reps)
        out[transport] = {"seconds": dt, "placement": res.bucket.placement}
    return out


def bench_col_bucket(n: int, m: int, p: int, t: int, b: int, reps: int,
                     devices: int):
    """A tall-N bucket (auto-routed to the C-MP-AMP column layout,
    DESIGN.md §7) through the same dispatcher: layout routing must not
    cost throughput relative to a row bucket of the same element count."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import BucketPolicy, SolveService
    from repro.serving.buckets import round_up

    _, _, reqs, s0s = make_load(n, m, p, t, b, eps=0.02, layout=None)
    mesh = make_serve_mesh(devices) if devices > 1 else None
    svc = SolveService(policy=BucketPolicy(max_batch=round_up(max(b, devices),
                                                              devices),
                                           n_quantum=64, mp_quantum=8),
                       rate_accounting=False, mesh=mesh)
    res = svc.solve(reqs)  # warmup/compile
    assert res[0].bucket.layout == "col", res[0].bucket
    import numpy as np
    mse = float(np.mean([r.mse(s) for r, s in zip(res, s0s)]))
    dt, _ = best_of(lambda: svc.solve(reqs), reps)
    return dt, res[0].bucket.placement, mse


def bench_wire(n: int, m: int, p: int, t: int, b: int, reps: int,
               erasure: float):
    """Measured-wire accounting (DESIGN.md §10): every request opts into
    ``measure_wire``; the clean pass pins measured rANS payload against
    the model entropy, the lossy pass (``erasure > 0``) reports the byte
    cost of each recovery policy on the same masks."""
    import numpy as np
    from repro.serving import BucketPolicy, SolveService

    _, _, reqs, s0s = make_load(n, m, p, t, b)
    svc = SolveService(policy=BucketPolicy(max_batch=max(b, 1),
                                           n_quantum=64, mp_quantum=8))

    def run(rate, recovery):
        wreqs = [dataclass_replace(r, measure_wire=True, erasure_rate=rate,
                                   erasure_seed=i, recovery=recovery)
                 for i, r in enumerate(reqs)]
        svc.solve(wreqs)                   # warmup/compile
        dt, res = best_of(lambda: svc.solve(wreqs), reps)
        row = {
            "seconds": dt,
            "mse": float(np.mean([r.mse(s)
                                  for r, s in zip(res, s0s)])),
            "bytes_on_wire": float(np.mean([r.bytes_on_wire
                                            for r in res])),
            "payload_bytes": float(np.mean([r.payload_bytes
                                            for r in res])),
            "time_on_air_s": float(np.mean([r.time_on_air_s
                                            for r in res])),
            "energy_j": float(np.mean([r.energy_j for r in res])),
        }
        # delivered-rate model bytes (H_Q per element per processor) —
        # the number the measured rANS payload must land within ~5% of;
        # reported rates are on-the-wire, so undo the recovery factor
        from repro.core.rate_alloc import erasure_rate_factors
        _, _, wire_f = erasure_rate_factors(rate, recovery)
        model = []
        for r in res:
            fin = np.isfinite(r.rates) & (r.rates > 0)
            delivered = r.rates[fin].sum() / wire_f
            lossless = float((~fin).sum()) * 32.0
            model.append((delivered * p + lossless * p) * n / 8.0)
        row["model_payload_bytes"] = float(np.mean(model))
        row["payload_vs_model"] = (row["payload_bytes"]
                                   / row["model_payload_bytes"])
        return row

    out = {"clean": run(0.0, "retransmit")}
    if erasure > 0.0:
        out["retransmit"] = run(erasure, "retransmit")
        out["rate_up"] = run(erasure, "rate_up")
    return out


def bench_cluster(n: int, m: int, p: int, t: int, b: int, reps: int,
                  hosts: int, prewarm: bool):
    """Multi-host elastic serving plane (DESIGN.md §11), emulated on one
    box: a ``ClusterService`` over ``hosts`` in-process backends vs a
    single ``SolveService`` on the same total device count.

    Single-core emulation methodology: the box cannot run two hosts'
    XLA programs genuinely in parallel, so the bench *routes* the full
    stream through the real cluster router (``partition``), times each
    host's share in isolation, and reports

        cluster wall = max over hosts of (share wall) + routing overhead

    — the wall a real 2-host deployment would see, assuming hosts
    compute concurrently (they do: separate processes, separate
    devices) and the router is the only serial stage (it is: routing is
    pure bookkeeping, measured here as the min over reps of a warm
    ``partition`` pass — steady-state routing cost, not first-call dict
    setup). The baseline and every host share are timed interleaved
    round-robin in the same rep loop (``time_variants``): timing them
    in separate sequential loops lets a few percent of box-load drift
    masquerade as a scaling loss. Aggregate req/s and weak scaling
    derive from that wall. A full ``ClusterService.solve`` pass then
    pins bit-identity against the single-host results and the
    zero-steady-state-compile invariant.
    """
    import numpy as np
    from repro.serving import (BucketPolicy, ClusterService, PrewarmSpec,
                               RouterPolicy, SolveService)

    prior, _, reqs, _ = make_load(n, m, p, t, b)
    policy = BucketPolicy(max_batch=8, n_quantum=64, mp_quantum=8)
    menu = [PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t, policy="fixed",
                        prior=prior, batch_widths=(8,))]

    # single-host baseline: same policy, same prewarm, whole stream
    svc = SolveService(policy=policy, rate_accounting=False)
    if prewarm:
        svc.prewarm(menu)
    base_res = svc.solve(reqs)                    # warmup + reference

    # cluster: every bucket replicated on every host (min_replicas) so
    # the least-loaded router spreads one bucket's traffic — the regime
    # the weak-scaling claim is about
    cl = ClusterService(n_hosts=hosts, policy=policy,
                        router_policy=RouterPolicy(min_replicas=hosts),
                        rate_accounting=False)
    if prewarm:
        cl.prewarm(menu)

    shares = cl.partition(reqs)                   # cold pass fixes shares
    route_overhead, _ = best_of(lambda: cl.partition(reqs), reps)

    for hid, share in shares.items():             # warmup per host
        cl.backends[hid].service.solve(share)
    compiles_warm = cl.compile_count()

    ops = {"1host": lambda: svc.solve(reqs)}
    for hid, share in shares.items():
        ops[hid] = (lambda be=cl.backends[hid], sh=share:
                    be.service.solve(sh))
    walls, _ = time_variants(ops, reps)
    wall_1 = walls["1host"]
    host_walls = {hid: walls[hid] for hid in shares}
    wall_cluster = max(host_walls.values()) + route_overhead

    # bit-identity: the routed stream through the full frontend must
    # reproduce the single-host results exactly (same padded batch
    # width -> same compiled program; vmap lanes are independent)
    cl_res = cl.solve(reqs)
    max_dx = max(float(np.max(np.abs(cr.x - br.x)))
                 for cr, br in zip(cl_res, base_res))

    rt = cl.router.stats()
    return {
        "hosts": hosts, "n": n, "m": m, "p": p, "t": t, "batch": b,
        "max_batch": policy.max_batch, "prewarm": prewarm,
        "req_s_1host": b / wall_1,
        "req_s_cluster": b / wall_cluster,
        "weak_scaling": wall_1 / wall_cluster,
        "per_host_req_s": {hid: len(shares[hid]) / w
                           for hid, w in host_walls.items()},
        "share_sizes": {hid: len(s) for hid, s in shares.items()},
        "route_overhead_s": route_overhead,
        "imbalance": rt["imbalance"],
        "steady_state_compiles": cl.compile_count() - compiles_warm,
        "bitwise_max_abs_diff": max_dx,
        "methodology": "emulated hosts on one box: stream routed by the "
                       "real ClusterRouter (partition), baseline and "
                       "host shares timed interleaved round-robin, "
                       "cluster wall = max host wall + steady-state "
                       "routing overhead (min over warm partitions)",
    }


def bench_chaos(n: int, m: int, p: int, t: int, b: int, hosts: int,
                prewarm: bool):
    """Failure-injection drill (DESIGN.md §13): the same stream through
    a cluster whose last host is killed mid-stream by a deterministic
    ``FaultPlan``, measuring what fault tolerance costs and proving
    what it preserves — zero lost requests, bit-identical replays, and
    the detect -> recovered latency distribution. The kill lands on the
    victim's 5th submit, stranding requests in an open partial batch
    (the hardest case: failover must re-form the group on survivors at
    the same padded width)."""
    import numpy as np
    from repro.serving import (BucketPolicy, ChaosBackend, ClusterService,
                               FaultPlan, LocalBackend, PrewarmSpec,
                               RouterPolicy, SolveService)

    prior, _, reqs, _ = make_load(n, m, p, t, b)
    policy = BucketPolicy(max_batch=8, n_quantum=64, mp_quantum=8)
    menu = [PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t, policy="fixed",
                        prior=prior, batch_widths=(8,))]

    ref = SolveService(policy=policy, rate_accounting=False)
    if prewarm:
        ref.prewarm(menu)
    base_res = ref.solve(reqs)

    victim = f"host{hosts - 1}"
    backends = [LocalBackend(f"host{i}",
                             SolveService(policy=policy,
                                          rate_accounting=False))
                for i in range(hosts - 1)]
    backends.append(ChaosBackend(
        LocalBackend(victim, SolveService(policy=policy,
                                          rate_accounting=False)),
        FaultPlan.kill_at(5)))
    cl = ClusterService(
        backends=backends, policy=policy,
        router_policy=RouterPolicy(min_replicas=hosts, suspect_after=1,
                                   dead_after=2, retry_limit=2,
                                   retry_backoff_s=0.0))
    if prewarm:
        cl.prewarm(menu)

    t0 = time.perf_counter()
    got = sorted(cl.solve(reqs), key=lambda r: r.request_id)
    wall = time.perf_counter() - t0

    max_dx = max(float(np.max(np.abs(cr.x - br.x)))
                 for cr, br in zip(got, base_res))
    st = cl.stats()
    rec = st["recovery"] or {}
    out = {
        "hosts": hosts, "batch": b, "victim": victim,
        "fault_plan": "kill_at(5)",
        "completed": len(got), "admitted": len(reqs),
        "lost": st["lost"], "failovers": st["failovers"],
        "retries": st["retries"],
        "retries_per_request": st["retries"] / max(1, len(reqs)),
        "host_states": st["host_states"],
        "recovery_p50_ms": rec.get("p50_ms"),
        "recovery_p95_ms": rec.get("p95_ms"),
        "recovered": rec.get("count", 0),
        "wall_s": wall,
        "bitwise_max_abs_diff": max_dx,
    }
    cl.close()
    return out


def dataclass_replace(req, **kw):
    import dataclasses
    return dataclasses.replace(req, request_id=-1, **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller problem + widths, fewer reps (CI sanity)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--devices", type=int, default=1,
                    help="force this many host-platform devices (mesh "
                         "placements activate above 1)")
    ap.add_argument("--erasure", type=float, default=0.0,
                    help="packet-drop rate for the measured-wire section "
                         "(runs both recovery policies at this rate)")
    ap.add_argument("--hosts", type=int, default=2,
                    help="emulated host count for the cluster section "
                         "(DESIGN.md §11); 1 skips it")
    ap.add_argument("--chaos", action="store_true",
                    help="run the failure-injection drill: kill one "
                         "emulated host mid-stream and report recovery "
                         "latency + zero-loss counters (DESIGN.md §13)")
    ap.add_argument("--no-prewarm", dest="prewarm", action="store_false",
                    help="skip SolveService.prewarm (measures cold-ish "
                         "services; compiles still leave the timed region "
                         "via the warmup pass)")
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args()

    # forcing more host devices than cores measures thread contention, not
    # data-parallel scaling (ROADMAP open item): clamp and say so, so
    # BENCH_serve.json numbers are always from a real-parallelism config
    cores = os.cpu_count() or 1
    if args.devices > cores:
        print(f"WARNING: --devices {args.devices} exceeds the "
              f"{cores} available cores; clamping to {cores} so the "
              f"benchmark measures scaling, not oversubscription")
        args.devices = cores

    if args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    import jax  # first jax import happens after XLA_FLAGS is set

    if args.devices > 1 and jax.default_backend() != "cpu":
        # the forced devices are host-platform (CPU) devices: on an
        # accelerator backend the mesh numbers would be labelled with
        # devices the run never used
        sys.exit(f"--devices {args.devices} forces CPU host devices, but "
                 f"the backend is {jax.default_backend()!r}; rerun with "
                 f"JAX_PLATFORMS=cpu")
    assert jax.device_count() >= args.devices, \
        (jax.device_count(), args.devices)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from roofline import git_commit  # benchmarks/ is the script dir

    report = {"devices": args.devices, "smoke": bool(args.smoke),
              "backend": jax.default_backend(), "commit": git_commit(),
              "jax_device_count": jax.device_count(),
              "methodology": {
                  "timing": "warmup excluded; min over reps with variants "
                            "interleaved round-robin per round",
                  "prewarm": bool(args.prewarm)},
              "batched": [], "latency": {}, "counters": {},
              "data_parallel": {}, "proc_sharded": {}}

    # the serving regime: many small per-user recoveries, where a single
    # solve is per-dispatch/per-op overhead-bound and batching amortizes it
    n, m, p, t = 128, 64, 4, 8
    if args.smoke:
        widths, reps = (1, 8, 32), 3
    else:
        widths, reps = (1, 8, 32, 128), args.reps

    print(f"problem: N={n} M={m} P={p} T={t}  (ECSQ fixed schedule, CPU="
          f"{jax.default_backend() == 'cpu'}, prewarm={args.prewarm})")
    print(f"{'B':>4s} {'seq req/s':>10s} {'svc req/s':>10s} "
          f"{'speedup':>8s} {'max mse diff':>13s}")
    speedups = {}
    for b in widths:
        dt_seq, dt_svc, dmse = bench_width(n, m, p, t, b, reps,
                                           args.prewarm)
        sp = dt_seq / dt_svc
        speedups[b] = sp
        print(f"{b:4d} {b / dt_seq:10.1f} {b / dt_svc:10.1f} "
              f"{sp:7.2f}x {dmse:13.2e}")
        report["batched"].append({
            "batch": b, "seq_req_s": b / dt_seq, "svc_req_s": b / dt_svc,
            "speedup": sp, "max_mse_diff": dmse})

    # hot-path latency percentiles through a prewarmed stream (ISSUE 6)
    n_req, lat_reps = (48, 2) if args.smoke else (96, 4)
    latency, counters = bench_latency(n, m, p, t, n_req, lat_reps,
                                      args.prewarm)
    print(f"\nlatency (stream, B<=16): p50 {latency['p50_ms']:.2f} ms  "
          f"p95 {latency['p95_ms']:.2f} ms  p99 {latency['p99_ms']:.2f} ms  "
          f"steady-state compiles {latency['steady_state_compiles']}")
    print(f"telemetry health: se-drift median "
          f"{latency['se_drift_median']:.3f} / p95 "
          f"{latency['se_drift_p95']:.3f} "
          f"({latency['se_drift_alerts']} alert(s) over "
          f"{latency['monitored_requests']} monitored), "
          f"{latency['incomplete_spans']} incomplete span trees")
    oc = counters["operand_cache"]
    print(f"operand cache: {oc['hits']} hits / {oc['misses']} misses / "
          f"{oc['evictions']} evictions ({oc['bytes'] / 1024:.0f} KiB); "
          f"compiles {counters['compiles']['total']}; dispatches "
          f"{counters['dispatches']['total']}")
    report["latency"] = latency
    report["counters"] = counters

    # data-parallel placement: a compute-bound bucket where sharding the
    # batch across devices pays (the tiny dispatch-bound load above would
    # only measure collective overhead)
    ndp, mdp, bdp = (512, 128, 8) if args.smoke else (2048, 512, 32)
    dt_dp, placement, n_buckets = bench_data_parallel(
        ndp, mdp, p, t, bdp, max(2, reps // 2), args.devices)
    print(f"\ndata-parallel bucket: N={ndp} M={mdp} B={bdp} "
          f"placement={placement} devices={args.devices}: "
          f"{bdp / dt_dp:.1f} req/s")
    report["data_parallel"] = {
        "n": ndp, "m": mdp, "batch": bdp, "placement": placement,
        "req_s": bdp / dt_dp, "seconds": dt_dp,
        "compiled_buckets": n_buckets}

    # processor-sharded placement: one large request, the mesh axis as the
    # paper's P, exact vs compressed wire
    nps, mps, pps = (2048, 512, 8) if args.smoke else (8192, 2048, 8)
    proc = bench_proc_sharded(nps, mps, pps, t, max(2, reps // 2),
                              args.devices)
    for tr, row in proc.items():
        print(f"proc-sharded single:  N={nps} M={mps} P={pps} wire={tr} "
              f"placement={row['placement']}: {row['seconds']*1e3:.1f} ms")
    report["proc_sharded"] = {"n": nps, "m": mps, "p": pps, **proc}

    # column-layout bucket: tall-N requests auto-routed to C-MP-AMP
    # (DESIGN.md §7) through the same dispatcher
    ncb, mcb, bcb = (1024, 128, 8) if args.smoke else (4096, 512, 16)
    dt_cb, placement_cb, mse_cb = bench_col_bucket(
        ncb, mcb, p, t, bcb, max(2, reps // 2), args.devices)
    print(f"column bucket:        N={ncb} M={mcb} B={bcb} "
          f"placement={placement_cb} layout=col: {bcb / dt_cb:.1f} req/s "
          f"(mse {mse_cb:.2e})")
    report["col_bucket"] = {
        "n": ncb, "m": mcb, "batch": bcb, "placement": placement_cb,
        "req_s": bcb / dt_cb, "seconds": dt_cb, "mse": mse_cb}

    # cluster tier (DESIGN.md §11): weak scaling across emulated hosts,
    # bit-identity vs single-host, zero steady-state recompiles
    if args.hosts > 1:
        bcl = 32 if args.smoke else 64
        cluster = bench_cluster(n, m, p, t, bcl, max(2, reps // 2),
                                args.hosts, args.prewarm)
        print(f"\ncluster ({args.hosts} emulated hosts, B={bcl}, "
              f"max_batch={cluster['max_batch']}):")
        print(f"  1-host {cluster['req_s_1host']:.1f} req/s -> cluster "
              f"{cluster['req_s_cluster']:.1f} req/s "
              f"({cluster['weak_scaling']:.2f}x weak scaling, route "
              f"overhead {cluster['route_overhead_s']*1e3:.2f} ms)")
        print(f"  shares {cluster['share_sizes']}  imbalance "
              f"{cluster['imbalance']:.2f}x  steady-state compiles "
              f"{cluster['steady_state_compiles']}  max|dx| "
              f"{cluster['bitwise_max_abs_diff']:.1e}")
        # measured per-frame TCP round-trips on a loopback BackendServer
        # leg (DESIGN.md §12): what a real remote host adds per frame kind
        rtt = bench_tcp_rtt(n, m, p, t, bcl, args.prewarm)
        line = "  ".join(f"{op}: p50 {s['p50_ms']:.2f}ms "
                         f"p95 {s['p95_ms']:.2f}ms (n={s['count']})"
                         for op, s in rtt.items())
        print(f"  loopback frame rtt  {line}")
        cluster["tcp_rtt"] = rtt
        report["cluster"] = cluster

    # chaos drill (DESIGN.md §13): kill one emulated host mid-stream;
    # the gate is zero lost requests and bit-identical failover replays,
    # the measurement is recovery latency + retry cost
    if args.chaos and args.hosts > 1:
        bch = 16 if args.smoke else 32
        chaos = bench_chaos(n, m, p, t, bch, args.hosts, args.prewarm)
        print(f"\nchaos ({args.hosts} hosts, B={bch}, "
              f"{chaos['fault_plan']} on {chaos['victim']}):")
        print(f"  {chaos['completed']}/{chaos['admitted']} completed, "
              f"{chaos['lost']} lost, {chaos['failovers']} failover(s), "
              f"{chaos['retries']} retries "
              f"({chaos['retries_per_request']:.2f}/req)")
        rec_p50 = chaos["recovery_p50_ms"]
        rec_p95 = chaos["recovery_p95_ms"]
        print(f"  recovery p50 "
              f"{-1.0 if rec_p50 is None else rec_p50:.1f} ms  p95 "
              f"{-1.0 if rec_p95 is None else rec_p95:.1f} ms "
              f"(n={chaos['recovered']})  max|dx| "
              f"{chaos['bitwise_max_abs_diff']:.1e}  states "
              f"{chaos['host_states']}")
        report["chaos"] = chaos

    # measured wire bytes (DESIGN.md §10): rANS payload vs model entropy,
    # plus the lossy-link byte cost per recovery policy at --erasure.
    # Config is smoke-independent: byte counts are deterministic, so the
    # CI smoke run compares directly against the committed full baseline
    bwire = 8
    wire = bench_wire(n, m, p, t, bwire, max(2, reps // 2), args.erasure)
    print(f"\nmeasured wire (B={bwire}, erasure={args.erasure}):")
    print(f"{'variant':>12s} {'payload B':>10s} {'model B':>10s} "
          f"{'ratio':>6s} {'wire B':>10s} {'energy J':>9s} {'mse':>9s}")
    for name, row in wire.items():
        print(f"{name:>12s} {row['payload_bytes']:10.0f} "
              f"{row['model_payload_bytes']:10.0f} "
              f"{row['payload_vs_model']:6.3f} {row['bytes_on_wire']:10.0f} "
              f"{row['energy_j']:9.2e} {row['mse']:9.2e}")
    report["wire"] = {"n": n, "m": m, "p": p, "t": t, "batch": bwire,
                      "erasure": args.erasure, **wire}

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"\nwrote {args.json}")

    failures = []
    # 2x re-baselined under the interleaved methodology (a fully warmed
    # sequential loop runs ~2.5x faster than the old per-variant timing
    # credited it; B=32 measures 2.3-2.9x on 2-8 core CPU)
    if 32 in speedups and speedups[32] < 2.0:
        failures.append(f"B=32 speedup {speedups[32]:.2f}x below the 2x "
                        f"acceptance target")
    if args.prewarm and 1 in speedups and speedups[1] < 1.0:
        failures.append(f"B=1 speedup {speedups[1]:.2f}x below the 1x "
                        f"acceptance target (with prewarm, ISSUE 6)")
    if latency["incomplete_spans"] != 0:
        failures.append(f"{latency['incomplete_spans']} requests returned "
                        f"incomplete span trees (must be 0)")
    if "cluster" in report:
        cl = report["cluster"]
        if cl["hosts"] == 2 and cl["weak_scaling"] < 1.8:
            failures.append(f"cluster weak scaling "
                            f"{cl['weak_scaling']:.2f}x below the 1.8x "
                            f"2-host acceptance target (ISSUE 8)")
        if args.prewarm and cl["steady_state_compiles"] != 0:
            failures.append(f"cluster ran "
                            f"{cl['steady_state_compiles']} steady-state "
                            f"compiles after prewarm (must be 0)")
        if cl["bitwise_max_abs_diff"] != 0.0:
            failures.append(f"cluster results differ from single-host by "
                            f"max|dx|={cl['bitwise_max_abs_diff']:.2e} "
                            f"(must be bit-identical)")
    if "chaos" in report:
        ch = report["chaos"]
        if ch["lost"] != 0 or ch["completed"] != ch["admitted"]:
            failures.append(f"chaos drill lost "
                            f"{ch['admitted'] - ch['completed']} "
                            f"request(s) (must be 0)")
        if ch["bitwise_max_abs_diff"] != 0.0:
            failures.append(f"chaos failover replays differ from "
                            f"single-host by max|dx|="
                            f"{ch['bitwise_max_abs_diff']:.2e} "
                            f"(must be bit-identical)")
        if ch["retries"] == 0:
            failures.append("chaos drill recorded no retries despite "
                            "killing a host")
    for msg in failures:
        print(f"WARNING: {msg}")
    # --smoke is a CI sanity check on shared runners: surface the
    # number, never turn wall-clock jitter into a red build
    return 0 if (args.smoke or not failures) else 1


if __name__ == "__main__":
    sys.exit(main())
