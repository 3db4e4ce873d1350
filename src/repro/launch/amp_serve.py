"""AMP solve-service launcher: synthetic heterogeneous load -> SolveService.

Generates a stream of CS recovery requests with mixed shapes, priors, SNRs
and rate policies (the "many users, many scenarios" traffic of ROADMAP),
runs them through the shape-bucketed batching service, and reports
per-request quality/rate plus end-to-end throughput.

  PYTHONPATH=src python -m repro.launch.amp_serve --smoke
  PYTHONPATH=src python -m repro.launch.amp_serve --requests 256 \\
      --max-batch 64 --policies fixed,bt,lossless

``--mesh`` serves over all visible devices through the placement
dispatcher (DESIGN.md §6): the bucket column then shows where each
request ran (data-parallel vs processor-sharded).

The shape menu mixes wide (row-partitioned) and tall (column-partitioned
C-MP-AMP, DESIGN.md §7) requests; the layout router batches each family
into its own buckets and the summary reports rate totals *per layout* —
row rates are bits per signal element per processor, column rates are
bits per *measurement* per processor (length-M residual exchanges), so
one aggregate line would add apples to oranges.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..compile_cache import enable_compile_cache
from ..core.amp import sample_problem
from ..core.denoisers import BernoulliGauss
from ..core.state_evolution import CSProblem
from ..serving import (BucketPolicy, PrewarmSpec, SolveRequest,
                       SolveService)

# (N, M, P) menu — wide shapes (N/M ~ 3.2) route row, tall ones (N/M >=
# 4) route column; P divides every M and every N
SHAPES = [(512, 160, 4), (1024, 320, 8), (2048, 512, 8), (4096, 512, 8)]
EPS_MENU = (0.05, 0.1)
SNR_MENU = (15.0, 20.0, 25.0)


def make_request(rng: np.random.Generator, i: int, policies) -> tuple:
    n, m, p = SHAPES[rng.integers(len(SHAPES))]
    # tall shapes undersample harder (kappa = M/N down to 1/8): keep their
    # signals sparse enough to sit inside the AMP recovery region
    eps_menu = (0.02, 0.05) if n >= 4 * m else EPS_MENU
    prior = BernoulliGauss(eps=float(rng.choice(eps_menu)))
    snr = float(rng.choice(SNR_MENU))
    t = int(rng.choice((6, 8, 10)))
    policy = str(rng.choice(policies))
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=snr)
    s0, a, y = sample_problem(jax.random.PRNGKey(i), n, m, prior,
                              prob.sigma_e2)
    kw = {}
    if policy == "fixed":
        deltas = np.full(t, 0.05, np.float32)
        deltas[0] = np.inf
        kw["deltas"] = deltas
    req = SolveRequest(y=y, a=a, prior=prior, snr_db=snr, n_proc=p,
                       n_iter=t, policy=policy, **kw)
    return req, s0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--policies", default="lossless,fixed,bt",
                    help="comma list from lossless,fixed,dp,bt")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="16 requests, small batches, no rate accounting")
    ap.add_argument("--mesh", action="store_true",
                    help="serve over all visible devices (placement "
                         "dispatcher; forced-host devices need XLA_FLAGS "
                         "set before launch)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="serve through the cluster tier (DESIGN.md §11) "
                         "with this many emulated hosts: a ClusterService "
                         "routes buckets across per-host SolveServices "
                         "and autoscales per-bucket replicas from demand "
                         "EWMAs")
    ap.add_argument("--prewarm", action="store_true",
                    help="AOT-compile the SHAPES bucket menu before "
                         "streaming (DESIGN.md §9): compiles move out of "
                         "the serving path, the summary then reports "
                         "steady-state compiles")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="dump per-request trace spans as Chrome "
                         "trace-event JSONL (DESIGN.md §12; wrap the "
                         "lines in [...] for chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the final metrics snapshot as Prometheus "
                         "text exposition format")
    args = ap.parse_args()
    enable_compile_cache()

    n_req = 16 if args.smoke else args.requests
    policies = args.policies.split(",")
    rng = np.random.default_rng(args.seed)
    pairs = [make_request(rng, i, policies) for i in range(n_req)]

    mesh = None
    max_batch = args.max_batch
    if args.mesh:
        from ..serving.buckets import round_up
        from .mesh import make_serve_mesh
        mesh = make_serve_mesh()
        # data-parallel dispatch needs a device-multiple batch cap
        max_batch = round_up(max_batch, mesh.shape["data"])
    if args.hosts > 1:
        from ..serving import ClusterService, RouterPolicy
        assert not args.mesh, \
            "--hosts emulates single-device hosts; combine with --mesh " \
            "only on a real multi-host launch (repro.launch.multihost)"
        svc = ClusterService(
            n_hosts=args.hosts, policy=BucketPolicy(max_batch=max_batch),
            router_policy=RouterPolicy(scrape_every_s=0.25,
                                       ewma_halflife_s=2.0),
            rate_accounting=not args.smoke)
    else:
        svc = SolveService(policy=BucketPolicy(max_batch=max_batch),
                           rate_accounting=not args.smoke, mesh=mesh)
    prewarmed = 0
    if args.prewarm:
        # one spec per (shape, t-bucket, program family): T in {6,8} and
        # {10} pad to distinct t_max buckets; BT solves trace a different
        # program (in-graph table controller) than the other policies
        fams = [p for p in ("lossless", "bt") if p == "lossless"
                or "bt" in policies]
        menu = [PrewarmSpec(n=n, m=m, n_proc=p, n_iter=t, policy=fam)
                for (n, m, p) in SHAPES for t in (8, 12) for fam in fams]
        rep = svc.prewarm(menu)
        if args.hosts > 1:
            rep = next(iter(rep.values()))     # per-host reports are equal
        prewarmed = rep["programs"]
        print(f"prewarm: {rep['programs']} programs over "
              f"{len(rep['buckets'])} buckets in {rep['seconds']:.1f}s")
    if args.hosts > 1:
        # production elasticity shape (DESIGN.md §12): the autoscaler
        # scrape loop runs on its own daemon thread at scrape_every_s
        # instead of piggybacking ticks on the submit path
        svc.start_scraper()
    t0 = time.time()
    results = list(svc.stream(r for r, _ in pairs))
    dt = time.time() - t0
    if args.hosts > 1:
        svc.stop_scraper()

    # request ids are assigned in submission order, i.e. pairs[rid]
    print(f"{'id':>4s} {'policy':>9s} {'T':>3s} {'bucket':>22s} {'B':>4s} "
          f"{'mse':>10s} {'bits':>7s}")
    for r in sorted(results, key=lambda res: res.request_id):
        req, s0 = pairs[r.request_id]
        bk = f"({r.bucket.n_pad},{r.bucket.m_pad},{r.bucket.n_proc}," \
             f"{r.bucket.t_max}){r.bucket.placement[0]}" \
             f"{r.bucket.layout[0]}"
        # untracked (no finite per-iteration rate) shows "-"; a genuine
        # 0.00-bit total from finite rates still prints as a number
        bits = f"{r.total_bits:7.2f}" if r.tracked else "      -"
        print(f"{r.request_id:4d} {req.policy:>9s} {req.n_iter:3d} "
              f"{bk:>22s} {r.batch_size:4d} {r.mse(s0):10.3e} {bits}")

    # per-layout rate totals: row rates count bits/signal-element/proc,
    # column rates bits/measurement/proc — never one aggregate number
    unit = {"row": "bits/elem", "col": "bits/meas"}
    for layout in ("row", "col"):
        in_layout = [r for r in results if r.bucket.layout == layout]
        if not in_layout:
            continue
        tracked = [r for r in in_layout if r.tracked]
        tot = sum(r.total_bits for r in tracked)
        print(f"{layout}: {len(in_layout)} requests, "
              f"{len(tracked)} rate-tracked, "
              f"{tot:.1f} {unit[layout]} total"
              + (f" ({tot / len(tracked):.2f} avg)" if tracked else ""))
    st = svc.stats()
    if args.hosts > 1:
        # cluster tier: per-host hot-path stats roll up, plus the
        # scheduler's routing/autoscaling view (DESIGN.md §11)
        hosts = st["hosts"]
        compiles = sum(h["compiles"]["total"] for h in hosts.values())
        hits = sum(h["operand_cache"]["hits"] for h in hosts.values())
        misses = sum(h["operand_cache"]["misses"] for h in hosts.values())
        buckets = sum(len(h["compiles"]["by_bucket"])
                      for h in hosts.values())
        rt = st["router"]
        print(f"\n{n_req} requests in {dt:.2f}s  "
              f"({n_req / dt:.1f} req/s, {len(hosts)} hosts, "
              f"{buckets} compiled buckets)")
        print(f"hot path: {compiles} compiles"
              + (f" ({compiles - prewarmed} after prewarm)"
                 if args.prewarm else "")
              + f", operand cache {hits} hits / {misses} misses")
        print(f"router: served {rt['served']} "
              f"(cost imbalance {rt['imbalance']:.2f}x), "
              f"{st['shed']} shed; autoscaler events: "
              f"{st['autoscaler']['events'] or 'none'}")
        # fault-tolerance plane (DESIGN.md §13): quiet on a healthy run,
        # loud when the drill — or a real fault — fired
        faults = {k: st[k] for k in
                  ("failovers", "retries", "hedges", "lost", "degraded")
                  if st.get(k)}
        unhealthy = {h: s for h, s in st["host_states"].items()
                     if s != "healthy"}
        if faults or unhealthy:
            rec = st.get("recovery") or {}
            print(f"faults: " + ", ".join(f"{k} {v}"
                                          for k, v in faults.items())
                  + (f"; states {unhealthy}" if unhealthy else "")
                  + (f"; recovery p95 {rec['p95_ms']:.1f}ms "
                     f"(n={rec['count']})" if rec else ""))
    else:
        oc = st["operand_cache"]
        print(f"\n{n_req} requests in {dt:.2f}s  "
              f"({n_req / dt:.1f} req/s, "
              f"{len(svc._engines)} compiled buckets)")
        print(f"hot path: {st['compiles']['total']} compiles"
              + (f" ({st['compiles']['total'] - prewarmed} after prewarm)"
                 if args.prewarm else "")
              + f", operand cache {oc['hits']} hits / {oc['misses']} misses"
              f" ({oc['bytes'] / (1 << 20):.1f} MiB), "
              f"{st['singleton_dispatches']} singleton dispatches")

    # telemetry plane (DESIGN.md §12): SE-drift summary + optional dumps
    drifts = [r.se_drift for r in results
              if r.se_drift is not None and np.isfinite(r.se_drift)]
    if drifts:
        from ..telemetry import DRIFT_ALERT
        alerts = sum(1 for d in drifts if d > DRIFT_ALERT)
        print(f"se drift: median {float(np.median(drifts)):.3f}, "
              f"max {max(drifts):.3f}, {alerts} alert(s) over "
              f"{len(drifts)} monitored requests")
    if args.trace_out:
        from ..telemetry import write_trace_jsonl
        with open(args.trace_out, "w") as fp:
            n_ev = write_trace_jsonl(fp, results)
        print(f"trace: {n_ev} span events -> {args.trace_out}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as fp:
            fp.write(svc.metrics_text())
        print(f"metrics: Prometheus snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
