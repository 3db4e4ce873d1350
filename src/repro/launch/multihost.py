"""Two-process ``jax.distributed`` cluster drill on the CPU (DESIGN.md §11).

This is a CPU drill, never a chip run: every child is pinned to
``JAX_PLATFORMS=cpu``, so on a host with an accelerator the processes
never contend for it (a chip belongs to one process at a time).

Run with no cluster env set, this module is the **parent**: it picks
free ports, spawns one child per process (same interpreter, same argv)
with ``AMP_COORDINATOR`` / ``AMP_NUM_PROCESSES`` / ``AMP_PROCESS_ID``,
``JAX_PLATFORMS=cpu`` and ``--xla_force_host_platform_device_count``
fake devices, waits, and propagates the worst child exit code — the CI
``multihost`` job's entry point. The parent itself never imports jax.

With ``AMP_PROCESS_ID`` set, it is a **child**: every process joins the
``jax.distributed`` cluster via ``init_cluster`` (real coordinator
handshake, global device discovery), then

  * process 1..K-1 each serve a ``SolveService`` behind a
    ``BackendServer`` — codec frames on TCP, no pickle — until the
    frontend sends the shutdown op, and
  * process 0 (the frontend, ``ClusterInfo.is_frontend``) builds a
    ``ClusterService`` over its own ``LocalBackend`` plus one
    ``TcpBackend`` per remote, prewarms the menu, streams a smoke load,
    and pins the invariants: results bit-identical to a single-host
    ``SolveService`` on the same stream, zero steady-state compiles
    after prewarm, every host actually served.

On CPU the cluster coordinates but cannot run cross-process XLA
computations (``supports_cross_host_collectives`` is False), so this is
exactly the regime the request-level router exists for: the test proves
the TCP + codec path end-to-end under a real multi-process jax runtime.

  PYTHONPATH=src python -m repro.launch.multihost --smoke

``--chaos`` (DESIGN.md §13) is the two-process fault drill: the
frontend submits the full stream, then kills host1's real backend
process mid-flight (the ``X`` frame op — the server stops serving with
computed results still buffered), and the gate is that the flush
recovers everything over the real TCP path: zero lost requests,
failover counted, host1 evicted as dead, recovery latency measured,
and the surviving results bit-identical to a single-host run.
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time

_DEVICES_PER_HOST = 4


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parent(args) -> int:
    coord = _free_port()
    backend_ports = [_free_port() for _ in range(args.processes - 1)]
    env = dict(os.environ)
    env.update({
        "AMP_COORDINATOR": f"127.0.0.1:{coord}",
        "AMP_NUM_PROCESSES": str(args.processes),
        "AMP_BACKEND_PORTS": ",".join(map(str, backend_ports)),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                      + f" --xla_force_host_platform_device_count="
                        f"{_DEVICES_PER_HOST}").strip(),
    })
    procs = []
    for pid in range(args.processes):
        cenv = dict(env, AMP_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen([sys.executable, "-m",
                                       "repro.launch.multihost", *sys.argv[1:]],
                                      env=cenv))
    deadline = time.time() + args.timeout
    codes = []
    try:
        for p in procs:
            left = max(1.0, deadline - time.time())
            try:
                codes.append(p.wait(timeout=left))
            except subprocess.TimeoutExpired:
                p.kill()
                codes.append(124)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    worst = max(abs(c) for c in codes)
    print(f"multihost parent (CPU drill): child exit codes {codes}")
    return worst


def _make_load(n_req: int):
    import jax
    import numpy as np

    from ..core.amp import sample_problem
    from ..core.denoisers import BernoulliGauss
    from ..core.state_evolution import CSProblem
    from ..serving import SolveRequest

    n, m, p, t = 128, 64, 4, 8
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=n, m=m, prior=prior, snr_db=20.0)
    deltas = np.full(t, 0.05, np.float32)
    deltas[0] = np.inf
    reqs = []
    for i in range(n_req):
        _, a, y = sample_problem(jax.random.PRNGKey(i), n, m, prior,
                                 prob.sigma_e2)
        reqs.append(SolveRequest(y=y, a=a, prior=prior, n_proc=p,
                                 n_iter=t, policy="fixed", deltas=deltas))
    return prior, reqs


def child(args) -> int:
    from .mesh import init_cluster, supports_cross_host_collectives

    info = init_cluster()
    ports = [int(p) for p in
             os.environ["AMP_BACKEND_PORTS"].split(",") if p]
    print(f"multihost[{info.process_index}]: {info.process_count} procs, "
          f"{info.local_devices} local / {info.global_devices} global "
          f"devices, cross-host collectives="
          f"{supports_cross_host_collectives()}")
    assert info.process_count == args.processes, info
    assert info.global_devices == args.processes * _DEVICES_PER_HOST, info

    from ..serving import BucketPolicy, PrewarmSpec, SolveService
    from ..serving.frontend import BackendServer, LocalBackend

    policy = BucketPolicy(max_batch=8, n_quantum=64, mp_quantum=8)

    if not info.is_frontend:
        # backend process: serve until the frontend's shutdown op
        port = ports[info.process_index - 1]
        server = BackendServer(
            LocalBackend(f"host{info.process_index}",
                         SolveService(policy=policy,
                                      rate_accounting=False)),
            port=port)
        print(f"multihost[{info.process_index}]: backend on :{port}")
        server.serve_forever()
        return 0

    # frontend process: LocalBackend for host0 + TcpBackend per remote
    import numpy as np

    from ..serving import ClusterService, RouterPolicy
    from ..serving.frontend import TcpBackend

    from ..serving.wire import BackendUnavailable

    backends = [LocalBackend("host0",
                             SolveService(policy=policy,
                                          rate_accounting=False))]
    for i, port in enumerate(ports, start=1):
        for attempt in range(60):   # backend process may still be booting
            try:
                backends.append(TcpBackend(
                    ("127.0.0.1", port), f"host{i}",
                    connect_timeout_s=5.0, recv_timeout_s=60.0))
                break
            except (ConnectionError, OSError, BackendUnavailable):
                time.sleep(0.5)
        else:
            print(f"multihost[0]: backend host{i} on :{port} never came up")
            return 2

    rp = RouterPolicy(min_replicas=len(backends))
    if args.chaos:
        # fast detection: one failed call suspects, two evict
        rp = RouterPolicy(min_replicas=len(backends), suspect_after=1,
                          dead_after=2, retry_limit=2,
                          retry_backoff_s=0.05)
    cluster = ClusterService(backends=backends, policy=policy,
                             router_policy=rp)
    prior, reqs = _make_load(args.requests)
    menu = [PrewarmSpec(n=128, m=64, n_proc=4, n_iter=8, policy="fixed",
                        prior=prior, batch_widths=(8,))]
    cluster.prewarm(menu)
    # per-host warm counts: a host that dies mid-drill drops out of the
    # cluster-wide count, so steady-state compiles compare per survivor
    warm = {hid: b.compile_count()
            for hid, b in cluster.backends.items()}

    t0 = time.time()
    if args.chaos:
        # submit everything, then kill host1's backend PROCESS with
        # its results still buffered server-side: the flush must fail
        # over every stranded request to the survivors
        ids = [cluster.submit(r) for r in reqs]
        stranded = sum(1 for hk in cluster._inflight if hk[0] == "host1")
        cluster.backends["host1"].kill_server()
        print(f"multihost[0]: chaos — killed host1 with {stranded} "
              f"requests in flight there")
        got = list(cluster.flush())
        own = set(ids)
        results = sorted((r for r in got if r.request_id in own),
                         key=lambda r: r.request_id)
    else:
        results = sorted(cluster.solve(reqs), key=lambda r: r.request_id)
    dt = time.time() - t0

    # single-host reference on the same stream: cluster results must be
    # bit-identical (same padded widths -> same compiled programs)
    ref_svc = SolveService(policy=policy, rate_accounting=False)
    ref_svc.prewarm(menu)
    ref = ref_svc.solve(reqs)
    max_dx = max(float(np.max(np.abs(c.x - r.x)))
                 for c, r in zip(results, ref))

    st = cluster.stats()
    served = st["router"]["served"]
    steady = sum(b.compile_count() - warm[hid]
                 for hid, b in cluster.backends.items()
                 if cluster.router.host_state(hid) != "dead")
    print(f"multihost[0]: {len(results)} results in {dt:.2f}s over "
          f"{len(backends)} hosts; served {served}; "
          f"steady-state compiles {steady}; max|dx| {max_dx:.1e}; "
          f"imbalance {st['router']['imbalance']:.2f}x")
    if args.chaos:
        rec = st["recovery"] or {}
        print(f"multihost[0]: chaos — states {st['host_states']}; "
              f"failovers {st['failovers']}, retries {st['retries']}, "
              f"lost {st['lost']}; recovery p95 "
              f"{rec.get('p95_ms', float('nan')):.1f}ms "
              f"(n={rec.get('count', 0)})")
    # measured TCP routing overhead per frame kind (DESIGN.md §12):
    # submits ("S") are the hot path, flush/prewarm amortize
    for host_id, per_op in cluster.rtt_stats().items():
        line = "  ".join(f"{op}: p50 {s['p50_ms']:.2f}ms "
                         f"p95 {s['p95_ms']:.2f}ms (n={s['count']})"
                         for op, s in per_op.items())
        print(f"multihost[0]: {host_id} frame rtt  {line}")
    cluster.close(shutdown_remote=True)

    failures = []
    if len(results) != len(reqs):
        failures.append(f"{len(reqs) - len(results)} results missing")
    if max_dx != 0.0:
        failures.append(f"cluster differs from single-host: "
                        f"max|dx|={max_dx:.2e}")
    if steady != 0:
        failures.append(f"{steady} steady-state compiles after prewarm")
    if any(v == 0 for v in served.values()):
        failures.append(f"idle host in {served}")
    if args.chaos:
        if st["lost"] != 0:
            failures.append(f"{st['lost']} requests lost in failover")
        if st["failovers"] != 1:
            failures.append(f"expected 1 failover, saw {st['failovers']}")
        if st["retries"] == 0:
            failures.append("no retries counted despite a host kill")
        if st["host_states"].get("host1") != "dead":
            failures.append(f"host1 not evicted: {st['host_states']}")
        if not st["recovery"]:
            failures.append("no recovery latency recorded")
    for msg in failures:
        print(f"multihost[0]: FAIL: {msg}")
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--smoke", action="store_true",
                    help="16 requests (CI sanity)")
    ap.add_argument("--chaos", action="store_true",
                    help="kill one backend process mid-stream and gate "
                         "on zero-loss failover (DESIGN.md §13)")
    ap.add_argument("--timeout", type=float, default=420.0,
                    help="parent-side wall clock before children are "
                         "killed (exit 124)")
    args = ap.parse_args()
    if args.smoke:
        args.requests = 16
    if os.environ.get("AMP_PROCESS_ID") is None:
        return parent(args)
    return child(args)


if __name__ == "__main__":
    sys.exit(main())
