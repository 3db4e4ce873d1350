"""Observe a serving run end to end: per-request trace spans, the live
SE-drift monitor, and the Prometheus metrics snapshot (DESIGN.md §12).

Runs a mixed load through a telemetry-enabled ``SolveService``, prints
each request's span tree and SE drift, renders the service's metrics
registry as Prometheus text, and writes a Chrome trace
(``chrome://tracing`` / Perfetto) of the whole run.

  PYTHONPATH=src python examples/observe.py [--trace-out amp_trace.jsonl]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax
import numpy as np

from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss
from repro.core.state_evolution import CSProblem
from repro.serving import BucketPolicy, SolveRequest, SolveService
from repro.telemetry import (DRIFT_ALERT, hist_quantile, span_names,
                             write_trace_jsonl)

# Three operating points; the middle one lies about its SNR by 20 dB,
# so the drift monitor should flag it while the honest requests sit
# well under the alert line.
SPECS = [
    (0.10, 20.0, 20.0, 1024, 320, 8, 8),    # honest
    (0.10, 20.0,  0.0, 1024, 320, 8, 8),    # declares 0 dB, signal is 20
    (0.02, 25.0, 25.0,  512, 160, 4, 8),    # honest
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write Chrome trace-event JSONL of the run")
    args = ap.parse_args()

    svc = SolveService(policy=BucketPolicy(max_batch=32), telemetry=True)
    reqs = []
    for i, (eps, snr_true, snr_decl, n, m, p, t) in enumerate(SPECS):
        prior = BernoulliGauss(eps=eps)
        prob = CSProblem(n=n, m=m, prior=prior, snr_db=snr_true)
        _, a, y = sample_problem(jax.random.PRNGKey(i), n, m, prior,
                                 prob.sigma_e2)
        reqs.append(SolveRequest(y=y, a=a, prior=prior, snr_db=snr_decl,
                                 n_proc=p, n_iter=t, policy="lossless"))

    results = svc.solve(reqs)

    print("request trace spans + SE drift:")
    for spec, res in zip(SPECS, results):
        _, snr_true, snr_decl, n, m, p, t = spec
        tree = " -> ".join(span_names(res.spans))
        drift = ("   n/a" if res.se_drift is None
                 else f"{res.se_drift:6.3f}")
        flag = (" <-- ALERT (declared SNR is wrong)"
                if res.se_drift is not None and res.se_drift > DRIFT_ALERT
                else "")
        print(f"  N={n:5d} snr_decl={snr_decl:4.1f} (true {snr_true:4.1f})"
              f"  drift {drift}{flag}")
        print(f"    {tree}")
        for name, _, t0, t1, *counts in res.spans:
            print(f"    {name:>10s}  {1e3 * (t1 - t0):8.3f} ms"
                  + (f"  {counts[0]}" if counts else ""))

    snap = svc.metrics()
    for metric in snap["metrics"]:
        if metric["name"] != "amp_request_latency_seconds":
            continue
        for sample in metric["samples"]:
            p95 = hist_quantile(sample, 0.95)
            if p95 is not None:
                print(f"\nlatency p95 (histogram estimate): "
                      f"<= {1e3 * p95:.1f} ms")

    print("\nPrometheus snapshot (drift + request families):")
    for line in svc.metrics_text().splitlines():
        if "se_drift" in line or "requests_total" in line:
            print(f"  {line}")

    if args.trace_out:
        with open(args.trace_out, "w") as fp:
            n_ev = write_trace_jsonl(fp, results)
        print(f"\ntrace: {n_ev} span events -> {args.trace_out}")


if __name__ == "__main__":
    main()
