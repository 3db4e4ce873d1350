"""Non-blocking serving-perf regression check for CI.

Compares a freshly measured ``BENCH_serve.json`` against the committed
baseline and prints a GitHub Actions ``::warning::`` annotation when the
stream p50 latency regresses by more than ``--threshold`` (default 25%)
or a batched speedup drops below the baseline by the same margin.
Measured wire bytes (the ``wire`` section) get a tighter 10% band:
byte counts are deterministic at fixed config — drift there is an
accounting change, not runner jitter.

Always exits 0: CI wall-clock on shared runners is jittery, so this
surfaces drift on the PR without turning noise into a red build. The
archived artifacts carry the full trajectory for offline comparison.

  python benchmarks/check_serve_regression.py \
      --baseline /tmp/bench_serve_baseline.json --fresh BENCH_serve.json
"""
from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_serve.json (snapshot before the "
                         "bench overwrites it)")
    ap.add_argument("--fresh", default="BENCH_serve.json",
                    help="just-measured BENCH_serve.json")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="relative regression that triggers a warning")
    args = ap.parse_args()

    try:
        with open(args.baseline) as f:
            base = json.load(f)
        with open(args.fresh) as f:
            fresh = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"::notice::serve-bench comparison skipped: {e}")
        return 0

    warnings = []

    b_lat, f_lat = base.get("latency") or {}, fresh.get("latency") or {}
    b50, f50 = b_lat.get("p50_ms"), f_lat.get("p50_ms")
    if b50 and f50:
        rel = f50 / b50 - 1.0
        line = (f"stream p50 {f50:.2f} ms vs baseline {b50:.2f} ms "
                f"({rel:+.0%}, commit {base.get('commit', '?')})")
        if rel > args.threshold:
            warnings.append(f"p50 latency regressed: {line}")
        else:
            print(f"serve-bench: {line}")

    b_sp = {row["batch"]: row["speedup"] for row in base.get("batched", [])}
    for row in fresh.get("batched", []):
        b = row["batch"]
        if b not in b_sp or b_sp[b] <= 0:
            continue
        rel = row["speedup"] / b_sp[b] - 1.0
        line = (f"B={b} speedup {row['speedup']:.2f}x vs baseline "
                f"{b_sp[b]:.2f}x ({rel:+.0%})")
        if rel < -args.threshold:
            warnings.append(f"batched speedup regressed: {line}")
        else:
            print(f"serve-bench: {line}")

    b_wire, f_wire = base.get("wire") or {}, fresh.get("wire") or {}
    same_cfg = all(b_wire.get(k) == f_wire.get(k)
                   for k in ("n", "m", "p", "t", "batch", "erasure"))
    for variant in ("clean", "retransmit", "rate_up"):
        bb = (b_wire.get(variant) or {}).get("bytes_on_wire")
        fb = (f_wire.get(variant) or {}).get("bytes_on_wire")
        if not (same_cfg and bb and fb):
            continue
        rel = fb / bb - 1.0
        line = (f"{variant} bytes-on-wire {fb:.0f} vs baseline {bb:.0f} "
                f"({rel:+.0%})")
        if abs(rel) > 0.10:
            warnings.append(f"wire bytes drifted beyond 10% at fixed "
                            f"config (accounting change?): {line}")
        else:
            print(f"serve-bench: {line}")

    ssc = (f_lat or {}).get("steady_state_compiles")
    if ssc:
        warnings.append(f"steady-state stream triggered {ssc} recompiles "
                        f"(prewarm should cover the whole menu)")

    # telemetry plane (DESIGN.md §12): all advisory. Drift p95 above the
    # alert line (DRIFT_ALERT = 1.0 in repro.telemetry.drift) means the
    # SE predictions no longer describe realized solves — a modeling or
    # rating bug, not runner jitter. Incomplete span trees mean a
    # dispatch path stopped stamping its stages. (The telemetry plane's
    # cost is measured on the chip, with tracing on and off; PERF.md.)
    # p95 threshold is 2x the per-request alert line: at the bench's
    # small N the drift tail is heavy with finite-size realization
    # noise (p95 ~1.2 on a healthy run), while a systematic modeling
    # bug shifts the whole distribution decades up the log scale.
    d95 = (f_lat or {}).get("se_drift_p95")
    if d95 is not None and d95 > 2.0:
        warnings.append(f"SE-drift p95 {d95:.2f} above 2x the "
                        f"drift-alert line over "
                        f"{f_lat.get('monitored_requests')} monitored "
                        f"requests (mis-modeled operating point?)")
    bad_spans = (f_lat or {}).get("incomplete_spans")
    if bad_spans:
        warnings.append(f"{bad_spans} requests returned incomplete or "
                        f"non-monotonic span trees (must be 0)")

    # cluster tier (DESIGN.md §11): aggregate throughput drift at same
    # host count, plus the hard invariants (zero steady-state recompiles,
    # router cost imbalance within 2x on a homogeneous stream)
    b_cl, f_cl = base.get("cluster") or {}, fresh.get("cluster") or {}
    b_agg, f_agg = b_cl.get("req_s_cluster"), f_cl.get("req_s_cluster")
    if b_agg and f_agg and b_cl.get("hosts") == f_cl.get("hosts"):
        rel = f_agg / b_agg - 1.0
        line = (f"{f_cl['hosts']}-host aggregate {f_agg:.1f} req/s vs "
                f"baseline {b_agg:.1f} req/s ({rel:+.0%}, weak scaling "
                f"{f_cl.get('weak_scaling', 0):.2f}x)")
        if rel < -args.threshold:
            warnings.append(f"cluster throughput regressed: {line}")
        else:
            print(f"serve-bench: {line}")
    if f_cl.get("steady_state_compiles"):
        warnings.append(f"cluster ran {f_cl['steady_state_compiles']} "
                        f"steady-state recompiles after prewarm")
    imb = f_cl.get("imbalance")
    if imb is not None and imb > 2.0:
        warnings.append(f"cluster router cost imbalance {imb:.2f}x "
                        f"exceeds 2x on a homogeneous stream")

    # fault-tolerance drill (DESIGN.md §13): lost requests and non-
    # identical replays are correctness (always warn); recovery latency
    # and retry cost compare against baseline when both runs drilled
    b_ch, f_ch = base.get("chaos") or {}, fresh.get("chaos") or {}
    if f_ch:
        lost = (f_ch.get("admitted", 0) - f_ch.get("completed", 0)
                + f_ch.get("lost", 0))
        if lost:
            warnings.append(f"chaos drill lost {lost} request(s) "
                            f"(zero-loss failover is the gate)")
        if f_ch.get("bitwise_max_abs_diff"):
            warnings.append(f"chaos failover replays differ from "
                            f"single-host by max|dx|="
                            f"{f_ch['bitwise_max_abs_diff']:.2e} "
                            f"(must be bit-identical)")
        b95, f95 = b_ch.get("recovery_p95_ms"), f_ch.get("recovery_p95_ms")
        if b95 and f95 and b_ch.get("hosts") == f_ch.get("hosts"):
            rel = f95 / b95 - 1.0
            line = (f"recovery p95 {f95:.1f} ms vs baseline {b95:.1f} ms "
                    f"({rel:+.0%}, {f_ch.get('retries_per_request', 0):.2f} "
                    f"retries/req)")
            # recovery includes a replayed solve: give it double headroom
            if rel > 2 * args.threshold:
                warnings.append(f"failover recovery regressed: {line}")
            else:
                print(f"serve-bench: {line}")
        elif f95:
            print(f"serve-bench: recovery p95 {f95:.1f} ms "
                  f"({f_ch.get('retries_per_request', 0):.2f} retries/req, "
                  f"no baseline drill to compare)")

    for w in warnings:
        print(f"::warning::{w}")
    if not warnings:
        print("serve-bench: no regressions beyond "
              f"{args.threshold:.0%} threshold")
    return 0   # advisory only — never fail the build on wall-clock noise


if __name__ == "__main__":
    sys.exit(main())
