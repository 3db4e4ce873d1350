#!/usr/bin/env python3
"""One traced run of one cell, with the device's idle time split by the
service's phases.

  python3 bench/phase_trace.py --workload <name> --seed <n> --seconds <s>

Runs ``bench/run.py --trace 1`` in this process, keeps the profiler
trace's ``amp.*`` host spans before the trace is deleted, and prints two
JSON lines on standard output: the run's result line, then the split
(``idle_by_phase``: idle seconds of the first device by the innermost
``amp.*`` span over each gap, else by the ``bench.*`` span;
``idle_ms_per_batch``: the same per batch finalized in the window;
``amp_share``: the share of the idle time under an ``amp.*`` span;
``tail_idle_share``: idle time under ``amp.complete`` over the traced
window, in %).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import phases  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402


class KeepingTracer(harness.Tracer):
    """``harness.Tracer`` that keeps the phase events of its trace."""

    events: list | None = None

    def read(self):
        self.stop()
        if self.host_span is not None:
            KeepingTracer.events = phases.extract(
                trace_reduce.trace_file(self.dir))
        return super().read()


def split(events: list) -> dict:
    idle = phases.idle_by_phase(events)
    total = sum(idle.values())
    n = phases.batches(events)
    amp = sum(v for k, v in idle.items()
              if k.startswith(phases.AMP_PREFIX))
    return {
        "idle_by_phase": idle,
        "batches": n,
        "idle_ms_per_batch": ({k: 1e3 * v / n for k, v in idle.items()}
                              if n else None),
        "amp_share": amp / total if total > 0 else None,
        "tail_idle_share": (100.0 * phases.tail_idle_s(events)
                            / phases.window_s(events)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.Tracer = KeepingTracer
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc != 0 or KeepingTracer.events is None:
        return rc or 1
    print(json.dumps(split(KeepingTracer.events)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
