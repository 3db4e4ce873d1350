#!/usr/bin/env python3
"""Readings from which the comparison's limits are set, on the chip.

  python3 bench/control.py --workload <name> --seeds <a,b,...> --seconds <s>
  python3 bench/control.py --workload <name> --seeds <a,b,...> --fault <name>

For each seed, in one process: one run of the cell with a short window at
the cell's own load and the program's readings of every number compared
(``checks``). Without ``--fault``, also the control's: the reference
computed one precision step below (three bfloat16 passes,
``Precision.HIGH``), put in the program's place and compared with the
same limits. With ``--fault``, the named fault of ``bench/faults.py`` is
planted in the program first, and the readings are the fault's. One JSON
line per seed, then the largest program reading and the smallest control
reading of each number (and, under a fault, the smallest reading). The
benchmark's own runs do not run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, each its own run")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", default=None,
                    help="a fault of bench/faults.py to plant first")
    args = ap.parse_args(argv)

    import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    import harness
    if args.fault is not None:
        import faults
        faults.FAULTS[args.fault](setattr)

    prog: dict = {}
    low: dict = {}
    ctrl: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_start=time.perf_counter(),
                                   control=args.fault is None)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        line = {"seed": seed, "fault": args.fault, "correct": res["correct"],
                "checks": res["checks"], "control": res.get("control")}
        print(json.dumps(line), flush=True)
        for k, v in res["checks"].items():
            prog[k] = max(prog.get(k, v["value"]), v["value"])
            low[k] = min(low.get(k, v["value"]), v["value"])
        for k, v in (res.get("control") or {}).items():
            if k != "correct" and v is not None:
                ctrl[k] = min(ctrl.get(k, v), v)
    summary = {"workload": args.workload, "fault": args.fault,
               "program_max": prog}
    if args.fault is None:
        summary["control_min"] = ctrl
    else:
        summary["program_min"] = low
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
