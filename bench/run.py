#!/usr/bin/env python3
"""Chip benchmark of the MP-AMP solve service: one run of one cell.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout: the cell, its configuration, its
traffic mix and its per-layer metrics are found by name from
``BENCHMARK.json``. With ``--trace 0`` it prints the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window's last 5 s. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
``breakdown`` when traced, then ``checks``: each number compared with the
reference beside its limit). The checks are also the last lines of
standard error.

Exits 2 without a result when JAX finds no TPU or fewer chips than the
cell asks for. JAX's persistent compilation cache is kept at
``bench/.jax_cache`` inside the checkout, so only a checkout's first run of
a cell compiles; it keeps every entry (no size limit, so no eviction).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    import repro.serving  # noqa: F401  the system under test: fail early without it
    import harness
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
