"""LC kernel-suite benchmark: the machine-readable kernel perf trajectory.

Times the fused AMP local-computation step per (layout x batch x P) cell
in three variants (DESIGN.md §8):

  * ``vmap_ref``  — the pre-v2 baseline: per-processor LC ``vmap``ed over
    P (and again over the batch), sum-of-squares reduction separate;
  * ``batched``   — the v2 engine path: one batched-grid fused op over
    the whole (B, P) stack — the compiled Pallas kernels on TPU; off TPU
    the XLA-compiled jnp reference, and the report says so
    (``batched_impl``);
  * ``interpret`` — the Pallas kernels through the interpreter (the CI
    parity path; orders of magnitude slower, timed for trend only).

Each cell reports achieved GB/s for the batched variant against the
``roofline.lc_bytes`` HBM model (A read exactly twice per step) and the
memory-bound time floor at the device's published HBM bandwidth
(``roofline.PEAKS``, keyed by ``device_kind``). A device without a
published peak (the CPU among them) needs an explicit ``--bw``. Results
land in ``BENCH_kernels.json`` with backend / device kind / commit
provenance.

  PYTHONPATH=src python benchmarks/bench_kernels.py [--smoke] [--bw BPS]

Acceptance tracking: the compiled batched path must beat the
per-processor vmap baseline on the (row, B=8, P=4) cell; a miss prints a
warning (and fails a non-smoke run, mirroring bench_serve).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from roofline import device_peaks, git_commit, lc_bytes  # noqa: E402


def time_variants(ops: dict, reps: int, inner: int = 3) -> dict:
    """Seconds per call per variant: min over ``reps`` rounds, variants
    interleaved round-robin within each round so noisy-neighbor phases on
    shared CI boxes hit every variant equally."""
    for fn in ops.values():
        fn()  # warmup / compile
    best = {k: float("inf") for k in ops}
    for _ in range(reps):
        for k, fn in ops.items():
            t0 = time.perf_counter()
            for _ in range(inner):
                fn()
            best[k] = min(best[k], (time.perf_counter() - t0) / inner)
    return best


def make_row_ops(b: int, p: int, m: int, n: int, interpret_cells: bool):
    """(vmap_ref, batched, interpret|None) jitted row-LC steps + operands."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.amp_fused.ops import amp_local_grid, pad_row_shards
    from repro.kernels.amp_fused.ref import (amp_local_ref_grid,
                                             amp_local_ref_vmap)

    rng = np.random.default_rng(b * 131 + p)
    mp_ = m // p
    a = jnp.asarray(rng.normal(size=(b, p, mp_, n)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, n)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(b, p, mp_)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(b, p, mp_)).astype(np.float32))

    # the kernels take tile-aligned operands (the engine pads once)
    ap, yp = pad_row_shards(a, y)
    zp = jnp.pad(z, ((0, 0), (0, 0), (0, ap.shape[-2] - mp_)))
    xp = jnp.pad(x, ((0, 0), (0, ap.shape[-1] - n)))
    pallas = lambda interpret: jax.jit(jax.vmap(
        lambda a_, x_, y_, z_: amp_local_grid(
            a_, x_, y_, z_, 0.3, p, use_pallas=True, interpret=interpret)))

    vb = jax.jit(jax.vmap(
        lambda a_, x_, y_, z_: amp_local_ref_vmap(a_, x_, y_, z_, 0.3, p)))
    block = lambda r: jax.block_until_ready(r)
    ops = {"vmap_ref": lambda: block(vb(a, x, y, z))}
    if jax.default_backend() == "tpu":
        bb = pallas(False)
        ops["batched"] = lambda: block(bb(ap, xp, yp, zp))
    else:
        bb = jax.jit(jax.vmap(
            lambda a_, x_, y_, z_: amp_local_ref_grid(a_, x_, y_, z_, 0.3,
                                                      p)))
        ops["batched"] = lambda: block(bb(a, x, y, z))
    if interpret_cells:
        ib = pallas(True)
        ops["interpret"] = lambda: block(ib(ap, xp, yp, zp))
    return ops


def make_col_ops(b: int, p: int, m: int, n: int, interpret_cells: bool):
    """Column-layout per-round LC: residual pass + fused inner step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.amp_fused.ops import (col_inner_step, col_residual,
                                             pad_col_shards)
    from repro.kernels.amp_fused.ref import (col_inner_step_ref,
                                             col_residual_ref)

    rng = np.random.default_rng(b * 173 + p)
    np_ = n // p
    a = jnp.asarray(rng.normal(size=(b, p, m, np_)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, p, np_)).astype(np.float32) * 0.1)
    z = jnp.asarray(rng.normal(size=(b, p, m)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(b, m)).astype(np.float32))
    mask = jnp.ones((np_,), jnp.float32)
    pri = (200.0, 0.1, 0.0, 1.0)  # m_eff, eps, mu_s, sigma_s2

    def step_ref(a_, x_, z_, g_):
        r = col_residual_ref(a_, x_)
        xn, c, _ = col_inner_step_ref(a_, x_, x_, z_, g_, mask, *pri, False)
        return r, xn, c

    def step_vmap(a_, x_, z_, g_):
        # per-processor vmap baseline: one column block at a time
        r = jax.vmap(lambda ap, xp_: jnp.dot(
            ap, xp_, precision=jax.lax.Precision.HIGHEST))(a_, x_)
        xn, c, _ = jax.vmap(
            lambda ap, xp_, zp: col_inner_step_ref(
                ap[None], xp_[None], xp_[None], zp[None], g_, mask, *pri,
                False))(a_, x_, z_)
        return r, xn, c

    def step_pallas(interpret):
        def f(a_, x_, z_, g_):
            r = col_residual(a_, x_, use_pallas=True, interpret=interpret)
            xn, c, _ = col_inner_step(a_, x_, x_, z_, g_, mask, *pri,
                                      update_z=False, use_pallas=True,
                                      interpret=interpret)
            return r, xn, c
        return f

    # the kernels take tile-aligned operands (the engine pads once)
    apad, gpad = pad_col_shards(a, g)
    zpad = jnp.pad(z, ((0, 0), (0, 0), (0, apad.shape[-2] - m)))
    vb = jax.jit(jax.vmap(step_vmap))
    block = lambda r: jax.block_until_ready(r)
    ops = {"vmap_ref": lambda: block(vb(a, x, z, g))}
    if jax.default_backend() == "tpu":
        bb = jax.jit(jax.vmap(step_pallas(False)))
        ops["batched"] = lambda: block(bb(apad, x, zpad, gpad))
    else:
        bb = jax.jit(jax.vmap(step_ref))
        ops["batched"] = lambda: block(bb(a, x, z, g))
    if interpret_cells:
        ib = jax.jit(jax.vmap(step_pallas(True)))
        ops["interpret"] = lambda: block(ib(apad, x, zpad, gpad))
    return ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes, fewer reps, interpret on the "
                         "smallest cells only (CI)")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--bw", type=float, default=None,
                    help="memory bandwidth for the roofline bound "
                         "(default: the device's published HBM peak; "
                         "required where none is published, e.g. CPU)")
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args()

    import jax

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind
    try:
        bw = args.bw or device_peaks(kind)["hbm_bw"]
    except KeyError as e:
        sys.exit(f"{e.args[0]}: pass --bw to compare against a bandwidth "
                 f"of your own")
    # off TPU the "batched" variant is the jnp reference, not the kernels
    impl = "pallas" if backend == "tpu" else "jnp_ref"
    # smoke keeps the full problem size (at M=256-class shapes the B=8
    # cells are dispatch-dominated and the vmap-vs-batched gap drowns in
    # jitter) but trims the cell grid and reps for CI wall-clock
    if args.smoke:
        m, n, reps = 512, 2048, 4
        batches, procs = (1, 8), (1, 4)
    else:
        m, n, reps = 512, 2048, args.reps
        batches, procs = (1, 8, 32), (1, 4, 8)

    report = {
        "backend": backend, "device_kind": kind,
        "devices": jax.device_count(), "batched_impl": impl,
        "commit": git_commit(), "smoke": bool(args.smoke),
        "m": m, "n": n, "bw_model": bw, "cells": [],
    }
    print(f"LC kernel suite: M={m} N={n} device={kind!r} "
          f"batched={impl} bw_model={bw/1e9:.0f} GB/s")
    hdr = (f"{'layout':>6s} {'B':>3s} {'P':>3s} {'vmap_ref':>10s} "
           f"{'batched':>10s} {'speedup':>8s} {'GB/s':>7s} {'roofl%':>7s} "
           f"{'interpret':>10s}")
    print(hdr)
    print("-" * len(hdr))

    target = None
    for layout in ("row", "col"):
        make = make_row_ops if layout == "row" else make_col_ops
        for b in batches:
            for p in procs:
                # interpret timings only on the smallest cells: the
                # interpreter is ~100x off, trend not throughput
                interp = (b * p <= 8) if args.smoke else (b * p <= 32)
                ops = make(b, p, m, n, interp)
                cell = {"layout": layout, "batch": b, "p": p}
                for name, dt in time_variants(ops, reps).items():
                    cell[f"{name}_s"] = dt
                bytes_ = lc_bytes(m, n, batch=b)
                cell["speedup"] = cell["vmap_ref_s"] / cell["batched_s"]
                cell["achieved_gbps"] = bytes_ / cell["batched_s"] / 1e9
                cell["roofline_frac"] = (bytes_ / bw) / cell["batched_s"]
                report["cells"].append(cell)
                if layout == "row" and b == 8 and p == 4:
                    target = cell
                it = cell.get("interpret_s")
                print(f"{layout:>6s} {b:3d} {p:3d} "
                      f"{cell['vmap_ref_s']*1e3:9.3f}ms "
                      f"{cell['batched_s']*1e3:9.3f}ms "
                      f"{cell['speedup']:7.2f}x "
                      f"{cell['achieved_gbps']:7.1f} "
                      f"{100*cell['roofline_frac']:6.1f}% "
                      + (f"{it*1e3:9.1f}ms" if it else f"{'—':>10s}"))

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"\nwrote {args.json}")

    if target is not None and target["speedup"] < 1.0:
        print(f"WARNING: batched path {target['speedup']:.2f}x vs the "
              f"vmap baseline on the (row, B=8, P=4) cell — below the "
              f"acceptance target (>1x)")
        # smoke runs on shared CI runners surface the number without
        # turning wall-clock jitter into a red build
        return 0 if args.smoke else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
