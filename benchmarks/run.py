"""Benchmark harness — one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows followed by human-readable tables.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np


def bench_fig1() -> list[str]:
    """Fig. 1: SDR + per-iteration rates for the three sparsities."""
    from paper_repro import EPS_LIST, run_fig1
    rows = []
    for eps in EPS_LIST:
        t0 = time.time()
        fig = run_fig1(eps)
        dt = (time.time() - t0) * 1e6
        print(f"--- eps={eps} (T={fig['T']}) ---")
        print("  SE SDR      :", np.round(fig["se_sdr"], 2))
        print("  AMP sim SDR :", np.round(fig["centralized_sdr"], 2))
        print("  BT sim SDR  :", np.round(fig["bt_sdr_sim"], 2))
        print("  BT rates    :", np.round(fig["bt_rates_sim"], 2))
        print("  DP sim SDR  :", np.round(fig["dp_sdr_sim"], 2))
        print("  DP rates(RD):", np.round(fig["dp_rates_rd"], 2))
        rows.append(f"fig1_eps{eps},{dt:.0f},"
                    f"T={fig['T']};centralized_final={fig['centralized_sdr'][-1]:.2f}dB;"
                    f"bt_final={fig['bt_sdr_sim'][-1]:.2f}dB;"
                    f"dp_final={fig['dp_sdr_sim'][-1]:.2f}dB;"
                    f"bt_max_rate={np.max(fig['bt_rates_sim']):.2f}b")
    return rows


def bench_table1() -> list[str]:
    """Table 1: total bits/element, ours vs paper."""
    from paper_repro import PAPER_TABLE1, run_table1
    rows = []
    print(f"{'eps':>5s} {'T':>3s} {'BT-RD':>14s} {'BT-ECSQ':>14s} "
          f"{'DP-RD':>14s} {'DP-ECSQ':>14s}  (ours/paper)")
    for r in run_table1():
        p = PAPER_TABLE1[r["eps"]]
        print(f"{r['eps']:5.2f} {r['T']:3d} "
              f"{r['bt_rd_total']:6.2f}/{p['bt_rd']:6.2f} "
              f"{r['bt_ecsq_total']:6.2f}/{p['bt_ecsq']:6.2f} "
              f"{r['dp_rd_total']:6.2f}/{p['dp_rd']:6.2f} "
              f"{r['dp_ecsq_total']:6.2f}/{p['dp_ecsq']:6.2f}")
        rows.append(
            f"table1_eps{r['eps']},{r['runtime_s']*1e6:.0f},"
            f"bt_rd={r['bt_rd_total']:.2f};bt_ecsq={r['bt_ecsq_total']:.2f};"
            f"dp_rd={r['dp_rd_total']:.2f};dp_ecsq={r['dp_ecsq_total']:.2f};"
            f"dp_sdr_gap={r['centralized_final_sdr']-r['dp_final_sdr']:.2f}dB")
    return rows


def bench_ablation() -> list[str]:
    """Rate-allocation policy ablation (DP vs uniform vs front/back-loaded)."""
    from bench_ablation import run_ablation
    rows = []
    for name, v in run_ablation().items():
        print(f"{name:14s} SDR {v['final_sdr']:6.2f} dB  "
              f"({v['bits_spent']:.1f} bits/elem)")
        rows.append(f"ablation_{name},0,sdr={v['final_sdr']:.2f}dB;"
                    f"bits={v['bits_spent']:.1f}")
    return rows


def bench_engine() -> list[str]:
    """Unified-engine benchmark: host-loop vs scan-compiled vs vmap-batched.

    The scan variant eliminates the per-iteration host sync of the legacy
    mp_amp loop; the batched variant amortizes dispatch over >=32 instances
    (the serving scenario). Reported per-instance us and MSE agreement.
    """
    import jax
    from repro.core.amp import sample_problem
    from repro.core.denoisers import BernoulliGauss
    from repro.core.engine import (AmpEngine, EcsqTransport, EngineConfig,
                                   FixedSchedule)
    from repro.core.state_evolution import CSProblem

    import jax.numpy as jnp
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=2048, m=1024, prior=prior)
    t_iter, p, batch = 10, 8, 32
    deltas = np.full(t_iter, 0.05, np.float32)
    # one shared sensing matrix, B consistent measurement vectors from it
    _, a_shared, y0 = sample_problem(jax.random.PRNGKey(0), prob.n, prob.m,
                                     prior, prob.sigma_e2)
    ys = [y0]
    for i in range(1, batch):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(100 + i), 3)
        support = jax.random.bernoulli(k1, prior.eps, (prob.n,))
        s_i = jnp.where(support, jax.random.normal(k2, (prob.n,)), 0.0)
        e_i = np.sqrt(prob.sigma_e2) * jax.random.normal(k3, (prob.m,))
        ys.append(np.asarray(a_shared @ np.asarray(s_i) + np.asarray(e_i),
                             np.float32))
    ys = np.stack(ys)

    engine = AmpEngine(
        prior, EngineConfig(n_proc=p, n_iter=t_iter, collect_symbols=False,
                            collect_xs=False),
        EcsqTransport(), FixedSchedule(deltas))

    def timeit(fn, reps):
        fn()  # warmup / compile
        t0 = time.time()
        for _ in range(reps):
            fn()
        return (time.time() - t0) / reps * 1e6

    us_host = timeit(lambda: engine.solve_host_loop(ys[0], a_shared), 3)
    us_scan = timeit(lambda: engine.solve(ys[0], a_shared), 3)
    us_batch = timeit(lambda: engine.solve_many(ys, a_shared), 3) / batch

    x_scan = engine.solve(ys[0], a_shared).x
    x_host = engine.solve_host_loop(ys[0], a_shared).x
    agree = float(np.abs(x_scan - x_host).max())
    print(f"host-loop : {us_host:9.0f} us/solve")
    print(f"scan      : {us_scan:9.0f} us/solve   ({us_host / us_scan:.2f}x)")
    print(f"batched   : {us_batch:9.0f} us/solve   ({us_host / us_batch:.2f}x,"
          f" B={batch})")
    print(f"scan vs host max|dx| = {agree:.2e}")
    return [
        f"engine_host_loop,{us_host:.0f},T={t_iter};P={p}",
        f"engine_scan,{us_scan:.0f},speedup_vs_host={us_host / us_scan:.2f}x",
        f"engine_batched,{us_batch:.0f},B={batch};"
        f"speedup_vs_host={us_host / us_batch:.2f}x;max_dx={agree:.2e}",
    ]


def bench_compressed_psum() -> list[str]:
    """Microbenchmark: compressed vs exact psum (CPU wall time + error)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.compression import QuantConfig, compressed_psum

    n_dev = jax.device_count()
    if n_dev < 2:
        return ["compressed_psum,0,skipped_single_device"]
    mesh = jax.make_mesh((n_dev,), ("d",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n_dev, 1 << 16)).astype(np.float32))
    rows = []
    for bits in (8, 4):
        fn = jax.jit(jax.shard_map(
            lambda v: compressed_psum(v[0], "d", QuantConfig(bits=bits))[0][None],
            mesh=mesh, in_specs=P("d", None), out_specs=P("d", None),
            axis_names={"d"}, check_vma=False))
        out = np.asarray(fn(x))[0]
        t0 = time.time()
        for _ in range(5):
            fn(x)[0].block_until_ready()
        dt = (time.time() - t0) / 5 * 1e6
        ref = np.asarray(x).sum(0)
        rel = float(np.abs(out - ref).max() / np.abs(ref).max())
        print(f"int{bits}: rel_err={rel:.2e} {dt:.0f}us/call")
        rows.append(f"compressed_psum_int{bits},{dt:.0f},rel_err={rel:.2e};"
                    f"wire_reduction={'4x' if bits == 8 else '8x'}")
    return rows


def bench_roofline() -> list[str]:
    """Roofline table from dry-run artifacts (if present)."""
    from roofline import format_table, load_cells
    ddir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "results", "dryrun")
    if not os.path.isdir(ddir):
        return ["roofline,0,no_dryrun_artifacts"]
    rows = load_cells(ddir)
    print(format_table(rows))
    out = []
    for r in rows:
        if "compute_s" in r:
            out.append(f"roofline_{r['arch']}_{r['shape']},0,"
                       f"dominant={r['dominant']};frac={r['roofline_frac']:.3f}")
    return out


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    all_rows: list[str] = []
    print("=== Fig. 1 reproduction (SDR + rates per iteration) ===")
    all_rows += bench_fig1()
    print("\n=== Table 1 reproduction (total bits/element) ===")
    all_rows += bench_table1()
    print("\n=== rate-allocation ablation (eps=0.05, R=2T) ===")
    all_rows += bench_ablation()
    print("\n=== unified engine (host-loop vs scan vs batched) ===")
    all_rows += bench_engine()
    print("\n=== compressed psum microbenchmark ===")
    all_rows += bench_compressed_psum()
    print("\n=== roofline (from dry-run artifacts) ===")
    all_rows += bench_roofline()
    print("\nname,us_per_call,derived")
    for r in all_rows:
        print(r)


if __name__ == "__main__":
    main()
