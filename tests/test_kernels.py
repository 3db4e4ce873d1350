"""Per-kernel interpret-mode validation against the pure-jnp oracles,
sweeping shapes and dtypes (assignment requirement (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.quantize.ops import dequantize, quantize
from repro.kernels.amp_fused.ops import amp_local_step


@pytest.mark.parametrize("shape", [(1, 512), (100, 1000), (256, 2048),
                                   (257, 2049), (3, 4096)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_quantize_kernel_vs_ref(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**31)
    x = jnp.asarray((rng.normal(size=shape) * 7).astype(dtype))
    xf = x.astype(jnp.float32)
    q1, s1, orig = quantize(xf, use_pallas=True, interpret=True)
    q2, s2, _ = quantize(xf, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1, np.float32),
                                  np.asarray(s2, np.float32))
    x1 = dequantize(q1, s1, orig, use_pallas=True, interpret=True)
    x2 = dequantize(q2, s2, orig, use_pallas=False)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=1e-6)
    # reconstruction error bound
    err = np.abs(np.asarray(x1) - np.asarray(xf))
    assert err.max() <= float(np.asarray(s1, np.float32).max()) * 0.5 + 1e-6


@pytest.mark.parametrize("qmax", [127, 7])
def test_quantize_kernel_qmax(qmax):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 2048)).astype(np.float32))
    q, s, orig = quantize(x, qmax=qmax, use_pallas=True, interpret=True)
    assert int(np.abs(np.asarray(q)).max()) <= qmax


@pytest.mark.parametrize("m,n", [(100, 1000), (128, 512), (130, 700),
                                 (512, 2048)])
def test_amp_fused_kernel_vs_ref(m, n):
    rng = np.random.default_rng(m * n)
    a = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32)) / np.sqrt(m)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    y = jnp.asarray(rng.normal(size=m).astype(np.float32))
    z = jnp.asarray(rng.normal(size=m).astype(np.float32))
    for ons in (0.0, 0.45):
        z1, f1 = amp_local_step(a, x, y, z, ons, 30, use_pallas=False)
        z2, f2 = amp_local_step(a, x, y, z, jnp.float32(ons), 30,
                                use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(z1), np.asarray(z2),
                                   rtol=3e-5, atol=3e-6)
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                                   rtol=3e-5, atol=3e-6)


def test_amp_solver_with_kernel_matches_plain():
    """Full MP-AMP iteration built on the fused kernel == einsum solver."""
    from repro.core.amp import sample_problem
    from repro.core.denoisers import BernoulliGauss
    from repro.core.state_evolution import CSProblem
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=1000, m=300, prior=prior)
    s0, a, y = sample_problem(jax.random.PRNGKey(0), prob.n, prob.m, prior,
                              prob.sigma_e2)
    # one LC step on processor 0's shard, kernel vs ref
    a0, y0 = a[:30], y[:30]
    x = jnp.asarray(np.random.default_rng(1).normal(size=prob.n).astype(np.float32)) * 0.1
    z = jnp.asarray(y0)
    z1, f1 = amp_local_step(jnp.asarray(a0), x, jnp.asarray(y0), z, 0.3, 10,
                            use_pallas=False)
    z2, f2 = amp_local_step(jnp.asarray(a0), x, jnp.asarray(y0), z,
                            jnp.float32(0.3), 10, use_pallas=True,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-4,
                               atol=1e-5)


def test_engine_pallas_path_interpret_matches_ref():
    """The engine's ``use_kernel`` path runs the fused Pallas LC kernel in
    interpret mode on CPU — a full scan-compiled solve, not just the
    per-op parity above — so kernel regressions surface in CI without TPU
    hardware (previously this path was untestable off-TPU)."""
    from repro.core.amp import sample_problem
    from repro.core.denoisers import BernoulliGauss
    from repro.core.engine import (AmpEngine, EcsqTransport, EngineConfig,
                                   FixedSchedule)
    from repro.core.state_evolution import CSProblem
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=512, m=128, prior=prior)
    s0, a, y = sample_problem(jax.random.PRNGKey(5), prob.n, prob.m, prior,
                              prob.sigma_e2)
    deltas = np.full(3, np.inf, np.float32)
    mk = lambda use, interp: AmpEngine(
        prior, EngineConfig(n_proc=2, n_iter=3, use_kernel=use,
                            kernel_interpret=interp, collect_symbols=False),
        EcsqTransport(), FixedSchedule(deltas))
    ref = mk(False, False).solve(y, a)
    pal = mk(True, True).solve(y, a)
    np.testing.assert_allclose(pal.x, ref.x, atol=5e-6)
    np.testing.assert_allclose(pal.sigma2_hat, ref.sigma2_hat, rtol=1e-5)


def test_serving_pallas_path_interpret_matches_ref():
    """The serving het-batch path (vmapped scan over the Pallas kernel,
    interpret mode) matches the jnp reference for a mixed-shape batch."""
    from repro.core.amp import sample_problem
    from repro.core.denoisers import BernoulliGauss
    from repro.core.state_evolution import CSProblem
    from repro.serving import BucketPolicy, SolveRequest, SolveService
    prior = BernoulliGauss(eps=0.1)
    reqs = []
    for i, (n, m) in enumerate([(256, 64), (200, 64)]):
        prob = CSProblem(n=n, m=m, prior=prior)
        _, a, y = sample_problem(jax.random.PRNGKey(i), n, m, prior,
                                 prob.sigma_e2)
        reqs.append(SolveRequest(y=y, a=a, prior=prior, n_proc=2, n_iter=3,
                                 policy="lossless"))
    pol = BucketPolicy(max_batch=2, n_quantum=256, mp_quantum=32)
    ref = SolveService(policy=pol, rate_accounting=False,
                       use_kernel=False).solve(reqs)
    pal = SolveService(policy=pol, rate_accounting=False, use_kernel=True,
                       kernel_interpret=True).solve(reqs)
    for r, p in zip(ref, pal):
        np.testing.assert_allclose(p.x, r.x, atol=5e-6)


@pytest.mark.parametrize("p,mp,n", [(1, 128, 512), (4, 64, 500),
                                    (3, 150, 1000)])
@pytest.mark.parametrize("a_dtype", ["float32", "bfloat16"])
def test_amp_local_grid_matches_ref(p, mp, n, a_dtype):
    """Batched-grid kernel (P folded into the grid, sigma2_hat numerator
    fused into the z-pass) == the batched reference, for f32 and bf16
    A-streaming (both sides stream the same bf16 A; accumulation f32)."""
    import jax.numpy as jnp
    from repro.kernels.amp_fused.ops import amp_local_grid, pad_row_shards
    rng = np.random.default_rng(p * mp * n)
    a = jnp.asarray((rng.normal(size=(p, mp, n)) / np.sqrt(p * mp))
                    .astype(np.float32)).astype(a_dtype)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(p, mp)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(p, mp)).astype(np.float32))
    ap, yp = pad_row_shards(a, y)
    zp = jnp.pad(z, ((0, 0), (0, ap.shape[1] - mp)))
    xp_ = jnp.pad(x, (0, ap.shape[2] - n))
    z1, f1, ss1 = amp_local_grid(ap, xp_, yp, zp, 0.37, 10,
                                 use_pallas=True, interpret=True)
    z0, f0, ss0 = amp_local_grid(a, x, y, z, 0.37, 10, use_pallas=False)
    np.testing.assert_allclose(np.asarray(z1)[:, :mp], np.asarray(z0),
                               rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(f1)[:, :n], np.asarray(f0),
                               rtol=3e-5, atol=3e-5)
    # padded rows/cols are exactly zero, so the fused ss is the true sum
    assert np.all(np.asarray(z1)[:, mp:] == 0.0)
    np.testing.assert_allclose(float(ss1), float(ss0), rtol=1e-5)


@pytest.mark.parametrize("bm,bn", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("a_dtype", ["float32", "bfloat16"])
def test_amp_local_pallas_grid_multi_tile_matches_ref(bm, bn, a_dtype):
    """Explicit small tiles, so that both passes accumulate over several
    grid steps (z over N tiles, f over M tiles, the fused ss over
    (p, i)): the paths that byte-budgeted tiles leave one-step at small
    test shapes."""
    from repro.kernels.amp_fused.amp_fused import amp_local_pallas_grid
    from repro.kernels.amp_fused.ref import amp_local_ref_grid
    p, mp, n = 3, 256, 512
    rng = np.random.default_rng(bm * bn)
    a = jnp.asarray((rng.normal(size=(p, mp, n)) / np.sqrt(p * mp))
                    .astype(np.float32)).astype(a_dtype)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(p, mp)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(p, mp)).astype(np.float32))
    z1, f1, ss1 = amp_local_pallas_grid(a, x, y, z, 0.37, 10, interpret=True,
                                        bm=bm, bn=bn)
    z0, f0, ss0 = amp_local_ref_grid(a, x, y, z, 0.37, 10)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z0),
                               rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f0),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(float(ss1), float(ss0), rtol=1e-5)


def _round_up(v, q):
    return -(-v // q) * q


def _n_pad_512(n):
    """N padded by balanced tiles of at most 512 columns (the bound every
    byte-budgeted split must stay within)."""
    k = -(-n // 512)
    return k * _round_up(-(-n // k), 128)


@pytest.mark.parametrize("a_bytes", [2, 4])
@pytest.mark.parametrize("mp", [1, 75, 100, 112, 150, 512, 513, 912, 1024])
def test_row_tiles_fit_budget_align_and_never_grow_n(mp, a_bytes):
    """``row_tiles`` over a sweep of N: the A block fits the byte budget,
    bm sits on the 8-row quantum (one tile) or the 128-row one (several),
    bn on the 128-lane quantum, padded N never exceeds the 512-column
    split's, no fewer tiles would do, and the tiles re-derived from the
    padded stack (as the kernel does) divide it."""
    from repro.kernels.amp_fused.amp_fused import BLOCK_BYTES
    from repro.kernels.amp_fused.ops import row_tiles
    for n in [*range(1, 130_000, 293), 10_000, 10_240, 120_000]:
        bm, bn = row_tiles(mp, n, a_bytes)
        if mp <= 512:
            assert bm == _round_up(mp, 8), (mp, bm)
        else:
            assert bm % 128 == 0 and bm <= 512, (mp, bm)
        assert bn % 128 == 0 and bm * bn * a_bytes <= BLOCK_BYTES, (n, bn)
        n_pad = _round_up(n, bn)
        assert n_pad <= _n_pad_512(n), (n, bn)
        k = n_pad // bn
        for fewer in range(max(1, k - 3), k):
            t = _round_up(-(-n // fewer), 128)
            assert (bm * t * a_bytes > BLOCK_BYTES
                    or fewer * t > _n_pad_512(n)), (n, bn, fewer)
        bm2, bn2 = row_tiles(_round_up(mp, bm), n_pad, a_bytes)
        assert _round_up(mp, bm) % bm2 == 0 and n_pad % bn2 == 0, (n, bn2)
    # the paper's bucket (Mp 112, N 10,240): four ~1.1 MB f32 blocks
    assert row_tiles(112, 10_240, 4) == (112, 2560)
    assert row_tiles(112, 10_240, 2) == (112, 5120)


def _walk_eqns(jaxpr):
    """All eqns of a jaxpr, recursing into sub-jaxprs (scan/pjit/...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                yield from _walk_eqns(sub)
            elif isinstance(v, (list, tuple)):
                for vv in v:
                    sub = getattr(vv, "jaxpr", None)
                    if sub is not None:
                        yield from _walk_eqns(sub)


def test_no_matrix_pad_inside_scan_body():
    """ISSUE 5 satellite: tile-alignment of the (M, N) operand happens
    once at solve entry, never per iteration — the scanned body's jaxpr
    contains no rank>=2 ``pad`` (only the cheap (N,) message-vector pad
    is allowed inside the scan)."""
    from repro.core.denoisers import BernoulliGauss
    from repro.core.engine import AmpEngine, EngineConfig

    prior = BernoulliGauss(eps=0.1)
    eng = AmpEngine(prior, EngineConfig(n_proc=2, n_iter=3, use_kernel=True,
                                        kernel_interpret=True,
                                        collect_symbols=False))
    m, n = 300, 1000                      # forces tile padding (150 -> 256)
    a = np.zeros((m, n), np.float32)
    y = np.zeros(m, np.float32)
    a_p, y_p = eng._split(y, a)
    assert a_p.shape != (2, 150, 1000), "test expects a padded shard stack"
    jaxpr = jax.make_jaxpr(
        lambda ap, yp, sched: eng._scan_fn(m, n)(ap, yp, sched))(
            a_p, y_p, eng._sched_operand())
    scans = [e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "scan"]
    assert scans, "solve should be scan-compiled"
    for scan in scans:
        for eqn in _walk_eqns(scan.params["jaxpr"].jaxpr):
            if eqn.primitive.name == "pad":
                assert eqn.outvars[0].aval.ndim < 2, (
                    f"matrix-sized pad inside the scanned body: "
                    f"{eqn.outvars[0].aval}")


def test_engine_bf16_kernel_interpret_matches_bf16_ref():
    """bf16 A-streaming through the Pallas path (interpret) == bf16
    through the reference path: the dtype is a storage/streaming choice,
    not a kernel-specific numeric."""
    from repro.core.amp import sample_problem
    from repro.core.denoisers import BernoulliGauss
    from repro.core.engine import AmpEngine, EngineConfig
    from repro.core.state_evolution import CSProblem
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=512, m=128, prior=prior)
    s0, a, y = sample_problem(jax.random.PRNGKey(11), prob.n, prob.m, prior,
                              prob.sigma_e2)
    mk = lambda use, interp: AmpEngine(
        prior, EngineConfig(n_proc=2, n_iter=4, use_kernel=use,
                            kernel_interpret=interp, collect_symbols=False,
                            a_dtype="bfloat16"))
    ref = mk(False, False).solve(y, a)
    pal = mk(True, True).solve(y, a)
    assert float(np.mean((pal.x - ref.x) ** 2)) <= 1e-10
    np.testing.assert_allclose(pal.sigma2_hat, ref.sigma2_hat, rtol=1e-4)


@pytest.mark.parametrize("b,h,kv,dh,s,pos,win",
                         [(2, 8, 2, 64, 1024, 700, 0),
                          (1, 4, 4, 32, 512, 511, 0),
                          (2, 6, 2, 64, 1000, 600, 128)])
def test_decode_attn_kernel_vs_ref(b, h, kv, dh, s, pos, win):
    from repro.kernels.decode_attn.ops import decode_attention
    rng = np.random.default_rng(b * s)
    q = jnp.asarray(rng.normal(size=(b, h, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, kv, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, kv, dh)).astype(np.float32))
    o1 = decode_attention(q, k, v, pos, win, use_pallas=False)
    o2 = decode_attention(q, k, v, jnp.int32(pos), win, use_pallas=True,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,t,h,dh", [(2, 64, 3, 16), (1, 100, 2, 64),
                                      (2, 32, 4, 8)])
def test_wkv6_kernel_vs_scan(b, t, h, dh):
    from repro.kernels.wkv6.ops import wkv6
    from repro.models.rwkv6 import wkv_scan_ref
    key = jax.random.PRNGKey(b * t)
    ks = jax.random.split(key, 5)
    r, k, v = (0.5 * jax.random.normal(ks[i], (b, t, h, dh)) for i in range(3))
    logw = jnp.maximum(-jnp.exp(jax.random.normal(ks[3], (b, t, h, dh)) * 0.5
                                - 1.0), -2.0)
    u = 0.3 * jax.random.normal(ks[4], (h, dh))
    y_ref, _ = wkv_scan_ref(r, k, v, logw, u)
    y_pal = wkv6(r, k, v, logw, u, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=3e-4, atol=3e-4)
