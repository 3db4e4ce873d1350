"""Cross-cutting property tests (system invariants)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# DP optimality (paper Sec. 3.4): no schedule with the same budget beats DP
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dp_ctx():
    from repro.core.denoisers import BernoulliGauss, make_mmse_interp
    from repro.core.rate_alloc import dp_allocate
    from repro.core.rate_distortion import RDModel
    from repro.core.state_evolution import CSProblem
    prob = CSProblem(prior=BernoulliGauss(eps=0.05))
    rd = RDModel(prob.prior)
    mm = make_mmse_interp(prob.prior)
    t, r_total = 8, 16.0
    dp = dp_allocate(prob, 30, t, r_total, rd=rd, mmse_fn=mm)
    return prob, rd, mm, t, r_total, dp


def _run_schedule(prob, rd, mm, rates, p=30):
    sig = prob.sigma0_2
    for rt in rates:
        sq2 = float(rd.distortion_msg(max(rt, 0.0), sig, p))
        sig = prob.sigma_e2 + float(mm(sig + p * sq2)) / prob.kappa
    return sig


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_dp_beats_random_schedules(dp_ctx, seed):
    """DP's final variance is minimal among random same-budget schedules
    (on the DP's own rate grid, where its optimality claim lives)."""
    prob, rd, mm, t, r_total, dp = dp_ctx
    rng = np.random.default_rng(seed)
    # random split of the budget on the 0.1-bit grid
    ticks = int(round(r_total / 0.1))
    counts = rng.multinomial(ticks, np.ones(t) / t)
    rates = counts * 0.1
    sig_rand = _run_schedule(prob, rd, mm, rates)
    assert dp.sigma2_d[-1] <= sig_rand * (1 + 1e-9), (rates, sig_rand)


# ---------------------------------------------------------------------------
# head padding mask invariants
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(kv=st.integers(1, 8), g=st.integers(1, 8),
       mult=st.sampled_from([4, 8, 16]))
def test_head_mask_counts(kv, g, mult):
    import dataclasses
    from repro.configs.base import ModelConfig
    from repro.models.layers import head_mask
    h = kv * g
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=8,
                      n_heads=h, n_kv_heads=kv, d_head=4, d_ff=8, vocab=64)
    cfgp = cfg.padded_heads(mult)
    assert cfgp.h_eff % mult == 0
    assert cfgp.h_eff % cfgp.kv_eff == 0
    m = head_mask(cfgp)
    if m is None:  # no padding was needed
        assert cfgp.h_eff == h
        return
    m = np.asarray(m)
    # exactly the original number of active heads, correctly grouped
    assert int(m.sum()) == h
    g_eff = cfgp.h_eff // cfgp.kv_eff
    grouped = m.reshape(cfgp.kv_eff, g_eff)
    assert np.all(grouped.sum(axis=1)[:kv] == g)


# ---------------------------------------------------------------------------
# compressed psum properties
# ---------------------------------------------------------------------------

def test_compressed_psum_zero_and_determinism(multidev):
    multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.compression import compressed_psum, QuantConfig
mesh = jax.make_mesh((4,), ('d',))
fn = jax.jit(jax.shard_map(
    lambda v: compressed_psum(v[0], 'd', QuantConfig(bits=8, block=128))[0][None],
    mesh=mesh, in_specs=P('d', None), out_specs=P('d', None),
    axis_names={'d'}, check_vma=False))
# zeros -> exactly zeros (no bias injected by the scale floor)
z = jnp.zeros((4, 1000), jnp.float32)
assert np.all(np.asarray(fn(z)) == 0.0)
# determinism: same input -> bit-identical output
x = jnp.asarray(np.random.default_rng(7).normal(size=(4, 1000)).astype(np.float32))
a, b = np.asarray(fn(x)), np.asarray(fn(x))
assert np.array_equal(a, b)
# sign symmetry: Q(-x) == -Q(x) for the midtread quantizer
c = np.asarray(fn(-x))
assert np.allclose(a, -c, atol=1e-6)
print('ok')
""", 4)


# ---------------------------------------------------------------------------
# int4/int8 block-quantization round-trip properties (the serving/collective
# wire format: core/compression.py)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(bits=st.sampled_from([4, 8]), block=st.sampled_from([128, 512]),
       n=st.integers(1, 1500), seed=st.integers(0, 2**31 - 1),
       scale=st.floats(1e-3, 1e3))
def test_block_quantize_roundtrip_properties(bits, block, n, seed, scale):
    """pack/unpack identity, |dequant error| <= Delta_b/2, and the
    quant_noise_var accounting upper-bounds the realized MSE."""
    from repro.core.compression import (QuantConfig, dequantize_blocks,
                                        pack_int4, quant_noise_var,
                                        quantize_blocks, unpack_int4)
    qc = QuantConfig(bits=bits, block=block)
    rng = np.random.default_rng(seed)
    x = jnp.asarray((rng.normal(size=(2, n)) * scale).astype(np.float32))
    q, s = quantize_blocks(x, qc)

    # (a) symbols bounded by the wire width (no silent overflow)
    assert int(jnp.abs(q).max()) <= qc.qmax

    # (b) int4 wire format: pack/unpack is the identity on symbols
    if bits == 4:
        assert (unpack_int4(pack_int4(q)) == q).all()

    # (c) per-element reconstruction error <= Delta_b/2: the bf16 scale
    # nudge guarantees the max element never clips
    deq = np.asarray(dequantize_blocks(q, s, qc, orig_len=n))
    err = np.abs(deq - np.asarray(x))
    d_elem = np.repeat(np.asarray(s, np.float32), qc.block, axis=-1)[:, :n]
    assert (err <= d_elem / 2 + 1e-6 * float(scale)).all()

    # (d) quant_noise_var = mean(Delta_b^2)/12 upper-bounds the realized
    # MSE up to the uniform-error worst case factor 3 (Delta^2/4 vs /12);
    # measured over the padded layout (q keeps the block padding)
    deq_pad = np.asarray(dequantize_blocks(q, s, qc))
    x_pad = np.zeros_like(deq_pad)
    x_pad[:, :n] = np.asarray(x)
    mse = float(np.mean((deq_pad - x_pad) ** 2))
    assert mse <= 3.0 * float(quant_noise_var(s, qc)) + 1e-12 * scale**2


# ---------------------------------------------------------------------------
# quantized SE monotonicity in the rate (more bits never hurt)
# ---------------------------------------------------------------------------

def test_se_monotone_in_rate():
    from repro.core.denoisers import BernoulliGauss, make_mmse_interp
    from repro.core.rate_distortion import RDModel
    from repro.core.state_evolution import CSProblem
    prob = CSProblem(prior=BernoulliGauss(eps=0.05))
    rd = RDModel(prob.prior)
    mm = make_mmse_interp(prob.prior)
    finals = []
    for rate in (0.5, 1.0, 2.0, 4.0):
        sig = prob.sigma0_2
        for _ in range(8):
            sq2 = float(rd.distortion_msg(rate, sig, 30))
            sig = prob.sigma_e2 + float(mm(sig + 30 * sq2)) / prob.kappa
        finals.append(sig)
    assert all(a >= b - 1e-12 for a, b in zip(finals, finals[1:])), finals
