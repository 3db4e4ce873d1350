import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.compression import (QuantConfig, dequantize_blocks, pack_int4,
                                    quantize_blocks, unpack_int4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 4096))
def test_int4_pack_roundtrip(seed, n):
    n = n * 2  # even
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.integers(-7, 8, n), jnp.int8)
    assert (unpack_int4(pack_int4(q)) == q).all()


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e4])
def test_quant_error_bound(bits, scale):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 2048)).astype(np.float32)) * scale
    qc = QuantConfig(bits=bits, block=256)
    q, s = quantize_blocks(x, qc)
    xr = dequantize_blocks(q, s, qc, orig_len=2048)
    err = np.abs(np.asarray(xr) - np.asarray(x))
    bound = np.asarray(s, np.float32).repeat(256, -1).reshape(err.shape) * 0.5
    assert (err <= bound + 1e-12 * scale).all()


def test_quant_handles_zeros_and_padding():
    qc = QuantConfig(bits=8, block=256)
    x = jnp.zeros((1, 100), jnp.float32)  # shorter than a block
    q, s = quantize_blocks(x, qc)
    xr = dequantize_blocks(q, s, qc, orig_len=100)
    assert np.allclose(np.asarray(xr), 0.0)


def test_compressed_psum_multidevice(multidev):
    multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.compression import compressed_psum, QuantConfig
mesh = jax.make_mesh((8,), ('d',))
rng = np.random.default_rng(1)
x = jnp.asarray(rng.normal(size=(8, 3000)).astype(np.float32))
for bits, tol in ((8, 0.02), (4, 0.25)):
    fn = jax.jit(jax.shard_map(
        lambda v: compressed_psum(v[0], 'd', QuantConfig(bits=bits, block=256))[0][None],
        mesh=mesh, in_specs=P('d', None), out_specs=P('d', None),
        axis_names={'d'}, check_vma=False))
    y = np.asarray(fn(x))
    ref = np.asarray(x).sum(0)
    rel = np.abs(y[0] - ref).max() / np.abs(ref).max()
    assert rel < tol, (bits, rel)
    for i in range(8):
        assert np.allclose(y[i], y[0])   # all devices agree exactly
print('ok')
""")


def test_compressed_psum_int8_wire_visible(multidev):
    """The lowered HLO must carry int8 (u8/s8) collective operands."""
    multidev("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.compression import compressed_psum, QuantConfig
mesh = jax.make_mesh((8,), ('d',))
fn = jax.jit(jax.shard_map(
    lambda v: compressed_psum(v[0], 'd', QuantConfig(bits=8, block=256))[0][None],
    mesh=mesh, in_specs=P('d', None), out_specs=P('d', None),
    axis_names={'d'}, check_vma=False))
txt = fn.lower(jnp.zeros((8, 3000), jnp.float32)).compile().as_text()
coll = [l for l in txt.splitlines() if 'all-to-all' in l or 'all-gather' in l]
int8_coll = [l for l in coll if 's8[' in l or 'u8[' in l]
assert int8_coll, coll[:5]
print('ok')
""")


@pytest.mark.parametrize("orig_len", [4095, 4093])
def test_int4_odd_length_pad_roundtrip(orig_len):
    """int4 wire encode with an odd (non-block-multiple) length: the block
    padding plus nibble packing must round-trip back to |err| <= Delta/2 on
    exactly the original elements (DESIGN.md §2)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, orig_len)).astype(np.float32))
    qc = QuantConfig(bits=4, block=256)
    q, s = quantize_blocks(x, qc)
    # the padded symbol stream is what travels: pack -> unpack -> dequantize
    q_wire = unpack_int4(pack_int4(q))
    assert (q_wire == q).all()
    xr = dequantize_blocks(q_wire, s, qc, orig_len=orig_len)
    assert xr.shape == (1, orig_len)
    err = np.abs(np.asarray(xr) - np.asarray(x))
    bound = np.asarray(s, np.float32).repeat(256, -1)[:, :orig_len] * 0.5
    assert (err <= bound + 1e-12).all()


def test_compressed_psum_int4_wire_visible(multidev):
    """DESIGN.md §2 claims s8/u8 collective operands for the *int4* wire
    too (nibbles packed into uint8); lower at an odd per-chunk length so
    the pack/pad path is the one being compiled."""
    multidev("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.compression import compressed_psum, QuantConfig
mesh = jax.make_mesh((8,), ('d',))
qc = QuantConfig(bits=4, block=256)
fn = jax.jit(jax.shard_map(
    lambda v: compressed_psum(v[0], 'd', qc)[0][None],
    mesh=mesh, in_specs=P('d', None), out_specs=P('d', None),
    axis_names={'d'}, check_vma=False))
n = 2999  # odd, not a multiple of the 8*256*2 chunking quantum
txt = fn.lower(jnp.zeros((8, n), jnp.float32)).compile().as_text()
coll = [l for l in txt.splitlines() if 'all-to-all' in l or 'all-gather' in l]
int8_coll = [l for l in coll if 's8[' in l or 'u8[' in l]
assert int8_coll, coll[:5]
# the only non-integer collectives are the per-block scale side channels
# (<= chunk/block elements each; XLA CPU widens their bf16 to f32) — no
# full-chunk-width float payload may appear on the wire
import re
for l in coll:
    if not (' all-to-all(' in l or ' all-gather(' in l):
        continue  # a fusion consuming a collective result, not wire
    for dt, dims in re.findall(r'(f32|bf16)\\[([0-9,]+)\\]', l):
        size = 1
        for d in dims.split(','):
            size *= int(d)
        assert size <= 8 * 8 * 2, (size, l)  # devices^2 x scale blocks
# and the lowered program still sums correctly (quantization error only)
rng = np.random.default_rng(3)
x = jnp.asarray(rng.normal(size=(8, n)).astype(np.float32))
y = np.asarray(fn(x))
ref = np.asarray(x).sum(0)
rel = np.abs(y[0] - ref).max() / np.abs(ref).max()
assert rel < 0.25, rel
print('ok')
""")
