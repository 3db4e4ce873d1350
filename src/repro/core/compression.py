"""Lossy-compressed collectives — the paper's technique as a TPU-native
transport layer (DESIGN.md §2).

The paper quantizes the per-processor fusion messages f_t^p before the sum at
the fusion center. On a TPU mesh the fusion *is* an all-reduce, so the
equivalent is a two-phase compressed psum executed inside shard_map:

  phase 1 (reduce-scatter equivalent): each device splits its summand into
     P chunks, quantizes (per-block max-abs midtread, int8 or packed int4)
     and all_to_all's them; every device dequantizes + sums its own chunk.
  phase 2 (all-gather equivalent): the reduced chunk is re-quantized and
     all_gather'd; devices dequantize into the full result.

Wire bytes per device drop from ~2 * 2 * N (bf16 ring all-reduce) to
~2 * N * bits/8 — 4x at int8, 8x at int4 — visible in the lowered HLO as
int8/uint8 collective operand types (this is what the roofline's collective
term reads).

Quantization-noise accounting follows the paper's modified SE: a P-summand
fusion at per-block bin width Delta_b injects variance sum_p Delta_{b,p}^2/12;
``quant_noise_var`` reports it so training-side controllers (BT analogue) can
pick bit widths against a noise budget. Error feedback (residual carry) is
provided for optimizer integration.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


__all__ = ["QuantConfig", "quantize_blocks", "dequantize_blocks",
           "pack_int4", "unpack_int4", "compressed_psum", "quant_noise_var",
           "compressed_grad_transform"]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    bits: int = 8             # 8 or 4 (packed)
    block: int = 512          # elements per scale block
    stochastic: bool = False  # stochastic rounding (decode-side unbiasedness)

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1


def _pad_to(x, k):
    r = (-x.shape[-1]) % k
    if r:
        x = jnp.concatenate([x, jnp.zeros(x.shape[:-1] + (r,), x.dtype)], -1)
    return x, r


def quantize_blocks(x, qc: QuantConfig, key=None):
    """x (..., N) -> (q int8 (..., N), scale bf16 (..., N/block)).

    Midtread symmetric: q = round(x / Delta), Delta = max|block| / qmax.
    """
    orig = x.shape[-1]
    x, _ = _pad_to(x.astype(jnp.float32), qc.block)
    blocks = x.reshape(*x.shape[:-1], -1, qc.block)
    amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    # round the scale to its bf16 wire format *before* use so the encoder and
    # decoder agree exactly (otherwise the scale mismatch adds ~0.4% * q error);
    # the 1.004 nudge makes the bf16 rounding an upper bound, so the max
    # element never clips and |err| <= Delta/2 holds exactly
    delta = jnp.maximum(amax / qc.qmax, 1e-30) * 1.004
    delta = delta.astype(jnp.bfloat16).astype(jnp.float32)
    scaled = blocks / delta
    if qc.stochastic and key is not None:
        noise = jax.random.uniform(key, scaled.shape) - 0.5
        q = jnp.floor(scaled + 0.5 + noise)
    else:
        q = jnp.round(scaled)
    q = jnp.clip(q, -qc.qmax, qc.qmax).astype(jnp.int8)
    # returned q keeps the block padding; dequantize_blocks(orig_len=...)
    # truncates back (orig recorded by callers)
    return q.reshape(*x.shape), delta[..., 0].astype(jnp.bfloat16)


def dequantize_blocks(q, scale, qc: QuantConfig, orig_len: int | None = None):
    n = q.shape[-1]
    blocks = q.reshape(*q.shape[:-1], -1, qc.block).astype(jnp.float32)
    out = blocks * scale.astype(jnp.float32)[..., None]
    out = out.reshape(*q.shape[:-1], n)
    if orig_len is not None and orig_len != n:
        out = out[..., :orig_len]
    return out


def pack_int4(q):
    """int8 values in [-7, 7] -> packed uint8, two nibbles per byte.

    Pairing via reshape (not strided slices): strided-slice partitioning
    inside a manual-axis shard_map trips an XLA SPMD CHECK at 512 devices.
    """
    u = (q.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    pairs = u.reshape(*u.shape[:-1], u.shape[-1] // 2, 2)
    return pairs[..., 0] | (pairs[..., 1] << 4)


def unpack_int4(p):
    lo = (p & 0xF).astype(jnp.int8)
    hi = ((p >> 4) & 0xF).astype(jnp.int8)
    # sign-extend 4-bit two's complement
    sext = lambda v: jnp.where(v > 7, v - 16, v)
    out = jnp.stack([sext(lo), sext(hi)], axis=-1)
    return out.reshape(*p.shape[:-1], p.shape[-1] * 2)


def quant_noise_var(scale, qc: QuantConfig):
    """Per-element quantization noise variance Delta^2/12 (paper Sec. 3.2)."""
    d = scale.astype(jnp.float32)
    return jnp.mean(d * d) / 12.0


def _wire_encode(q, qc: QuantConfig):
    return pack_int4(q) if qc.bits == 4 else q


def _wire_decode(w, qc: QuantConfig):
    return unpack_int4(w) if qc.bits == 4 else w


def compressed_psum(x, axis_name: str, qc: QuantConfig = QuantConfig()):
    """Sum ``x`` over ``axis_name`` with lossy-compressed transport.

    Must run inside shard_map with ``axis_name`` manual. Exact semantics of
    psum up to quantization error; returns (sum, injected_noise_var) where
    injected_noise_var follows the paper's P * sigma_Q^2 accounting.
    """
    n = lax.axis_size(axis_name)
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    # chunk so every device owns flat_len/n contiguous elements
    flat, _ = _pad_to(flat[None], n * qc.block * 2)
    flat = flat[0]
    chunks = flat.reshape(n, -1)

    # phase 1: quantize per-destination chunks, exchange, reduce own chunk
    q, scale = quantize_blocks(chunks, qc)
    noise1 = quant_noise_var(scale, qc) * n       # n summands -> n * sigma_Q^2
    wire = _wire_encode(q, qc)
    wire_r = lax.all_to_all(wire, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
    scale_r = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0,
                             tiled=True)
    q_r = _wire_decode(wire_r, qc)
    own = dequantize_blocks(q_r, scale_r, qc).sum(axis=0)   # (chunk,)

    # phase 2: re-quantize the reduced chunk, gather everyone's
    q2, scale2 = quantize_blocks(own[None], qc)
    noise2 = quant_noise_var(scale2, qc)
    wire2 = _wire_encode(q2[0], qc)
    wire_g = lax.all_gather(wire2, axis_name, axis=0, tiled=False)
    scale_g = lax.all_gather(scale2, axis_name, axis=0, tiled=False)
    q_g = _wire_decode(wire_g, qc)
    full = dequantize_blocks(q_g, scale_g.reshape(q_g.shape[0], -1), qc)
    out = full.reshape(-1)[: x.size].reshape(shape)
    return out.astype(x.dtype), noise1 + noise2


def compressed_grad_transform(grads, residual, axis_name: str,
                              qc: QuantConfig = QuantConfig()):
    """Per-leaf compressed psum with error feedback.

    grads: pytree of *local* (unreduced over axis_name) gradients.
    residual: same-structure pytree carrying quantization residue (error
    feedback keeps the compression bias from accumulating across steps —
    beyond-paper, standard in gradient-compression practice).
    Returns (reduced grads, new residual, total noise var).
    """
    leaves, treedef = jax.tree.flatten(grads)
    res_leaves = jax.tree.leaves(residual)
    out, new_res, noise = [], [], jnp.zeros(())
    for g, r in zip(leaves, res_leaves):
        g_fb = g.astype(jnp.float32) + r.astype(jnp.float32)
        red, nv = compressed_psum(g_fb, axis_name, qc)
        # residual = what compression lost locally (recomputed against the
        # locally-quantized contribution, cheap proxy: requantize g_fb)
        q, s = quantize_blocks(g_fb.reshape(1, -1), qc)
        deq = dequantize_blocks(q, s, qc, orig_len=g_fb.size).reshape(g.shape)
        new_res.append((g_fb - deq).astype(r.dtype))
        out.append(red.astype(g.dtype))
        noise = noise + nv
    return (jax.tree.unflatten(treedef, out),
            jax.tree.unflatten(treedef, new_res), noise)
