"""Pallas TPU kernels for the AMP local-computation (LC) step — batched
grids over the full processor stack.

The LC step is two matvecs against the same sensing-matrix shard A^p:
    z' = y - A x + b z          (contraction over N)
    f  = x/P + A^T z'           (contraction over M/P)

The processor axis P is the leading grid dimension: one launch covers the
whole (P, M/P, N) stack, and the sigma2_hat sum-of-squares reduction is
fused into the z-pass (the estimate's numerator accumulates into a scalar as
each z tile completes, so z' is never re-read from HBM for the reduction).
The request batch B enters the grid through the ``pallas_call`` vmap
batching rule, which prepends a grid axis: a ``solve_many``/``solve_het``
batch is still a single kernel launch.

A may be stored in bf16 (``EngineConfig.a_dtype``): tiles stream from HBM
at half width and are upcast to f32 in VMEM before hitting the MXU, so
accumulation precision is unchanged while HBM traffic on the dominant
operand halves. Every f32 contraction runs at ``Precision.HIGHEST``.

TPU layout rules (the (8, 128) tiling of the last two block dims):

* vectors ride lane-major as ``(P, 1, len)`` / ``(1, len)`` arrays, so a
  block's last two dims are ``(1, tile)``: the 1 equals the array dim and
  the tile is either a lane multiple (128) or the whole padded length;
* A blocks are ``(1, bm, bn)`` with ``bm`` a multiple of 8 (the whole
  padded shard) or of 128 (tiled shards, whose z' blocks must then be lane
  multiples too) and ``bn`` a multiple of 128 — ``ops.row_tiles``;
* a block is sized by bytes, not by a fixed column count: ``bn`` is the
  widest balanced split of N whose ``bm x bn`` block of A fits
  ``BLOCK_BYTES``. Each grid step carries a fixed pipeline cost (a few
  tenths of a microsecond on a v5e) beside its DMA, so a 229 KB block
  (112 x 512 f32) streams A at about half the HBM roofline where a
  ~1 MB block (112 x 2,560) amortizes it;
* scalars (the Onsager coefficient in, the sum of squares out) live in
  SMEM as ``(1, 1)`` arrays.

Grid conventions: the reduction axis is the *last* grid dim, accumulating
into the output tile with an init at step 0. Every grid step of the
z-pass adds into the same SMEM ``ss`` scalar, which is race-free only
while the grid runs sequentially: its dimension semantics are therefore
all ``"arbitrary"``. The f-pass writes disjoint (p, n) tiles and marks
those two axes ``"parallel"``. A vmapped batch axis is always
``"parallel"`` (Pallas adds it); each batch element owns its ``ss``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM = 512   # max rows of A per tile (M axis) when a shard is split
BN = 512   # N pads no further than a split into tiles of <= BN columns
BLOCK_BYTES = 2 << 20   # max bytes of one (bm, bn) A block (N axis width)

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))   # (1, k) x (m, k) -> (1, m)
_NN = (((1,), (0,)), ((), ()))   # (1, k) x (k, n) -> (1, n)
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))
_MATVEC_T = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _dot(u, a, dims):
    return jax.lax.dot_general(u, a, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _z_kernel(ons_ref, a_ref, x_ref, y_ref, z_ref, o_ref, ss_ref, *, nj):
    """o[p,m] = y[p,m] - sum_n A[p,m,n] x[n] + onsager * z[p,m];
    grid (P, Mp/bm, N/bn); ss accumulates sum(o**2) as tiles complete."""
    p, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[0] = y_ref[0] + ons_ref[0, 0] * z_ref[0]

    o_ref[0] -= _dot(x_ref[...], a_ref[0].astype(jnp.float32), _NT)

    @pl.when(j == nj - 1)
    def _reduce():
        zb = o_ref[0]
        s = jnp.sum(zb * zb)
        first = (p == 0) & (i == 0)

        @pl.when(first)
        def _first():
            ss_ref[0, 0] = s

        @pl.when(jnp.logical_not(first))
        def _acc():
            ss_ref[0, 0] += s


def _f_kernel(a_ref, z_ref, x_ref, o_ref, *, inv_p):
    """o[p,n] = x[n]/P + sum_m A[p,m,n] z'[p,m]; grid (P, N/bn, Mp/bm)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[0] = inv_p * x_ref[...]

    o_ref[0] += _dot(z_ref[0], a_ref[0].astype(jnp.float32), _NN)


@partial(jax.jit, static_argnames=("n_proc", "interpret", "bm", "bn"))
def amp_local_pallas_grid(a_p, x, y_p, z_p, onsager, n_proc: int,
                          interpret: bool = False,
                          bm: int = BM, bn: int = BN):
    """Batched-grid fused LC step over the full processor stack.

    a_p (P, Mp, N) with Mp % bm == 0 and N % bn == 0 (``ops.py`` aligns),
    f32 or bf16; x (N,); y_p, z_p (P, Mp) f32. Returns
    ``(z_new (P, Mp), f (P, N), ss ())`` with ``ss = sum(z_new**2)``.
    """
    p, mp_, n = a_p.shape
    assert mp_ % bm == 0 and n % bn == 0, (a_p.shape, bm, bn)
    ni, nj = mp_ // bm, n // bn
    ons = jnp.asarray(onsager, jnp.float32).reshape(1, 1)
    x2 = x.reshape(1, n)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vec_m = pl.BlockSpec((1, 1, bm), lambda p, i, j: (p, 0, i))

    z_new, ss = pl.pallas_call(
        partial(_z_kernel, nj=nj),
        grid=(p, ni, nj),
        in_specs=[
            smem,
            pl.BlockSpec((1, bm, bn), lambda p, i, j: (p, i, j)),
            pl.BlockSpec((1, bn), lambda p, i, j: (0, j)),
            vec_m, vec_m,
        ],
        out_specs=[vec_m, smem],
        out_shape=[
            jax.ShapeDtypeStruct((p, 1, mp_), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(ons, a_p, x2, y_p.reshape(p, 1, mp_), z_p.reshape(p, 1, mp_))

    f = pl.pallas_call(
        partial(_f_kernel, inv_p=1.0 / n_proc),
        grid=(p, nj, ni),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda p, j, i: (p, i, j)),
            pl.BlockSpec((1, 1, bm), lambda p, j, i: (p, 0, i)),
            pl.BlockSpec((1, bn), lambda p, j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda p, j, i: (p, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p, 1, n), jnp.float32),
        compiler_params=_MATVEC_T,
        interpret=interpret,
    )(a_p, z_new, x2)
    return z_new.reshape(p, mp_), f.reshape(p, n), ss[0, 0]
