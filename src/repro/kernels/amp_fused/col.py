"""Pallas kernels for the column-layout (C-MP-AMP) LC hot path.

Two kernels cover the per-round A-touching work of ``engine._col_round``
/ ``_col_inner`` (DESIGN.md §7/§8):

* ``col_residual_pallas`` — the fused residual contributions
  ``r_p = A_p x_p`` over (M, N/P) column blocks, P folded into the grid.
* ``col_inner_pallas`` — one C-MP-AMP inner iteration in a single VMEM
  pass over A_p per contraction: stage 0 streams A_p once accumulating
  the message ``f_p = x_p + A_p^T z_p`` *and* the plug-in numerator
  ``||z_p||^2``, then (at the final M tile, with f_p still in VMEM)
  applies the Bernoulli-Gauss conditional-mean denoiser and its
  derivative sum ``c_p`` in closed form; stage 1 streams A_p a second
  time for the residual update ``z_p <- g - A_p (x' - x_p^0) + c_p z_p``.
  A is read exactly twice per inner iteration — the same
  information-theoretic minimum as the row kernels — and f_p / x' / c_p
  never round-trip to HBM between the stages' tiles (f_p and ||z_p||^2
  live in VMEM/SMEM scratch, x' in a revisited output block, c_p in an
  SMEM output).

The denoiser runs *in-kernel*, so its derivative cannot come from
``jax.grad``: the closed form lives beside the prior math as
``denoisers.eta_bg_and_deriv`` (one home for the Bernoulli-Gauss
formulas; pinned against ``jax.grad`` in tests/test_kernels_col.py) and
is re-exported here for kernel callers.

Blocking: A_p tiles are (bm, Np) — the full per-processor column slice
rides in VMEM, so Np is bounded by ``COL_NP_MAX`` (the widest slice the
v5e compiler accepts in the default scoped VMEM; ``tests/test_tpu_compile``
pins it) and wider slices are refused rather than tiled. Vectors ride
lane-major (``(P, 1, len)``), scalars in SMEM: the prior parameters as a
packed (1, 4) operand ``[m_eff, eps, mu_s, sigma_s2]`` (so one compiled
kernel serves traced per-instance priors — the heterogeneous path) and
``c_p`` as a (1, P) output (2-D, so a vmapped batch axis leaves the
last two block dims whole). A may be bf16 (upcast in VMEM, f32
accumulation at ``Precision.HIGHEST``).

Dimension semantics: the residual kernel writes disjoint tiles (all
``"parallel"``). The inner kernel is all ``"arbitrary"``: stage 1 reads
what stage 0 of the same processor wrote, and every processor shares the
f/||z||^2 scratch and the whole-array SMEM ``c_p`` output, which is
race-free only while the grid runs sequentially.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .amp_fused import _NN, _NT, _dot

# Widest per-processor column slice the column kernels take: the inner
# kernel's (128, Np) f32 A tile double-buffered, plus its lane-major
# (1, Np) vectors and f_p scratch, must fit the default scoped VMEM of a
# v5e core. 79 lane tiles compile, 80 do not (tests/test_tpu_compile.py).
# Column dispatch above it raises and the router keeps such requests on
# the row layout.
COL_NP_MAX = 10_112


def check_col_width(np_: int) -> None:
    """Refuse a column slice wider than the kernels compile for."""
    if np_ > COL_NP_MAX:
        raise ValueError(
            f"column slice of {np_} signal columns per processor exceeds "
            f"COL_NP_MAX={COL_NP_MAX}, the widest the column LC kernels "
            f"compile for; use more processors or the row layout")


def eta_bg_and_deriv(f, sigma2, eps, mu_s, sigma_s2):
    """Re-export of ``denoisers.eta_bg_and_deriv`` (the single home of
    the Bernoulli-Gauss closed forms) for kernel callers. Imported
    lazily: ``core.engine`` imports this package at module load, so a
    top-level ``core.denoisers`` import here would be circular."""
    from ...core.denoisers import eta_bg_and_deriv as _impl
    return _impl(f, sigma2, eps, mu_s, sigma_s2)


def _col_r_kernel(a_ref, x_ref, o_ref):
    """o[p,m] = sum_n A[p,m,n] x[p,n]; grid (P, M/bm), full-Np tiles."""
    o_ref[0] = _dot(x_ref[0], a_ref[0].astype(jnp.float32), _NT)


@partial(jax.jit, static_argnames=("interpret", "bm"))
def col_residual_pallas(a_cp, x, interpret: bool = False, bm: int = 128):
    """r_p = A_p x_p. a_cp (P, M, Np) with M % bm == 0; x (P, Np)."""
    p, m, np_ = a_cp.shape
    assert m % bm == 0, (a_cp.shape, bm)
    check_col_width(np_)
    r = pl.pallas_call(
        _col_r_kernel,
        grid=(p, m // bm),
        in_specs=[
            pl.BlockSpec((1, bm, np_), lambda p, i: (p, i, 0)),
            pl.BlockSpec((1, 1, np_), lambda p, i: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm), lambda p, i: (p, 0, i)),
        out_shape=jax.ShapeDtypeStruct((p, 1, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a_cp, x.reshape(p, 1, np_))
    return r.reshape(p, m)


def _col_inner_kernel(par_ref, a_ref, x_ref, x0_ref, z_ref, g_ref, mask_ref,
                      xo_ref, co_ref, *rest, ni, update_z):
    """One inner iteration; grid (P, 2, M/bm) (stage axis dropped when
    ``update_z`` is False). Stage 0 accumulates f/||z||^2 over M tiles and
    denoises at the last; stage 1 writes the updated residual tiles."""
    if update_z:
        zo_ref, f_acc, ss_acc = rest
        s, i = pl.program_id(1), pl.program_id(2)
    else:
        f_acc, ss_acc = rest
        s, i = 0, pl.program_id(1)
    p = pl.program_id(0)
    a = a_ref[0].astype(jnp.float32)      # (bm, Np)

    @pl.when((s == 0) & (i == 0))
    def _init():
        f_acc[...] = x_ref[0]
        ss_acc[0] = 0.0

    @pl.when(s == 0)
    def _accumulate():
        z = z_ref[0]                       # (1, bm)
        f_acc[...] += _dot(z, a, _NN)
        ss_acc[0] += jnp.sum(z * z)

    @pl.when((s == 0) & (i == ni - 1))
    def _denoise():
        m_eff = par_ref[0, 0]
        s2 = jnp.maximum(ss_acc[0] / m_eff, 1e-30)
        val, deriv = eta_bg_and_deriv(f_acc[...], s2, par_ref[0, 1],
                                      par_ref[0, 2], par_ref[0, 3])
        mask = mask_ref[...]
        xo_ref[0] = val * mask
        co_ref[0, p] = jnp.sum(deriv * mask) / m_eff

    if update_z:
        @pl.when(s == 1)
        def _residual():
            dx = xo_ref[0] - x0_ref[0]     # (1, Np)
            zo_ref[0] = (g_ref[...] - _dot(dx, a, _NT)
                         + co_ref[0, p] * z_ref[0])


@partial(jax.jit, static_argnames=("update_z", "interpret", "bm"))
def col_inner_pallas(a_cp, x, x0, z_p, g, n_mask, m_eff, eps, mu_s, sigma_s2,
                     update_z: bool, interpret: bool = False, bm: int = 128):
    """Fused C-MP-AMP inner iteration (see module docstring).

    a_cp (P, M, Np), M % bm == 0; x, x0 (P, Np); z_p (P, M); g (M,);
    n_mask (Np,). Scalars may be traced. Returns ``(x_new (P, Np),
    c_p (P,), z_new (P, M))``; ``z_new`` is ``z_p`` unchanged when
    ``update_z`` is False (the final inner iteration keeps the residual
    that fed the denoise — the Onsager boundary carry).
    """
    p, m, np_ = a_cp.shape
    assert m % bm == 0, (a_cp.shape, bm)
    check_col_width(np_)
    ni = m // bm
    par = jnp.stack([jnp.asarray(v, jnp.float32).reshape(())
                     for v in (m_eff, eps, mu_s, sigma_s2)]).reshape(1, 4)

    if update_z:
        ix = lambda fn: fn                 # index maps take (p, s, i)
        grid = (p, 2, ni)
    else:
        # no stage axis: wrap the 3-arg index maps with s pinned to 0
        ix = lambda fn: (lambda p, i, fn=fn: fn(p, 0, i))
        grid = (p, ni)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vec_n = pl.BlockSpec((1, 1, np_), ix(lambda p, s, i: (p, 0, 0)))
    in_specs = [
        smem,
        pl.BlockSpec((1, bm, np_), ix(lambda p, s, i: (p, i, 0))),
        vec_n, vec_n,
        pl.BlockSpec((1, 1, bm), ix(lambda p, s, i: (p, 0, i))),
        pl.BlockSpec((1, bm), ix(lambda p, s, i: (0, i))),
        pl.BlockSpec((1, np_), ix(lambda p, s, i: (0, 0))),
    ]
    out_specs = [vec_n, smem]
    out_shape = [
        jax.ShapeDtypeStruct((p, 1, np_), jnp.float32),   # x_new
        jax.ShapeDtypeStruct((1, p), jnp.float32),        # c_p
    ]
    if update_z:
        out_specs.append(pl.BlockSpec((1, 1, bm), lambda p, s, i: (p, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((p, 1, m), jnp.float32))

    outs = pl.pallas_call(
        partial(_col_inner_kernel, ni=ni, update_z=update_z),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, np_), jnp.float32),   # f_p
                        pltpu.SMEM((1,), jnp.float32)],      # ||z_p||^2
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(par, a_cp, x.reshape(p, 1, np_), x0.reshape(p, 1, np_),
      z_p.reshape(p, 1, m), g.reshape(1, m), n_mask.reshape(1, np_))
    x_new, c_p = outs[0].reshape(p, np_), outs[1].reshape(p)
    z_new = outs[2].reshape(p, m) if update_z else z_p
    return x_new, c_p, z_new
