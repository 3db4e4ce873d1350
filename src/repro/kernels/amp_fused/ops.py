"""Dispatch + tile alignment for the fused AMP LC kernel suite.

The engine calls the ``*_grid`` entry points with *pre-aligned* operands:
padding of the (M, N)-sized sensing operand happens once at solve entry
(``pad_row_shards`` / ``pad_col_shards`` — host-side numpy for the
homogeneous paths, one jnp pad outside the scan for the heterogeneous
wrappers), never inside the scanned iteration body (tests assert the
jaxpr). Zero-padding is exact end-to-end: padded rows/columns of A are
zero, so residuals/messages in the padded region are identically zero and
every transport maps 0 -> 0.

Tile sizes adapt to the problem (``row_tiles`` / ``col_tiles``): a shard
of at most ``BM`` rows is one M tile padded to the 8-row sublane quantum
(its lane-major z' block then equals the whole padded length), so
serving-sized shards such as Mp = 75 or 100 pad to 80 / 104 rather than
to a lane multiple; taller shards split into 128-row-aligned tiles. N
splits into the fewest balanced 128-column-aligned tiles whose A block
fits ``BLOCK_BYTES`` (in A's own dtype), padding N no further than tiles
of at most ``BN`` would: the grid steps are few and large, because each
step costs a fixed few tenths of a microsecond beside its DMA. The tiles
depend on A's dtype, so a stack is padded for the dtype the kernel will
stream (``pad_row_shards(..., a_dtype=)``).

``amp_local_step`` keeps the single-shard signature (pads per call) for
per-op tests and external callers; the engine does not use it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .amp_fused import BLOCK_BYTES, BM, BN, amp_local_pallas_grid
from .col import col_inner_pallas, col_residual_pallas, eta_bg_and_deriv
from .ref import (amp_local_ref, amp_local_ref_grid, col_inner_step_ref,
                  col_residual_ref)

__all__ = [
    "amp_local_step", "amp_local_grid", "col_residual", "col_inner_step",
    "row_tiles", "col_tiles", "pad_row_shards", "pad_col_shards",
    "eta_bg_and_deriv",
]


def _round_up(v: int, q: int) -> int:
    return -(-v // q) * q


def _balanced_tile(dim: int, full: int, quantum: int) -> int:
    """Largest-tile-<= ``full`` split of ``dim`` into near-equal
    ``quantum``-aligned tiles: k = ceil(dim/full) tiles of
    round_up(dim/k, quantum). Caps padding waste at quantum-1 rows per
    tile instead of up to full-1 (e.g. Mp=150 pads to 160, not 256)."""
    dim = max(dim, 1)
    k = -(-dim // full)
    return _round_up(-(-dim // k), quantum)


def _m_tile(m: int, full: int) -> int:
    """M tile for a lane-major z'/r block: the whole shard (8-row quantum)
    when it fits one tile of ``full`` rows, else balanced 128-row tiles."""
    if m <= full:
        return _round_up(max(m, 1), 8)
    return _balanced_tile(m, full, 128)


def row_tiles(mp: int, n: int, a_bytes: int = 4) -> tuple[int, int]:
    """(bm, bn) for a (P, Mp, N) row-shard stack of ``a_bytes``-wide A.

    bm: one M tile up to ``BM`` rows (8-row quantum), balanced 128-row
    tiles beyond. bn: the fewest balanced 128-column tiles whose
    ``bm x bn x a_bytes`` block fits ``BLOCK_BYTES``, among the splits
    that pad N no further than balanced tiles of at most ``BN`` columns
    (so a wider block never grows the padded stack). Fewer, larger grid
    steps amortize each step's fixed cost: at the paper's bucket (bm 112,
    N 10,240, f32) four 2,560-column tiles instead of twenty of 512.
    """
    bm = _m_tile(mp, BM)
    n = max(n, 1)
    limit = _round_up(n, _balanced_tile(n, BN, 128))
    widest = max(128, BLOCK_BYTES // (bm * a_bytes) // 128 * 128)
    k = -(-n // widest)
    while k * _round_up(-(-n // k), 128) > limit:
        k += 1
    return bm, _round_up(-(-n // k), 128)


def col_tiles(m: int) -> int:
    """bm for a (P, M, Np) column-shard stack (Np rides untiled, so the
    (bm, Np) A tile stays at 128 rows to bound VMEM)."""
    return _m_tile(m, 128)


def pad_row_shards(a_p, y_p, a_dtype=None):
    """Align a (..., P, Mp, N) row-shard stack (+ matching y, or None) to
    the kernel tiles of ``a_dtype`` (default: ``a_p``'s own; pass the
    dtype the kernel will stream when A is cast after padding) with zero
    padding. Works on numpy or jax arrays; no-op (returns the inputs
    unchanged) when already aligned."""
    mp_, n = a_p.shape[-2], a_p.shape[-1]
    a_bytes = np.dtype(a_p.dtype if a_dtype is None else a_dtype).itemsize
    bm, bn = row_tiles(mp_, n, a_bytes)
    dm, dn = _round_up(mp_, bm) - mp_, _round_up(n, bn) - n
    if dm == 0 and dn == 0:
        return a_p, y_p
    xp = np if isinstance(a_p, np.ndarray) else jnp
    nd = a_p.ndim
    a_p = xp.pad(a_p, [(0, 0)] * (nd - 2) + [(0, dm), (0, dn)])
    if y_p is not None:
        y_p = xp.pad(y_p, [(0, 0)] * (y_p.ndim - 1) + [(0, dm)])
    return a_p, y_p


def pad_col_shards(a_cp, y):
    """Align a (..., P, M, Np) column-shard stack (+ shared y) to kernel
    tiles: M is zero-padded to the tile multiple, Np rides untiled."""
    m = a_cp.shape[-2]
    dm = _round_up(m, col_tiles(m)) - m
    if dm == 0:
        return a_cp, y
    xp = np if isinstance(a_cp, np.ndarray) else jnp
    nd = a_cp.ndim
    a_cp = xp.pad(a_cp, [(0, 0)] * (nd - 2) + [(0, dm), (0, 0)])
    y = xp.pad(y, [(0, 0)] * (y.ndim - 1) + [(0, dm)])
    return a_cp, y


def amp_local_grid(a_p, x, y_p, z_p, onsager, n_proc: int,
                   use_pallas: bool | None = None, interpret: bool = False):
    """Batched-grid fused LC step over the whole (P, Mp, N) shard stack.

    Returns ``(z_new (P, Mp), f_p (P, N), ss ())`` — ``ss`` the fused
    sigma2_hat numerator ``sum(z_new**2)``. Pallas path requires
    tile-aligned shards (``pad_row_shards``); x must match N. A may be
    bf16 (upcast in VMEM / promoted by the reference einsum).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return amp_local_ref_grid(a_p, x, y_p, z_p, onsager, n_proc)
    bm, bn = row_tiles(a_p.shape[1], a_p.shape[2], a_p.dtype.itemsize)
    return amp_local_pallas_grid(a_p, x, y_p, z_p, onsager, n_proc,
                                 interpret=interpret, bm=bm, bn=bn)


def col_residual(a_cp, x, use_pallas: bool | None = None,
                 interpret: bool = False):
    """Column-layout residual contributions ``r_p = A_p x_p`` (P, M)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return col_residual_ref(a_cp, x)
    return col_residual_pallas(a_cp, x, interpret=interpret,
                               bm=col_tiles(a_cp.shape[1]))


def col_inner_step(a_cp, x, x0, z_p, g, n_mask, m_eff, eps, mu_s, sigma_s2,
                   update_z: bool, use_pallas: bool | None = None,
                   interpret: bool = False):
    """One fused C-MP-AMP inner iteration (message + denoise + optional
    residual update); see ``col.col_inner_pallas``. ``n_mask`` is a
    (Np,) 0/1 mask of real columns (pass all-ones when unpadded)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return col_inner_step_ref(a_cp, x, x0, z_p, g, n_mask, m_eff,
                                  eps, mu_s, sigma_s2, update_z)
    return col_inner_pallas(a_cp, x, x0, z_p, g, n_mask, m_eff, eps, mu_s,
                            sigma_s2, update_z, interpret=interpret,
                            bm=col_tiles(a_cp.shape[1]))


def amp_local_step(a, x, y, z, onsager, n_proc: int,
                   use_pallas: bool | None = None, interpret: bool = False):
    """Fused z'/f computation for one processor's LC step (v1 signature:
    pads per call, single (M, N) shard)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return amp_local_ref(a, x, y, z, onsager, n_proc)
    m, n = a.shape
    ap, yp = pad_row_shards(a[None], y[None])
    xp_ = jnp.pad(x, (0, ap.shape[2] - n))
    zp = jnp.pad(z, (0, ap.shape[1] - m))[None]
    bm, bn = row_tiles(ap.shape[1], ap.shape[2], ap.dtype.itemsize)
    z_new, f, _ = amp_local_pallas_grid(jnp.asarray(ap), xp_,
                                        jnp.asarray(yp), zp, onsager, n_proc,
                                        interpret=interpret, bm=bm, bn=bn)
    # padded x rows contribute x/P to padded f entries only; slice them away
    return z_new[0, :m], f[0, :n]
