"""Pure-jnp oracles for the fused AMP local-computation kernels.

``amp_local_ref`` is the original single-processor LC oracle;
``amp_local_ref_grid`` is the batched-grid counterpart (the whole
(P, M/P, N) shard stack in one call, sigma2_hat sum-of-squares fused) and
doubles as the engine's compiled CPU path. The column-layout oracles
mirror ``col.py``'s fused kernels: ``col_residual_ref`` (r_p = A_p x_p)
and ``col_inner_step_ref`` (message + denoise + optional residual
update — one C-MP-AMP inner iteration).

Both contractions are single ``dot_general``s over the whole stack (the
processor axis a batch dim of one op, not a ``vmap`` of P small ops) with
the elementwise tails and the sum-of-squares fused behind one jit — this
is the "batched grid" on CPU, and what ``benchmarks/bench_kernels.py``
measures against the per-processor ``vmap`` baseline.

Every contraction runs at ``Precision.HIGHEST``: on TPU the default f32
matmul rounds its operands to bf16, and these oracles are the f32
reference the kernels are held to on the chip.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_einsum = partial(jnp.einsum, precision=_HI)


def amp_local_ref(a, x, y, z, onsager, n_proc: int):
    """Paper Sec. 3.1 LC step for one processor:

        z' = y - A x + onsager * z
        f  = x / P + A^T z'

    a: (M, N); x: (N,); y, z: (M,). Returns (z', f)."""
    z_new = y - jnp.dot(a, x, precision=_HI) + onsager * z
    f = x / n_proc + jnp.dot(a.T, z_new, precision=_HI)
    return z_new, f


def amp_local_ref_grid(a_p, x, y_p, z_p, onsager, n_proc: int):
    """Batched-grid LC oracle over the full processor stack.

    a_p (P, Mp, N) — may be stored in bf16 (``EngineConfig.a_dtype``); the
    contraction promotes to f32, modelling bf16 HBM streaming with f32
    accumulation. x (N,); y_p, z_p (P, Mp). Returns
    ``(z_new (P, Mp), f_p (P, N), ss ())`` with ``ss = sum(z_new**2)``
    (the sigma2_hat numerator, fused exactly like the Pallas kernels).
    """
    a32 = a_p.astype(jnp.float32)
    z_new = y_p - _einsum("pmn,n->pm", a32, x) + onsager * z_p
    f_p = x / n_proc + _einsum("pmn,pm->pn", a32, z_new)
    return z_new, f_p, jnp.sum(z_new * z_new)


def col_residual_ref(a_cp, x):
    """Column-layout residual contributions r_p = A_p x_p.

    a_cp (P, M, Np) column shards; x (P, Np). Returns (P, M)."""
    return _einsum("pmn,pn->pm", a_cp.astype(jnp.float32), x)


def col_inner_step_ref(a_cp, x, x0, z_p, g, n_mask, m_eff,
                       eps, mu_s, sigma_s2, update_z: bool):
    """One C-MP-AMP inner iteration (engine ``_col_inner`` body), oracle.

    Per processor p (a_cp (P, M, Np), x/x0 (P, Np), z_p (P, M), g (M,)):

        s2_p = ||z_p||^2 / m_eff
        f_p  = x_p + A_p^T z_p
        x'   = eta(f_p; s2_p) * mask,  c_p = sum(eta' * mask) / m_eff
        z'   = g - A_p (x' - x0) + c_p z_p        (only when ``update_z``)

    Returns ``(x_new, c_p, z_new)`` with ``z_new = z_p`` when the update
    is skipped (the final inner iteration: ``z_p`` is the residual that
    fed the denoise, which is what the Onsager boundary carry needs).
    """
    from .col import eta_bg_and_deriv

    a32 = a_cp.astype(jnp.float32)
    s2_p = jnp.sum(z_p * z_p, axis=-1, keepdims=True) / m_eff
    f_p = x + _einsum("pmn,pm->pn", a32, z_p)
    val, deriv = eta_bg_and_deriv(f_p, s2_p, eps, mu_s, sigma_s2)
    if n_mask is not None:
        val = val * n_mask
        deriv = deriv * n_mask
    c_p = jnp.sum(deriv, axis=-1) / m_eff
    if update_z:
        z_new = (g[None, :] - _einsum("pmn,pn->pm", a32, val - x0)
                 + c_p[:, None] * z_p)
    else:
        z_new = z_p
    return val, c_p, z_new


def amp_local_ref_vmap(a_p, x, y_p, z_p, onsager, n_proc: int):
    """The pre-v2 engine path: per-processor LC ``vmap``ed over P, the
    sum-of-squares reduction separate. Kept as the benchmark baseline
    (``bench_kernels.py``) — not used by the engine."""
    z_new, f_p = jax.vmap(
        lambda ap, yp, zp: amp_local_ref(ap, x, yp, zp, onsager, n_proc)
    )(a_p.astype(jnp.float32), y_p, z_p)
    return z_new, f_p, jnp.sum(z_new * z_new)
