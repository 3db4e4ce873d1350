"""Centralized Bayesian AMP (paper Sec. 2, eqs. 1-3).

    f_t     = x_t + A^T z_t
    x_{t+1} = eta_t(f_t)
    z_{t+1} = y - A x_{t+1} + (N/M) * mean(eta_t'(f_t)) * z_t

The channel variance fed to the conditional-mean denoiser is the standard
plug-in estimate  sigma_hat_t^2 = ||z_t||^2 / M  [Bayati-Montanari; paper
Sec. 3.3], making the solver fully data-driven.

This is the P=1, lossless-fusion frontend of the unified ``core/engine.py``
solver: with one processor the LC/GC split reduces exactly to the
centralized recursion above (same iterates, bit-for-bit math), so the whole
solve is one scan-compiled engine call. It always runs the engine's jnp
path at ``Precision.HIGHEST``, never the Pallas kernels: it is the plain
f32 reference the kernels are checked against on the chip.
``amp_iteration`` is kept as the public single-step API.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .denoisers import BernoulliGauss, eta
from .engine import AmpEngine, EngineConfig, ExactFusion

__all__ = ["AMPState", "amp_iteration", "amp_solve", "sample_problem"]

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class AMPTrace:
    x: np.ndarray                # final estimate (N,)
    sigma2_hat: np.ndarray       # per-iteration plug-in variance (T,)
    mse: np.ndarray | None       # per-iteration MSE vs ground truth (T,) if s0 given


class AMPState(dict):
    """Carry pytree for lax.scan: {'x': (N,), 'z': (M,)}."""


@partial(jax.jit, static_argnames=("prior",))
def amp_iteration(x, z, y, a_mat, prior: BernoulliGauss):
    """One centralized AMP iteration. Returns (x_new, z_new, sigma2_hat)."""
    m = y.shape[0]
    n = x.shape[0]
    f = x + jnp.dot(a_mat.T, z, precision=_HI)
    sigma2_hat = jnp.sum(z * z) / m
    eta_fn = lambda v: eta(v, sigma2_hat, prior, xp=jnp)
    x_new = eta_fn(f)
    eta_mean_deriv = jax.grad(lambda v: jnp.sum(eta_fn(v)))(f).mean()
    z_new = (y - jnp.dot(a_mat, x_new, precision=_HI)
             + (n / m) * eta_mean_deriv * z)
    return x_new, z_new, sigma2_hat


def amp_solve(y, a_mat, prior: BernoulliGauss, n_iter: int,
              s0: np.ndarray | None = None) -> AMPTrace:
    """Run centralized AMP for ``n_iter`` iterations (one engine scan)."""
    engine = AmpEngine(
        prior, EngineConfig(n_proc=1, n_iter=n_iter, use_kernel=False,
                            collect_symbols=False,
                            collect_xs=s0 is not None),
        ExactFusion())
    trace = engine.solve(y, a_mat)
    mse = trace.mse(s0) if s0 is not None else None
    return AMPTrace(x=trace.x, sigma2_hat=trace.sigma2_hat, mse=mse)


def sample_problem(key, n: int, m: int, prior: BernoulliGauss, sigma_e2: float):
    """Draw (s0, A, y) per the paper's model: A_ij ~ N(0, 1/M), e ~ N(0, sigma_e^2)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    support = jax.random.bernoulli(k1, prior.eps, (n,))
    gauss = prior.mu_s + prior.sigma_s * jax.random.normal(k2, (n,))
    s0 = jnp.where(support, gauss, 0.0)
    a = jax.random.normal(k3, (m, n)) / jnp.sqrt(m * 1.0)
    e = jnp.sqrt(sigma_e2) * jax.random.normal(k4, (m,))
    y = jnp.dot(a, s0, precision=_HI) + e
    return np.asarray(s0), np.asarray(a), np.asarray(y)
