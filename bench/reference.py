"""The plain reference: centralized Bayesian AMP in float32.

    z_t     = y - A x_t + (1/M) sum(eta'(f_{t-1})) z_{t-1}   (z_0 = y)
    f_t     = x_t + A^T z_t
    x_{t+1} = eta(f_t; ||z_t||^2 / M)

with the Bernoulli-Gauss conditional-mean denoiser eta in closed form.
It imports nothing of the system under test. Row MP-AMP with lossless
fusion, and column MP-AMP with one inner iteration per round, compute
exactly this recursion, so one reference serves both layouts.

``solve`` runs a batch of problems, each with its own A. ``solve_shared``
runs the lanes of one A, the signals of one sensor: each iteration is
then two matrix products, and A may be split by rows over a mesh, jit's
partitioner putting in whatever exchange the products need.

``precision`` is how the two matrix-vector products are computed:
``"highest"`` is float32 (``Precision.HIGHEST``); ``"bf16x3"`` is the
three-pass bfloat16 product, ``Precision.HIGH`` on a TPU. The latter is
the control: the reference one precision step below what the
configuration states. Other backends compute every float32 product in
float32 whatever the precision flag says, so there the three passes are
written out, splitting each operand by masking its low 16 bits (a split
that no compiler may simplify away).
"""
from __future__ import annotations

import functools
import math

PRECISIONS = ("highest", "bf16x3")


def _split_bf16(v):
    """v = hi + lo with hi exactly a bfloat16 (v with its low 16 bits
    cleared) and lo rounded to bfloat16."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)


def _matvec(a, v, spec: str, precision: str):
    """einsum(spec, a, v) in float32 or in three bfloat16 passes; the
    shared-A recursion passes matrices for v."""
    import jax
    import jax.numpy as jnp
    if precision == "highest":
        return jnp.einsum(spec, a, v, precision=jax.lax.Precision.HIGHEST)
    if jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, v, precision=jax.lax.Precision.HIGH)
    a_hi, a_lo = _split_bf16(a)
    v_hi, v_lo = _split_bf16(v)
    dot = functools.partial(jnp.einsum, spec,
                            preferred_element_type=jnp.float32)
    return dot(a_hi, v_hi) + dot(a_hi, v_lo) + dot(a_lo, v_hi)


def eta_bg(f, s2, eps, mu, var_s):
    """Bernoulli-Gauss conditional mean E[S | S + sqrt(s2) Z = f] and its
    derivative in f, elementwise."""
    import jax.numpy as jnp
    v1 = var_s + s2
    log_slab = -0.5 * (f - mu) ** 2 / v1 - 0.5 * jnp.log(2 * math.pi * v1)
    log_spike = -0.5 * f * f / s2 - 0.5 * jnp.log(2 * math.pi * s2)
    logit = jnp.log(eps) - jnp.log1p(-eps) + log_slab - log_spike
    pi = 0.5 * (1.0 + jnp.tanh(0.5 * logit))
    cm = (mu * s2 + f * var_s) / v1
    d_logit = f / s2 - (f - mu) / v1
    return pi * cm, pi * (1.0 - pi) * d_logit * cm + pi * var_s / v1


@functools.lru_cache(maxsize=None)
def _solver(n_iter: int, precision: str):
    import jax
    import jax.numpy as jnp

    def one(a, y, eps, mu, var_s):
        m, n = a.shape

        def step(carry, _):
            x, z, ons = carry
            z = y - _matvec(a, x, "mn,n->m", precision) + ons * z
            s2 = jnp.maximum(jnp.sum(z * z) / m, 1e-30)
            f = x + _matvec(a, z, "mn,m->n", precision)
            x, d = eta_bg(f, s2, eps, mu, var_s)
            return (x, z, jnp.sum(d) / m), None

        init = (jnp.zeros(n, jnp.float32), jnp.zeros(m, jnp.float32),
                jnp.float32(0.0))
        (x, _, _), _ = jax.lax.scan(step, init, None, length=n_iter)
        return x

    return jax.jit(jax.vmap(one, in_axes=(0, 0, 0, None, None)))


def solve(a, y, eps, n_iter: int, mu: float = 0.0, sigma: float = 1.0,
          precision: str = "highest"):
    """Centralized AMP over a batch: a (L, M, N), y (L, M), eps (L,);
    returns x (L, N) after ``n_iter`` iterations, on the default device."""
    import jax.numpy as jnp
    assert precision in PRECISIONS, precision
    fn = _solver(int(n_iter), precision)
    return fn(jnp.asarray(a, jnp.float32), jnp.asarray(y, jnp.float32),
              jnp.asarray(eps, jnp.float32), jnp.float32(mu),
              jnp.float32(sigma * sigma))


@functools.lru_cache(maxsize=None)
def _shared_solver(n_iter: int, precision: str):
    import jax
    import jax.numpy as jnp

    def lanes(a, y, eps, mu, var_s):
        m, n = a.shape
        el = y.shape[0]

        def step(carry, _):
            x, z, ons = carry
            z = y - _matvec(a, x, "mn,ln->lm", precision) + ons[:, None] * z
            s2 = jnp.maximum(jnp.sum(z * z, axis=1) / m, 1e-30)
            f = x + _matvec(a, z, "mn,lm->ln", precision)
            x, d = eta_bg(f, s2[:, None], eps, mu, var_s)
            return (x, z, jnp.sum(d, axis=1) / m), None

        init = (jnp.zeros((el, n), jnp.float32),
                jnp.zeros((el, m), jnp.float32), jnp.zeros(el, jnp.float32))
        (x, _, _), _ = jax.lax.scan(step, init, None, length=n_iter)
        return x

    return jax.jit(lanes)


def solve_shared(a, y, eps, n_iter: int, mu: float = 0.0,
                 sigma: float = 1.0, precision: str = "highest"):
    """Centralized AMP over the lanes of one A: a (M, N), a device array
    that may be split by rows over a mesh, y (L, M), eps the prior's
    sparsity; returns x (L, N) after ``n_iter`` iterations, the same
    recursion as ``solve``."""
    import jax.numpy as jnp
    assert precision in PRECISIONS, precision
    fn = _shared_solver(int(n_iter), precision)
    return fn(a, jnp.asarray(y, jnp.float32), jnp.float32(eps),
              jnp.float32(mu), jnp.float32(sigma * sigma))
