"""Sensing problems drawn from ``--seed``: the paper's measurement model.

    y = A s0 + e,  A_ij ~ N(0, 1/M),  s0 ~ Bernoulli-Gauss(eps),
    e ~ N(0, sigma_e^2),  SNR = 10 log10(rho / sigma_e^2),
    rho = E[s0^2] / kappa,  kappa = M / N.

One jitted call on the device draws every sensor's A and a pool of
signals per sensor; on one chip the arrays then come to the host once,
since the service takes host arrays. On a mesh of several chips each A
stays on the devices, split by rows (the M axis) over the mesh, and only
the signals come to the host: with ``jax_threefry_partitionable`` (the
default) the sharded draw gives every A and s0 bit for bit as on one
device. y is the same product over fewer rows per device, so it can
differ from the one-device draw by float32 rounding of the sum.
"""
from __future__ import annotations

import functools

import numpy as np


def sensor_eps(cfg: dict) -> list:
    """Prior sparsity of each sensor: the configuration's eps values in
    turn."""
    eps = cfg["eps"]
    return [float(eps[i % len(eps)]) for i in range(cfg["sensors"])]


def sensor_iters(cfg: dict) -> list:
    """Iteration budget T of each sensor, the configuration's T for its eps."""
    t_of = dict(zip(map(float, cfg["eps"]), cfg["n_iter"]))
    return [int(t_of[e]) for e in sensor_eps(cfg)]


def noise_var(cfg: dict, eps: float) -> float:
    """sigma_e^2 for the configuration's SNR at prior sparsity ``eps``."""
    kappa = cfg["m"] / cfg["n"]
    second = eps * (cfg["mu_s"] ** 2 + cfg["sigma_s"] ** 2)
    return (second / kappa) / 10.0 ** (cfg["snr_db"] / 10.0)


def key_for(seed: int, stream: int):
    """A PRNG key from a seed of any size (64 bits are used)."""
    import jax
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, seed >> 32)
    return jax.random.fold_in(k, stream)


def _draw_body(s: int, k: int, m: int, n: int):
    import jax
    import jax.numpy as jnp

    def draw(key, eps, mu, sigma, sig_e):
        ka, ks, kg, ke = jax.random.split(key, 4)
        a = jax.random.normal(ka, (s, m, n), jnp.float32) / jnp.sqrt(
            jnp.float32(m))
        support = jax.random.uniform(ks, (s, k, n)) < eps[:, None, None]
        gauss = mu + sigma * jax.random.normal(kg, (s, k, n), jnp.float32)
        s0 = jnp.where(support, gauss, 0.0)
        e = sig_e[:, None, None] * jax.random.normal(ke, (s, k, m),
                                                     jnp.float32)
        y = jnp.einsum("smn,skn->skm", a, s0,
                       precision=jax.lax.Precision.HIGHEST) + e
        return a, s0, y

    return draw


@functools.lru_cache(maxsize=None)
def _draw_fn(s: int, k: int, m: int, n: int):
    import jax
    return jax.jit(_draw_body(s, k, m, n))


@functools.lru_cache(maxsize=None)
def _sharded_draw_fn(s: int, k: int, m: int, n: int, mesh):
    """The same draw with each sensor's A (M, N) its own array, its rows
    split over the mesh's one axis; s0 and y replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    draw = _draw_body(s, k, m, n)

    def draw_split(*args):
        a, s0, y = draw(*args)
        return tuple(a[i] for i in range(s)), s0, y

    rows = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0], None))
    whole = NamedSharding(mesh, PartitionSpec())
    return jax.jit(draw_split, out_shardings=((rows,) * s, whole, whole))


def draw_sensors(cfg: dict, pool: int, seed: int, stream: int = 0,
                 mesh=None) -> dict:
    """Every sensor's A and ``pool`` signals per sensor: s0 (S, pool, N)
    and y (S, pool, M), as host float32 arrays. Without ``mesh`` A is a
    host array (S, M, N); with a 1-D mesh, a tuple of S device arrays
    (M, N), each split by rows over it."""
    import jax
    import jax.numpy as jnp

    eps = sensor_eps(cfg)
    s, m, n = cfg["sensors"], cfg["m"], cfg["n"]
    sig_e = np.sqrt([noise_var(cfg, e) for e in eps]).astype(np.float32)
    args = (key_for(seed, stream), jnp.asarray(eps, jnp.float32),
            jnp.float32(cfg["mu_s"]), jnp.float32(cfg["sigma_s"]),
            jnp.asarray(sig_e))
    if mesh is not None:
        a, s0, y = _sharded_draw_fn(s, pool, m, n, mesh)(*args)
        s0, y = jax.device_get((s0, y))
        return {"a": a, "s0": s0, "y": y, "eps": eps}
    out = _draw_fn(s, pool, m, n)(*args)
    a, s0, y = jax.device_get(out)
    del out
    return {"a": a, "s0": s0, "y": y, "eps": eps}
