"""PRK PIC GEOMETRIC particles made on the device from a seed.

The Parallel Research Kernels' PIC initialisation: particles sit at cell
centres, column ``c`` is drawn with probability proportional to
``rho**c`` (a truncated geometric law, sampled by its inverse CDF), rows
are uniform, horizontal velocity is zero, vertical velocity ``vy0``, and
the charge is ``(2k+1) * 2 * m / (4 * sqrt(2) * Q)`` with the sign of the
column's parity.  With the alternating-column charge grid every particle
then moves exactly ``2k+1`` cells east and ``vy0`` cells north per step,
which is what the reference checks against.

Every seed gets the same population of particles (drawn once from
``population_seed``), listed in an order of its own: the seed only
permutes the particles.  So every run does the same work and balances
the same chare loads, while the arrays the program sees differ.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` (64 bits and beyond fold in)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def charge(k: int, mass: float = 1.0, Q: float = 1.0) -> float:
    return (2 * k + 1) * 2.0 * mass / (4.0 * math.sqrt(2.0) * Q)


@functools.lru_cache(maxsize=8)
def _generator(n: int, L: int, k: int, rho: float, vy0: float):
    import jax
    import jax.numpy as jnp

    qp = np.float32(charge(k))

    @jax.jit
    def gen(population_key, order_key):
        k1, k2 = jax.random.split(population_key)
        order = jax.random.permutation(order_key, n)
        u = jax.random.uniform(k1, (n,), jnp.float32)[order]
        row = jax.random.randint(k2, (n,), 0, L, jnp.int32)[order]
        r = jnp.float32(rho)
        col = jnp.floor(jnp.log1p(-u * (1.0 - r ** L)) / jnp.log(r))
        col = jnp.clip(col, 0, L - 1).astype(jnp.int32)
        x = col.astype(jnp.float32) + 0.5
        y = row.astype(jnp.float32) + 0.5
        q = jnp.where(col % 2 == 0, qp, -qp)
        return x, y, jnp.zeros_like(x), jnp.full_like(x, vy0), q

    return gen


def generate(seed: int, *, n: int, L: int, k: int, rho: float, vy0: float,
             population_seed: int):
    """(x, y, vx, vy, q) float32 device arrays of ``n`` particles: the
    population of ``population_seed`` in the order of ``seed``."""
    return _generator(int(n), int(L), int(k), float(rho), float(vy0))(
        seed_key(population_seed), seed_key(seed))
