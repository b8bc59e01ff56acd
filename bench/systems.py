"""Seeded test systems, their checks and HPL's operation count.

The generator and the check are the benchmark's own copies of those in
``chip_smoke.py``, so that no change to the program moves them.  They use
``jax.numpy`` alone and import nothing of the library under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key32(*words: int) -> int:
    """A 32-bit PRNG seed mixed from whole numbers of any size.

    ``jax.random.key`` keeps only the low 32 bits of a larger seed, so
    seeds that differ above bit 31 would collide without this."""
    return int(np.random.SeedSequence([int(w) for w in words])
               .generate_state(1, np.uint32)[0])


def _uniform(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def hpl_system(key, n):
    """HPL's matrix and right-hand side: entries uniform in [-0.5, 0.5]."""
    ka, kb = jax.random.split(key)
    return _uniform(ka, (n, n)), _uniform(kb, (n,))


KINDS = {"hpl": hpl_system}


# --------------------------------------------------------------------------
# checks on the device, at HIGHEST so that the check adds no error of its own
# --------------------------------------------------------------------------

def hpl_ratio(a, x, b):
    """HPL's scaled residual ‖Ax−b‖∞ / (ε (‖A‖∞‖x‖∞ + ‖b‖∞) n)."""
    r = jnp.dot(a, x, precision=HIGHEST) - b
    norm_a = jnp.max(jnp.sum(jnp.abs(a), axis=1))
    scale = (jnp.finfo(jnp.float32).eps * a.shape[0]
             * (norm_a * jnp.max(jnp.abs(x)) + jnp.max(jnp.abs(b))))
    return jnp.max(jnp.abs(r)) / scale


# --------------------------------------------------------------------------
# HPL's operation count and the bytes an LU solve has to move
# --------------------------------------------------------------------------

def hpl_flops(n: int) -> float:
    """HPL's count for a solve of order n: 2/3·n³ + 2·n², whatever
    algorithm implements it (masked or redundant work does not count)."""
    return 2.0 / 3.0 * n ** 3 + 2.0 * n ** 2


def hpl_bytes(n: int, itemsize: int = 4) -> float:
    """The least HBM traffic of an LU solve: read A once and write the
    factors once (2·n² words), then read the factors for the two
    substitutions (n² words), plus the vectors."""
    return itemsize * (3.0 * n * n + 4.0 * n)
