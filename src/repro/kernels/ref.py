"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each function is the mathematical definition with no tiling/blocking — the
kernels in this package must match these within per-dtype tolerances (see
tests/test_kernels.py shape/dtype sweeps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """C = A @ B with fp32 accumulation (MXU semantics)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def trsm_lower(l: jax.Array, b: jax.Array, *, unit_diagonal: bool = False
               ) -> jax.Array:
    """X with L @ X = B, L lower triangular."""
    return solve_triangular(l, b, lower=True, unit_diagonal=unit_diagonal)


def fused_cg_update(x: jax.Array, r: jax.Array, p: jax.Array,
                    ap: jax.Array, alpha) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass CG vector update: x += α p; r -= α Ap; return <r,r> too."""
    xn = x + alpha * p
    rn = r - alpha * ap
    rr = jnp.vdot(rn.astype(jnp.float32), rn.astype(jnp.float32))
    return xn, rn, rr


def fused_pipelined_dots(r: jax.Array, u: jax.Array, w: jax.Array):
    """Pipelined-CG reduction oracle: (<r,u>, <w,u>, <r,r>) in fp32."""
    rf = r.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    return jnp.vdot(rf, uf), jnp.vdot(wf, uf), jnp.vdot(rf, rf)
