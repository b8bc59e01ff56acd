"""A plain LU with partial pivoting: the benchmark's reference for the
library's direct engines.

One column at a time, in straightforward ``jax.numpy``: at step ``k`` the
pivot is found in column ``k`` on and below the diagonal, rows ``k`` and
``p`` swap whole, the entries below the pivot become the multipliers and
the trailing block takes one rank-1 update.  No blocking, no cyclic
layout, no ``shard_map``.  The loop keeps one shape: its ranges (rows
below ``k``, columns right of ``k``) are index comparisons inside each
step.  Products run under ``jax.default_matmul_precision("highest")``, so
a float32 system is computed in float32 on a TPU too; the dtype is the
matrix's own (float64 where x64 is on).  It imports nothing of the
library under test.

Pivot rule: LAPACK ``getrf``'s (``getf2``), the first row whose |entry|
is largest in the column (``jnp.argmax`` returns the first maximum).
Departure: a column that is zero on and below the diagonal is left as it
is and reported nowhere (``getrf`` would return it in ``INFO``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular


@functools.partial(jax.jit, static_argnames="pivoting")
def lu_factor(a, pivoting: bool = True):
    """``(piv, lu)``: LAPACK's pivot sequence (0-based: at step ``k`` row
    ``k`` swapped with row ``piv[k]``) and the packed factors, unit lower
    ``L`` below the diagonal and ``U`` on and above it.

    ``pivoting=False`` keeps the rows in the order given (``piv[k] ==
    k``): the factors of ``a[perm]`` for a ``perm`` found elsewhere."""
    n = a.shape[0]
    idx = jnp.arange(n)

    def step(k, carry):
        a, piv = carry
        p = (jnp.argmax(jnp.where(idx >= k, jnp.abs(a[:, k]), -jnp.inf))
             if pivoting else k)
        piv = piv.at[k].set(p)
        row_k, row_p = a[k], a[p]
        a = a.at[k].set(row_p).at[p].set(row_k)
        pivot = a[k, k]
        pivot = jnp.where(pivot == 0, jnp.ones_like(pivot), pivot)
        below = idx > k
        mult = jnp.where(below, a[:, k] / pivot, 0)
        a = a - jnp.outer(mult, jnp.where(below, a[k], 0))
        return a.at[:, k].set(jnp.where(below, mult, a[:, k])), piv

    with jax.default_matmul_precision("highest"):
        a, piv = jax.lax.fori_loop(0, n, step,
                                   (a, jnp.zeros_like(idx)))
    return piv, a


@jax.jit
def permutation(piv):
    """The row permutation of a pivot sequence: ``a[perm] == L @ U``."""
    def swap(k, perm):
        pk, pp = perm[k], perm[piv[k]]
        return perm.at[k].set(pp).at[piv[k]].set(pk)
    return jax.lax.fori_loop(0, piv.shape[0], swap,
                             jnp.arange(piv.shape[0]))


@jax.jit
def lu_solve(piv, lu, b):
    """``x`` with ``a x = b`` from :func:`lu_factor`'s output."""
    with jax.default_matmul_precision("highest"):
        y = solve_triangular(lu, b[permutation(piv)], lower=True,
                             unit_diagonal=True)
        return solve_triangular(lu, y, lower=False)


def solve(a, b):
    """``(piv, lu, x)``: the pivot sequence, the packed factors and the
    solution of ``a x = b``."""
    piv, lu = lu_factor(a)
    return piv, lu, lu_solve(piv, lu, b)
