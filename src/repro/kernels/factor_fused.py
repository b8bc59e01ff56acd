"""Fused factorization panel-update kernels (paper §2 direct path, TPU).

One blocked LU / Cholesky step after the (tiny) panel factorization is

    TRSM:  U12  = L11⁻¹ A12            (panel triangular solve)
    GEMM:  A22 -= L21 U12              (delayed rank-nb trailing update)

— two kernel launches and an extra round-trip of U12 through HBM when done
naively.  Following the kernel-fusion argument of Rupp et al.
(arXiv:1410.4054) applied to the direct path, this module fuses both into
ONE ``pallas_call``: each output tile computes its slice of the TRSM result
from the pre-inverted (nb, nb) diagonal block (inverse-based TRSM, the same
trick as :mod:`repro.kernels.trsm`) and immediately subtracts the rank-nb
product, so the panel solve never leaves VMEM.

The kernels are *masked*: they run over the whole (n, n) matrix they are
given, with the step offset ``k`` passed as an SMEM scalar, so one launch
geometry serves every step of a ``lax.fori_loop`` factorization in
:mod:`repro.core.lu` / :mod:`repro.core.cholesky` (ScaLAPACK-style static
windows; LU passes each stage's trailing window and the step's offset in
it), and the masked regions contribute exact zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.krylov_fused import _auto_interpret


def _iota2(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _lu_kernel(k_ref, linv_ref, c_ref, r_ref, a_ref, o_ref, *,
               nb: int, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = k_ref[0]

    # TRSM part: U12 tile = L11^{-1} @ R tile (inverse-based; MXU matmul).
    linv = linv_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)                       # (nb, bn)
    u = jnp.dot(linv, r, preferred_element_type=jnp.float32)
    ucols = j * bn + _iota2((nb, bn), 1)
    u_trail = jnp.where(ucols >= k + nb, u, 0.0)             # only cols > panel

    # GEMM part: rank-nb trailing update with the packed multipliers.
    c = c_ref[...].astype(jnp.float32)                       # (nb, nb) row tile
    crows = i * nb + _iota2((nb, nb), 0)
    l21 = jnp.where(crows >= k + nb, c, 0.0)                 # only rows below
    out = a_ref[...].astype(jnp.float32) - jnp.dot(
        l21, u_trail, preferred_element_type=jnp.float32)

    # write U12 into the panel row block (rows [k, k+nb), trailing cols) —
    # the l21 mask guarantees the GEMM contribution there is exactly zero.
    rows = i * nb + _iota2((nb, bn), 0)
    cols = j * bn + _iota2((nb, bn), 1)
    panel_row = (rows >= k) & (rows < k + nb) & (cols >= k + nb)
    out = jnp.where(panel_row, u, out)
    o_ref[...] = out.astype(o_ref.dtype)


def lu_panel_update(a: jax.Array, linv: jax.Array, k, *, nb: int,
                    bn: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """One fused LU step: TRSM of the panel row block + rank-nb update.

    ``a`` is the (n, n) working matrix *after* the pivoted panel has been
    written back (packed multipliers in columns [k, k+nb)); ``linv`` is the
    inverse of the unit-lower (nb, nb) diagonal block; ``k`` may be traced
    (the fori_loop step offset).
    """
    n = a.shape[0]
    bn = nb if bn is None else min(bn, n)
    if n % nb or n % bn:
        raise ValueError(f"n={n} not tiled by (nb={nb}, bn={bn})")
    c = jax.lax.dynamic_slice(a, (0, k), (n, nb))            # panel colblock
    r = jax.lax.dynamic_slice(a, (k, 0), (nb, n))            # panel rowblock
    k_arr = jnp.reshape(k, (1,)).astype(jnp.int32)
    interpret = _auto_interpret(interpret)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))

    return pl.pallas_call(
        functools.partial(_lu_kernel, nb=nb, bn=bn),
        grid=(n // nb, n // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # k scalar
            pl.BlockSpec((nb, nb), lambda i, j: (0, 0)),      # L11^{-1}
            pl.BlockSpec((nb, nb), lambda i, j: (i, 0)),      # colblock tile
            pl.BlockSpec((nb, bn), lambda i, j: (0, j)),      # rowblock tile
            pl.BlockSpec((nb, bn), lambda i, j: (i, j)),      # A tile
        ],
        out_specs=pl.BlockSpec((nb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), a.dtype),
        interpret=interpret,
        **params,
    )(k_arr, linv, c, r, a)


def _chol_kernel(k_ref, linv_ref, ci_ref, cj_ref, a_ref, o_ref, *, nb: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = k_ref[0]

    # TRSM part (right-side): L21 tile = C tile @ L11^{-T}.
    linv_t = linv_ref[...].astype(jnp.float32).T
    ci = ci_ref[...].astype(jnp.float32)                     # (nb, nb)
    cj = cj_ref[...].astype(jnp.float32)
    rows_i = i * nb + _iota2((nb, nb), 0)
    rows_j = j * nb + _iota2((nb, nb), 0)
    l21_i = jnp.where(rows_i >= k + nb,
                      jnp.dot(ci, linv_t, preferred_element_type=jnp.float32),
                      0.0)
    l21_j = jnp.where(rows_j >= k + nb,
                      jnp.dot(cj, linv_t, preferred_element_type=jnp.float32),
                      0.0)

    # SYRK part: symmetric rank-nb trailing update.
    out = a_ref[...].astype(jnp.float32) - jnp.dot(
        l21_i, l21_j.T, preferred_element_type=jnp.float32)

    # write L21 into the panel column block (cols [k, k+nb), rows below) —
    # l21_j is zero there, so the SYRK contribution is exactly zero.
    rows = rows_i
    cols = j * nb + _iota2((nb, nb), 1)
    panel_col = (cols >= k) & (cols < k + nb) & (rows >= k + nb)
    out = jnp.where(panel_col, l21_i, out)
    o_ref[...] = out.astype(o_ref.dtype)


def cholesky_panel_update(a: jax.Array, linv: jax.Array, k, *, nb: int,
                          interpret: bool | None = None) -> jax.Array:
    """One fused Cholesky step: panel TRSM + symmetric rank-nb update.

    ``a`` is the (n, n) working matrix *after* ``L_kk`` has been written to
    the diagonal block; ``linv`` is ``L_kk^{-1}``; ``k`` may be traced.
    """
    n = a.shape[0]
    if n % nb:
        raise ValueError(f"n={n} not tiled by nb={nb}")
    c = jax.lax.dynamic_slice(a, (0, k), (n, nb))            # panel colblock
    k_arr = jnp.reshape(k, (1,)).astype(jnp.int32)
    interpret = _auto_interpret(interpret)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))

    return pl.pallas_call(
        functools.partial(_chol_kernel, nb=nb),
        grid=(n // nb, n // nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # k scalar
            pl.BlockSpec((nb, nb), lambda i, j: (0, 0)),      # L_kk^{-1}
            pl.BlockSpec((nb, nb), lambda i, j: (i, 0)),      # C row tile i
            pl.BlockSpec((nb, nb), lambda i, j: (j, 0)),      # C row tile j
            pl.BlockSpec((nb, nb), lambda i, j: (i, j)),      # A tile
        ],
        out_specs=pl.BlockSpec((nb, nb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), a.dtype),
        interpret=interpret,
        **params,
    )(k_arr, linv, c, c, a)
