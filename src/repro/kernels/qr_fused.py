"""Fused QR panel-update kernel (the direct path's rectangular member).

One blocked Householder QR step after the (tiny) panel factorization is

    GEMM:  W    = Vᵀ A₂             (panel projections)
    GEMM:  A₂ -= V (Tᵀ W)           (compact-WY rank-nb trailing update)

— two kernel launches and a round-trip of the (nb, n) projection matrix
``W`` through HBM when done naively.  Following the same fusion argument
as :mod:`repro.kernels.factor_fused` (Rupp et al. 1410.4054 applied to
the direct path), this module fuses the whole update into ONE
``pallas_call``: each program owns a full-height column strip of ``A``,
computes its slice of ``W`` on the MXU, applies ``Tᵀ`` and the rank-nb
product while everything is still in VMEM, and writes the strip back
once.

The kernel is *masked*: it always runs over the full (m, n) padded
matrix with the step offset ``k`` passed as an SMEM scalar, so one launch
geometry serves every step of the ``lax.fori_loop`` factorization in
:mod:`repro.core.qr` — trace/compile cost is O(1) in the matrix size, and
columns left of the trailing window pass through untouched (``V`` is
already masked to the active rows by construction, so no row mask is
needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.krylov_fused import _auto_interpret


def _qr_kernel(k_ref, v_ref, t_ref, a_ref, o_ref, *, nb: int, bn: int):
    j = pl.program_id(0)
    k = k_ref[0]

    v = v_ref[...].astype(jnp.float32)                       # (m, nb)
    t = t_ref[...].astype(jnp.float32)                       # (nb, nb)
    a = a_ref[...].astype(jnp.float32)                       # (m, bn)

    # W slice = Vᵀ A strip, then the rank-nb product — all in VMEM.
    w = jnp.dot(v.T, a, preferred_element_type=jnp.float32)
    upd = jnp.dot(v, jnp.dot(t.T, w, preferred_element_type=jnp.float32),
                  preferred_element_type=jnp.float32)

    # only the trailing window (cols >= k + nb) takes the update; the
    # panel / factored columns stream through unchanged
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    out = jnp.where(cols >= k + nb, a - upd, a)
    o_ref[...] = out.astype(o_ref.dtype)


def qr_panel_update(a: jax.Array, v: jax.Array, t: jax.Array, k, *,
                    nb: int, bn: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """One fused QR step: A ← (I − V Tᵀ Vᵀ) A on the trailing columns.

    ``a`` is the (m, n) working matrix *after* the factored panel has
    been written back; ``v`` is the (m, nb) masked Householder block
    (unit diagonal explicit, zeros above the panel); ``t`` the compact-WY
    triangle; ``k`` may be traced (the fori_loop step offset).
    """
    m, n = a.shape
    bn = nb if bn is None else min(bn, n)
    if n % bn or v.shape != (m, nb) or t.shape != (nb, nb):
        raise ValueError(f"shapes not tiled: a={a.shape} v={v.shape} "
                         f"t={t.shape} bn={bn}")
    k_arr = jnp.reshape(k, (1,)).astype(jnp.int32)
    interpret = _auto_interpret(interpret)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))

    return pl.pallas_call(
        functools.partial(_qr_kernel, nb=nb, bn=bn),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # k scalar
            pl.BlockSpec((m, nb), lambda j: (0, 0)),          # V
            pl.BlockSpec((nb, nb), lambda j: (0, 0)),         # T
            pl.BlockSpec((m, bn), lambda j: (0, j)),          # A strip
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
        **params,
    )(k_arr, v, t, a)
