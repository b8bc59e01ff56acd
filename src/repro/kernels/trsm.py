"""Inverse-based block triangular solve kernel (paper §2 step 2, TPU-native).

GPU TRSV/TRSM is a latency-bound pointer chase; the TPU adaptation
converts the diagonal solves into GEMMs: the (sb × sb) diagonal
sub-blocks of T are inverted once outside the kernel (tiny, vmapped), and
the kernel performs the block substitution

    X_i = Tinv_ii @ (B_i - Σ_j T_ij X_j)     (j < i for L, j > i for U)

entirely with MXU matmuls.

Grid: (column tile c of B, step s, step t).  Step s solves block row s
of L, or block row nblk-1-s of U (back substitution runs the same loop
with the block indices reversed).  T streams through VMEM one (sb, sb)
tile at a time — it never lives there whole, so n is bounded by the
solved column tile, not by T.  For t > s the T index map clamps to the
diagonal tile, which the pipeline does not fetch again, so only T's own
triangle is read from HBM.  The solved rows X (n, bc) stay in a VMEM
scratch, in step order, that the later steps read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

# scoped-VMEM ceiling the kernel may request (v5e/v6e have 128 MiB);
# the solved-rows scratch is n × bc × 4 bytes, so n ≲ 180k at bc = 128
_VMEM_CAP = 100 << 20


def _trsm_kernel(t_ref, tinv_ref, b_ref, x_ref, xs_ref, acc_ref, *, sb: int):
    s = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = b_ref[...].astype(jnp.float32)

    @pl.when(t < s)
    def _update():
        x_t = xs_ref[pl.ds(pl.multiple_of(t * sb, sb), sb), :]
        acc_ref[...] -= jnp.dot(t_ref[...].astype(jnp.float32), x_t,
                                preferred_element_type=jnp.float32)

    @pl.when(t == s)
    def _solve():
        x_s = jnp.dot(tinv_ref[...], acc_ref[...],
                      preferred_element_type=jnp.float32)
        xs_ref[pl.ds(pl.multiple_of(s * sb, sb), sb), :] = x_s
        x_ref[...] = x_s.astype(x_ref.dtype)


def _trsm(t: jax.Array, b: jax.Array, *, lower: bool, unit_diagonal: bool,
          sb: int, bc: int, interpret: bool) -> jax.Array:
    n, m = b.shape
    sb = min(sb, n)
    bc = min(bc, m)
    if n % sb or m % bc:
        raise ValueError(f"shapes {(n, m)} not tiled by {(sb, bc)}")
    n_blocks = n // sb

    def blk(step):               # block row/column solved at a loop step
        return step if lower else n_blocks - 1 - step

    # invert the diagonal sub-blocks, in step order (tiny, once).  They
    # are gathered with one dynamic slice each: a reshape-and-diagonal of
    # T moves all n² entries, which at n = 16384 cost more than the solve
    starts = blk(jnp.arange(n_blocks)) * sb
    diag = jax.vmap(lambda k: jax.lax.dynamic_slice(t, (k, k), (sb, sb)))(
        starts).astype(jnp.float32)
    ident = jnp.eye(sb, dtype=jnp.float32)
    tinv = jax.vmap(lambda d: solve_triangular(
        d, ident, lower=lower, unit_diagonal=unit_diagonal))(diag)

    params = {}
    if not interpret:
        # double-buffered T / Tinv / B / X tiles + the two f32 scratches
        vmem = (2 * sb * sb * (t.dtype.itemsize + 4)
                + 4 * sb * bc * b.dtype.itemsize + 4 * (n + sb) * bc)
        if vmem > _VMEM_CAP:
            raise ValueError(f"trsm at n={n}, bc={bc} needs "
                             f"{vmem >> 20} MiB of VMEM (cap "
                             f"{_VMEM_CAP >> 20} MiB)")
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem + (4 << 20), 16 << 20))

    return pl.pallas_call(
        functools.partial(_trsm_kernel, sb=sb),
        grid=(m // bc, n_blocks, n_blocks),
        in_specs=[
            pl.BlockSpec((sb, sb), lambda c, s, u: (
                blk(s), blk(jnp.minimum(u, s)))),                  # T tile
            pl.BlockSpec((None, sb, sb), lambda c, s, u: (s, 0, 0)),  # Tinv
            pl.BlockSpec((sb, bc), lambda c, s, u: (blk(s), c)),      # B
        ],
        out_specs=pl.BlockSpec((sb, bc), lambda c, s, u: (blk(s), c)),
        out_shape=jax.ShapeDtypeStruct((n, m), b.dtype),
        scratch_shapes=[pltpu.VMEM((n, bc), jnp.float32),
                        pltpu.VMEM((sb, bc), jnp.float32)],
        interpret=interpret,
        **params,
    )(t, tinv, b)


def trsm_lower(l: jax.Array, b: jax.Array, *, unit_diagonal: bool = False,
               sb: int = 512, bc: int = 128, interpret: bool = False
               ) -> jax.Array:
    """Solve L X = B (L lower-triangular (n, n), B (n, m)).  Only the
    lower triangle of ``l`` is read."""
    return _trsm(l, b, lower=True, unit_diagonal=unit_diagonal, sb=sb,
                 bc=bc, interpret=interpret)


def trsm_upper(u: jax.Array, b: jax.Array, *, unit_diagonal: bool = False,
               sb: int = 512, bc: int = 128, interpret: bool = False
               ) -> jax.Array:
    """Solve U X = B (U upper-triangular): the same kernel, stepping
    through the block rows last to first.  Only the upper triangle of
    ``u`` is read."""
    return _trsm(u, b, lower=False, unit_diagonal=unit_diagonal, sb=sb,
                 bc=bc, interpret=interpret)


# --------------------------------------------------------------------------
# Auto-padding dispatch (same contract as krylov_fused.*_auto): arbitrary
# (n, m) shapes via an exact identity/zero pad, interpret mode off-TPU.
# The padded system is block-diagonal [[L, 0], [0, I]] with zero RHS rows,
# so the pad solves to exact zeros that are sliced away.
# --------------------------------------------------------------------------

_LANE = 128


def _pad_triangular(t: jax.Array, b: jax.Array, sb: int, bc: int):
    from repro.core import blocking     # lazy: keep kernels importable alone
    n, m = b.shape
    t, sb, n_pad = blocking.pad_system(t, sb)       # the ONE pad policy
    b = blocking.pad_rhs(b, n_pad)
    bc = min(bc, _LANE)          # lane-aligned column tile that we pad m to
    m_pad = -(-m // bc) * bc
    if m_pad != m:
        b = jnp.pad(b, ((0, 0), (0, m_pad - m)))
    return t, b, sb, bc, n, m


def _trsm_auto(solve_fn, t: jax.Array, b: jax.Array, *, unit_diagonal: bool,
               sb: int, bc: int, interpret: bool | None) -> jax.Array:
    from repro.kernels.krylov_fused import _auto_interpret
    squeeze = b.ndim == 1
    b2 = b[:, None] if squeeze else b
    t2, b2, sb, bc, n, m = _pad_triangular(t, b2, sb, bc)
    x = solve_fn(t2, b2, unit_diagonal=unit_diagonal, sb=sb, bc=bc,
                 interpret=_auto_interpret(interpret))
    x = x[:n, :m]
    return x[:, 0] if squeeze else x


def trsm_lower_auto(l: jax.Array, b: jax.Array, *,
                    unit_diagonal: bool = False, sb: int = 512,
                    bc: int = 128, interpret: bool | None = None
                    ) -> jax.Array:
    """``trsm_lower`` for arbitrary shapes (zero/identity pad is exact)."""
    return _trsm_auto(trsm_lower, l, b, unit_diagonal=unit_diagonal,
                      sb=sb, bc=bc, interpret=interpret)


def trsm_upper_auto(u: jax.Array, b: jax.Array, *,
                    unit_diagonal: bool = False, sb: int = 512,
                    bc: int = 128, interpret: bool | None = None
                    ) -> jax.Array:
    """``trsm_upper`` for arbitrary shapes.  The identity pad is the
    last block, which back substitution solves first: its zero RHS rows
    solve to exact zeros and never feed the real rows."""
    return _trsm_auto(trsm_upper, u, b, unit_diagonal=unit_diagonal,
                      sb=sb, bc=bc, interpret=interpret)
