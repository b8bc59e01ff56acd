"""MXU-tiled matmul Pallas kernel — the paper's CUBLAS-GEMM role.

This is the local "fine-grained" acceleration level of CUPLSS: the delayed
rank-k updates of the blocked LU/Cholesky and the local GEMMs of SUMMA all
bottom out here.  TPU adaptation of the CUDA GEMM: the BlockSpec grid plays
the role of the CUDA (blocks, threads/block) launch geometry (paper step 5),
and VMEM tiles replace shared memory.  Tiles are MXU-aligned (multiples of
128 in the lane dim, 8 in the sublane dim) and accumulation is fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
           bk: int = 256, interpret: bool = False) -> jax.Array:
    """C = A @ B.  Shapes must tile evenly: M % bm == N % bn == K % bk == 0."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"{(m, n, k)} not tiled by {(bm, bn, bk)}")
    grid = (m // bm, n // bn, k // bk)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        **params,
    )(a, b)
