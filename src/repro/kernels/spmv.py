"""Pallas BSR SpMV/SpMM kernel — the sparse mat-vec the Krylov engine's
hot loop runs on.

The CUDA sparse-solver literature (Rupp et al. 1410.4054; Cheik Ahamed &
Magoulès 2108.13162) makes the sparse mat-vec the dominant kernel of every
pipelined iterative method.  TPU adaptation: nonzeros are ``nb × nb`` BSR
bricks, so the irregular gather becomes a *regular* stream of small dense
GEMMs (MXU work), and the only indirection — which block of ``x`` each
brick multiplies — is resolved by **scalar-prefetched index maps**
(``PrefetchScalarGridSpec``): the block-column table is prefetched to SMEM
and drives the BlockSpec ``index_map`` of both the brick stream and the
``x`` gather, so bricks are DMA'd directly against their ``x`` blocks and
accumulated in VMEM scratch — gather + block-GEMM + accumulate in ONE
``pallas_call``.

Grid is ``(block_rows, max_bricks_per_row)`` over the padded blocked-ELL
view of the BSR structure (:meth:`repro.sparse.formats.BSR.ell_layout`);
pad slots read brick 0 / x-block 0 but are masked by the prefetched
``valid`` table, so uneven rows cost only the pad reads.  Off-TPU the
kernel runs in interpret mode (same dispatch rule as every other kernel in
this package); float64 stays float64 (interpret mode carries it exactly —
the jnp reference path is :meth:`BSR.matvec`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _auto_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _spmm_kernel(valid_ref, brick_ref, col_ref, data_ref, x_ref, y_ref,
                 acc_ref, *, max_blk: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = valid_ref[i * max_blk + j]
    contrib = jnp.dot(data_ref[0], x_ref[0],
                      preferred_element_type=acc_ref.dtype)
    acc_ref[...] += jnp.where(v > 0, contrib, 0)

    @pl.when(j == max_blk - 1)
    def _done():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


def bsr_spmm(data: jax.Array, brick_map, col_map, valid,
             x_blocks: jax.Array, *, nbr: int,
             interpret: bool = False) -> jax.Array:
    """Y = A @ X on BSR bricks.

    ``data`` (nnzb, nb, nb); ``brick_map`` / ``col_map`` / ``valid`` are
    the flattened (nbr·max_blk,) int32 blocked-ELL tables; ``x_blocks``
    (nbc, nb, k).  Returns (nbr, nb, k).
    """
    nnzb, nb, _ = data.shape
    nbc, nb2, k = x_blocks.shape
    if nb2 != nb:
        raise ValueError(f"brick size {nb} vs x block size {nb2}")
    if brick_map.shape != col_map.shape or brick_map.shape != valid.shape:
        raise ValueError("index tables must have identical shapes")
    (flat,) = brick_map.shape
    if flat % nbr:
        raise ValueError(f"table length {flat} not a multiple of nbr={nbr}")
    max_blk = flat // nbr
    acc_dtype = jnp.float64 if data.dtype == jnp.float64 else jnp.float32

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nbr, max_blk),
        in_specs=[
            pl.BlockSpec(         # brick stream, ordered by the prefetch map
                (1, nb, nb),
                lambda i, j, valid, brick, col: (brick[i * max_blk + j],
                                                 0, 0)),
            pl.BlockSpec(         # x gather: block-col table drives the DMA
                (1, nb, k),
                lambda i, j, valid, brick, col: (col[i * max_blk + j],
                                                 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nb, k),
                               lambda i, j, valid, brick, col: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((nb, k), acc_dtype)],
    )
    return pl.pallas_call(
        functools.partial(_spmm_kernel, max_blk=max_blk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr, nb, k), x_blocks.dtype),
        interpret=interpret,
        **params,
    )(valid, brick_map, col_map, data, x_blocks)


# --------------------------------------------------------------------------
# BSR-object wrappers — what SparseOperator dispatches to.  Arbitrary n is
# handled by the format itself (BSR carries the identity/zero pad of
# core/blocking; operands are zero-padded and outputs sliced, exact).
# --------------------------------------------------------------------------

def _tables(bsr):
    brick_map, col_map, valid = bsr.ell_layout()
    return (jnp.asarray(valid.ravel()), jnp.asarray(brick_map.ravel()),
            jnp.asarray(col_map.ravel()))


def bsr_matvec(bsr, x: jax.Array, *, interpret: bool | None = None
               ) -> jax.Array:
    """y = A x (x of shape (n,) or (n, k)) through the fused Pallas kernel;
    interpret mode off-TPU."""
    valid, brick_map, col_map = _tables(bsr)
    xb = bsr._blocks(x)
    yb = bsr_spmm(bsr.data, brick_map, col_map, valid, xb, nbr=bsr.nbr,
                  interpret=_auto_interpret(interpret))
    return bsr._unblocks(yb, x)


def bsr_matvec_ref(bsr, x: jax.Array) -> jax.Array:
    """jnp oracle (same math, gather + segment_sum) the kernel tests sweep
    against."""
    return bsr.matvec(x)
