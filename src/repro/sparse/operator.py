"""Sparse engines for the unified solver stack.

Two engines, mirroring the dense ones in :mod:`repro.core.operator`:

* :class:`SparseOperator` — single device.  Implements the full
  ``LinearOperator`` primitive set over a :class:`~repro.sparse.formats.BSR`
  or :class:`~repro.sparse.formats.ELL` matrix, so **every** registered
  Krylov method (cg, pipelined_cg, bicg, bicgstab, gmres) runs on sparse A
  unchanged.  ``backend="pallas"`` routes the mat-vec through the fused
  scalar-prefetch SpMV kernel (:mod:`repro.kernels.spmv`) *and* inherits
  the fused vector-update / pipelined-reduction kernels of the dense
  engine — the sparse analogue of the paper's "replace several Level-1
  calls with one fused kernel".
* :func:`spmd_solve` — the MPI-faithful distributed engine: BSR block
  *rows* are sharded over the mesh row axis and the component arrays
  (padded brick values + block-column table) thread through ONE
  ``shard_map`` exactly the way preconditioner state already flows.  Each
  rank owns full block rows, so the mat-vec is one ``all_gather`` of x and
  a local brick contraction — the classic sub-structuring layout of Cheik
  Ahamed & Magoulès (2108.13162): halo exchange, local SpMV, no reduction.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import blocking, dist, pblas
from repro.core import operator as op_mod
from repro.core import precond as precond_mod
from repro.core.operator import DenseOperator, LinearOperator
from repro.sparse import formats


class SparseOperator(DenseOperator):
    """Single-device sparse engine.  Reuses the dense engine's reductions
    and fused update kernels; only the communication-free mat-vec changes.
    ``backend="pallas"`` needs BSR (the kernel's brick layout); ELL runs
    the jnp reference path."""

    has_transpose = True

    def __init__(self, a: formats.SparseMatrix, *, backend: str = "ref"):
        if not getattr(a, "is_sparse", False):
            raise TypeError(f"expected a sparse matrix, got {type(a)}")
        if backend == "pallas" and not isinstance(a, formats.BSR):
            raise ValueError("backend='pallas' SpMV is BSR-only — convert "
                             "with BSR.from_dense or use backend='ref'")
        super().__init__(matvec=self._mv, matvec_t=self._mvt,
                         backend=backend)
        self.sparse = a
        self._a_t = None        # transposed structure; see prepare()

    def prepare(self, requires: tuple = ()) -> None:
        # build the transposed BSR only when the method declared Aᵀx, and
        # build it HERE — outside the solver loop — so the O(nnz) brick
        # permutation is never traced into a while_loop body (bicg)
        if "matvec_t" in requires and self._spmv_kernel_ok() \
                and self._a_t is None:
            self._a_t = self.sparse.transpose()

    def _spmv_kernel_ok(self):
        """Mosaic has no f64 lowering — on a TPU a non-f32 matrix raises
        (:func:`repro.core.blocking.pallas_float32`); off-TPU the kernel
        runs in interpret mode, which carries every dtype exactly."""
        return self.backend == "pallas" and (
            blocking.pallas_float32(self.sparse.dtype)
            or jax.default_backend() != "tpu")

    def _mv(self, v):
        if self._spmv_kernel_ok():
            from repro.kernels import spmv
            return spmv.bsr_matvec(self.sparse, v)
        return self.sparse.matvec(v)

    def _mvt(self, v):
        if self._spmv_kernel_ok():
            from repro.kernels import spmv
            if self._a_t is None:        # direct-driver fallback
                self._a_t = self.sparse.transpose()
            return spmv.bsr_matvec(self._a_t, v)
        return self.sparse.matvec_t(v)


# --------------------------------------------------------------------------
# Block-row-sharded explicit SPMD engine
# --------------------------------------------------------------------------

class SparseSpmdLocalOperator(LinearOperator):
    """Local view of block-row-sharded BSR inside a ``shard_map``: this
    rank owns ``nbr_loc`` full block rows (padded blocked-ELL layout).
    Mat-vec = all-gather x + local brick contraction (full row ownership —
    no reduction); Aᵀx is the dual scatter + one psum."""

    has_transpose = True

    def __init__(self, data_loc: jax.Array, cols_loc: jax.Array,
                 row: str, nb: int, nbc: int):
        self.data_loc = data_loc      # (nbr_loc, max_blk, nb, nb)
        self.cols_loc = cols_loc      # (nbr_loc, max_blk) global block-cols
        self.row, self.nb, self.nbc = row, nb, nbc

    def matvec(self, v):
        from repro.resilience import inject
        x_full = pblas.all_gather(v, self.row, tiled=True)     # (n_pad,)
        xb = x_full.reshape(self.nbc, self.nb)
        y = jnp.einsum("rmij,rmj->ri", self.data_loc, xb[self.cols_loc])
        return inject.tap("matvec", y.reshape(-1))

    def matvec_t(self, v):
        xb = v.reshape(-1, self.nb)                            # local rows
        contrib = jnp.einsum("rmij,ri->rmj", self.data_loc, xb)
        z = jnp.zeros((self.nbc, self.nb), v.dtype)
        z = z.at[self.cols_loc].add(contrib)
        z = pblas.psum(z, self.row)                            # full Aᵀx
        i = jax.lax.axis_index(self.row)
        nbr_loc = self.data_loc.shape[0]
        z = jax.lax.dynamic_slice_in_dim(z, i * nbr_loc, nbr_loc)
        return z.reshape(-1)

    def dot(self, u, v):
        return pblas.dot_local(u, v, self.row)

    def dots(self, pairs):
        return pblas.dots_local(pairs, self.row)    # ONE psum for all pairs

    def dotm(self, m, w):
        return pblas.dotm_local(m, w, self.row)

    def block_dots(self, vs):
        return pblas.gram_local(vs, self.row)       # ONE psum for the Gram


def spmd_solve(method: Callable, a: formats.BSR, b: jax.Array, mesh, *,
               x0: jax.Array | None = None,
               tol: float = 1e-6, maxiter: int = 1000,
               precond: "precond_mod.Preconditioner | None" = None,
               **extra):
    """Run a single-source Krylov driver on block-row-sharded BSR with its
    entire iteration inside one ``shard_map`` — the sparse counterpart of
    :func:`repro.core.operator.spmd_solve`, same drivers, same
    preconditioner state flow (named preconditioners only)."""
    if not isinstance(a, formats.BSR):
        raise ValueError("distributed sparse solves need a BSR matrix "
                         "(ELL has no block-row brick layout)")
    row, _ = dist.solver_axes(mesh)
    p = mesh.shape[row]
    if a.nbr % p:
        raise ValueError(
            f"BSR has {a.nbr} block rows, not divisible by the {p}-way "
            f"mesh row axis — choose nb so that (n / nb) % mesh_rows == 0")
    n, n_pad = a.shape[0], a.n_pad

    data_p = a.padded_data()                      # (nbr, max_blk, nb, nb)
    _, col_map, _ = a.ell_layout()
    cols = jnp.asarray(col_map)                   # (nbr, max_blk)
    bp = jnp.pad(b, (0, n_pad - n))

    pkind, pdata = op_mod.spmd_named_precond(precond, rows=n_pad,
                                             mesh_rows=p)
    if pkind == "jacobi" and pdata[0].shape[0] != n_pad:
        # identity pad rows really do have unit diagonal — pad with 1s
        pdata = (jnp.pad(pdata[0], (0, n_pad - pdata[0].shape[0]),
                         constant_values=1),)
    pspecs = precond_mod.data_specs(pkind, row)

    if x0 is None:
        def body(data_loc, cols_loc, b_loc, *pdata_loc):
            op = SparseSpmdLocalOperator(data_loc, cols_loc, row, a.nb,
                                         a.nbr)
            apply_m = precond_mod.local_apply(pkind, pdata_loc)
            res = method(op, b_loc, tol=tol, maxiter=maxiter,
                         precond=apply_m, **extra)
            return op_mod.result_leaves(res)

        res = op_mod.spmd_run(body, mesh, row,
                              (P(row), P(row), P(row)) + pspecs,
                              data_p, cols, bp, *pdata)
        return res._replace(x=res.x[:n])

    x0p = jnp.pad(x0, (0, n_pad - n))

    def body(data_loc, cols_loc, b_loc, x0_loc, *pdata_loc):
        op = SparseSpmdLocalOperator(data_loc, cols_loc, row, a.nb, a.nbr)
        apply_m = precond_mod.local_apply(pkind, pdata_loc)
        res = method(op, b_loc, x0_loc, tol=tol, maxiter=maxiter,
                     precond=apply_m, **extra)
        return op_mod.result_leaves(res)

    res = op_mod.spmd_run(body, mesh, row,
                          (P(row), P(row), P(row), P(row)) + pspecs,
                          data_p, cols, bp, x0p, *pdata)
    return res._replace(x=res.x[:n])
