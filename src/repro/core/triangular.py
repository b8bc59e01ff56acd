"""Blocked distributed triangular solves (paper §2, step 2: Ly = b, Ux = y).

Forward/backward substitution has Θ(n²) work; the blocked form turns the
inner dependence into (nb × nb) diagonal-block solves plus GEMV-style
rank-updates, so the bulk of the traffic is Level-2/3 BLAS on the 2-D block
layout.  The diagonal-block solve itself is tiny and replicated.

Block stepping is a fixed-shape ``lax.fori_loop`` (statically-shaped
diagonal slices + a masked column-block GEMV per step), so trace/compile
cost is O(1) in ``n``; non-block-multiple sizes are identity/zero padded
(exact — see :mod:`repro.core.blocking`).

TPU adaptation: instead of the GPU pointer-chasing TRSV, each step is a
fixed-shape dense ``solve_triangular`` on an (nb, nb) tile + a GEMV update
of the remaining right-hand side.  ``backend="pallas"`` skips the step loop
entirely and runs the whole solve in ONE inverse-based Pallas kernel launch
(:mod:`repro.kernels.trsm`, auto-padded, interpret mode off-TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.scipy.linalg import solve_triangular
from jax.sharding import PartitionSpec as P

from repro.core import blocking, dist, pblas
from repro.telemetry import comm as telem_comm


def _rows(y, k, nb):
    return jax.lax.dynamic_slice_in_dim(y, k, nb, 0)


def _set_rows(y, yk, k):
    return jax.lax.dynamic_update_slice_in_dim(y, yk.astype(y.dtype), k, 0)


def solve_lower_blocked(a: jax.Array, b: jax.Array, *,
                        unit_diagonal: bool = False, block_size: int = 128,
                        mesh=None, backend: str = "ref") -> jax.Array:
    """Solve L y = b where L is the lower triangle of ``a``."""
    blocking.check_backend(backend, mesh)
    if blocking.effective_backend(backend, a.dtype) == "pallas":
        # ONE inverse-based kernel launch on the kernel's own tile; the
        # auto wrapper applies the same pad policy itself
        from repro.kernels import trsm
        return trsm.trsm_lower_auto(a, b, unit_diagonal=unit_diagonal)
    n0 = b.shape[0]
    a, nb, n = blocking.pad_system(a, block_size)
    b = blocking.pad_rhs(b, n)
    rows = jnp.arange(n)[:, None]

    def step(s, y):
        k = s * nb
        lkk = jax.lax.dynamic_slice(a, (k, k), (nb, nb))
        yk = solve_triangular(lkk, _rows(y, k, nb), lower=True,
                              unit_diagonal=unit_diagonal)
        y = _set_rows(y, yk, k)
        # masked GEMV update of every row below the diagonal block
        colblk = jax.lax.dynamic_slice(a, (0, k), (n, nb))
        m = jnp.where(rows >= k + nb, colblk, 0)
        y = y - (m @ yk).astype(y.dtype)
        if mesh is not None and y.ndim == 1:
            y = dist.constrain_vector(y, mesh)
        return y

    y = jax.lax.fori_loop(0, n // nb, step, b)
    return y[:n0]


def solve_upper_blocked(a: jax.Array, b: jax.Array, *,
                        block_size: int = 128, mesh=None,
                        backend: str = "ref") -> jax.Array:
    """Solve U x = b where U is the upper triangle of ``a``."""
    blocking.check_backend(backend, mesh)
    if blocking.effective_backend(backend, a.dtype) == "pallas":
        from repro.kernels import trsm
        return trsm.trsm_upper_auto(a, b)
    n0 = b.shape[0]
    a, nb, n = blocking.pad_system(a, block_size)
    b = blocking.pad_rhs(b, n)
    rows = jnp.arange(n)[:, None]

    def step(s, x):
        k = n - (s + 1) * nb
        ukk = jax.lax.dynamic_slice(a, (k, k), (nb, nb))
        xk = solve_triangular(ukk, _rows(x, k, nb), lower=False)
        x = _set_rows(x, xk, k)
        # masked GEMV update of every row above the diagonal block
        colblk = jax.lax.dynamic_slice(a, (0, k), (n, nb))
        m = jnp.where(rows < k, colblk, 0)
        x = x - (m @ xk).astype(x.dtype)
        if mesh is not None and x.ndim == 1:
            x = dist.constrain_vector(x, mesh)
        return x

    x = jax.lax.fori_loop(0, n // nb, step, b)
    return x[:n0]


# --------------------------------------------------------------------------
# Distributed substitution on the block-cyclic column layout (paper §2,
# step 2, distributed-memory form).  These are LOCAL bodies — they run
# INSIDE a ``shard_map`` whose matrix operand is laid out by
# ``dist.CyclicLayout`` (each process owns full columns of its cyclic
# block set).  Per block step the owning process solves the (nb, nb)
# diagonal system and broadcasts the combined "solved block + GEMV update"
# delta vector in ONE masked psum; the right-hand side stays replicated.
# --------------------------------------------------------------------------


def _colblk(a_loc, t, nb):
    return jax.lax.dynamic_slice(a_loc, (0, t * nb), (a_loc.shape[0], nb))


def fsub_cyclic_local(a_loc, b, *, nb: int, procs: int, d, axes,
                      unit_diagonal: bool = False):
    """Forward substitution L y = b; ``b`` (n, k) replicated, L column-
    cyclic.  Returns the replicated solution."""
    n = a_loc.shape[0]
    rows = jnp.arange(n)[:, None]

    def step(s, y):
        k = s * nb
        owner, t = s % procs, s // procs
        colblk = _colblk(a_loc, t, nb)
        lkk = jax.lax.dynamic_slice(colblk, (k, 0), (nb, nb))
        yk = solve_triangular(lkk, _rows(y, k, nb), lower=True,
                              unit_diagonal=unit_diagonal)
        below = jnp.where(rows >= k + nb, colblk, 0)
        delta = -(below @ yk)
        delta = jax.lax.dynamic_update_slice(
            delta, (yk - _rows(y, k, nb)).astype(delta.dtype), (k, 0))
        # only the owner's delta is real; one bcast-psum applies it
        with telem_comm.site("trsv_bcast", iters=n // nb):
            delta = pblas.bcast_local(delta, owner, d, axes)
        return y + delta.astype(y.dtype)

    return jax.lax.fori_loop(0, n // nb, step, b)


def bsub_cyclic_local(a_loc, b, *, nb: int, procs: int, d, axes):
    """Backward substitution U x = b; U column-cyclic, b replicated."""
    n = a_loc.shape[0]
    rows = jnp.arange(n)[:, None]

    def step(s, x):
        g = n // nb - 1 - s
        k = g * nb
        owner, t = g % procs, g // procs
        colblk = _colblk(a_loc, t, nb)
        ukk = jax.lax.dynamic_slice(colblk, (k, 0), (nb, nb))
        xk = solve_triangular(ukk, _rows(x, k, nb), lower=False)
        above = jnp.where(rows < k, colblk, 0)
        delta = -(above @ xk)
        delta = jax.lax.dynamic_update_slice(
            delta, (xk - _rows(x, k, nb)).astype(delta.dtype), (k, 0))
        with telem_comm.site("trsv_bcast", iters=n // nb):
            delta = pblas.bcast_local(delta, owner, d, axes)
        return x + delta.astype(x.dtype)

    return jax.lax.fori_loop(0, n // nb, step, b)


def bsub_t_cyclic_local(a_loc, b, *, nb: int, procs: int, d, axes, gcol):
    """Backward substitution Lᵀ x = b with L stored column-cyclic (the
    Cholesky second solve).  Lᵀ's column block k is L's ROW block k, which
    is spread across every process — each contributes its partial GEMV for
    its own global columns via scatter + psum (the dual pattern to the
    forward solve's owner-broadcast)."""
    n = a_loc.shape[0]

    def step(s, x):
        g = n // nb - 1 - s
        k = g * nb
        owner, t = g % procs, g // procs
        with telem_comm.site("trsv_bcast", iters=n // nb):
            lkk = pblas.bcast_local(
                jax.lax.dynamic_slice(_colblk(a_loc, t, nb), (k, 0),
                                      (nb, nb)),
                owner, d, axes)
        xk = solve_triangular(lkk.T, _rows(x, k, nb), lower=False)
        # my partial update: x[j] -= L[kblk, j]ᵀ xk for my columns j < k
        lrow = jax.lax.dynamic_slice(a_loc, (k, 0), (nb, a_loc.shape[1]))
        contrib = -(lrow.T @ xk)
        contrib = jnp.where((gcol < k)[:, None], contrib, 0)
        delta = jax.lax.psum(
            jnp.zeros_like(x).at[gcol].set(contrib.astype(x.dtype)), axes)
        kpart = jax.lax.dynamic_update_slice(
            jnp.zeros_like(x), (xk - _rows(x, k, nb)).astype(x.dtype), (k, 0))
        return x + delta + kpart

    return jax.lax.fori_loop(0, n // nb, step, b)


def _cyclic_call(mesh, lay, body, a_cyc, bp):
    f = shard_map(body, mesh=mesh, in_specs=(lay.matrix_spec(), P()),
                  out_specs=P(), check_vma=False)
    return f(a_cyc, bp)


def _as_2d(b):
    return (b[:, None], True) if b.ndim == 1 else (b, False)


def solve_lower_spmd(a: jax.Array, b: jax.Array, *, block_size: int = 128,
                     mesh=None, unit_diagonal: bool = False) -> jax.Array:
    """Distributed L y = b on the block-cyclic column layout (one
    shard_map, one bcast-psum per block step)."""
    if mesh is None:
        raise ValueError("solve_lower_spmd needs a mesh; use "
                         "solve_lower_blocked for the local path")
    procs = dist.nprocs(mesh)
    n0 = b.shape[0]
    a, nb, n = blocking.pad_system_spmd(a, block_size, procs)
    lay = dist.cyclic_layout(mesh, n0, n, nb)
    bp, vec = _as_2d(blocking.pad_rhs(b, n))
    row, col = dist.solver_axes(mesh)
    q = mesh.shape[col]

    def body(a_loc, b_rep):
        d = pblas.flat_index_local(row, col, q)
        return fsub_cyclic_local(a_loc, b_rep, nb=nb, procs=procs, d=d,
                                 axes=(row, col),
                                 unit_diagonal=unit_diagonal)

    y = _cyclic_call(mesh, lay, body, a[:, lay.colperm], bp)[:n0]
    return y[:, 0] if vec else y


def solve_upper_spmd(a: jax.Array, b: jax.Array, *, block_size: int = 128,
                     mesh=None) -> jax.Array:
    """Distributed U x = b on the block-cyclic column layout."""
    if mesh is None:
        raise ValueError("solve_upper_spmd needs a mesh; use "
                         "solve_upper_blocked for the local path")
    procs = dist.nprocs(mesh)
    n0 = b.shape[0]
    a, nb, n = blocking.pad_system_spmd(a, block_size, procs)
    lay = dist.cyclic_layout(mesh, n0, n, nb)
    bp, vec = _as_2d(blocking.pad_rhs(b, n))
    row, col = dist.solver_axes(mesh)
    q = mesh.shape[col]

    def body(a_loc, b_rep):
        d = pblas.flat_index_local(row, col, q)
        return bsub_cyclic_local(a_loc, b_rep, nb=nb, procs=procs, d=d,
                                 axes=(row, col))

    x = _cyclic_call(mesh, lay, body, a[:, lay.colperm], bp)[:n0]
    return x[:, 0] if vec else x
