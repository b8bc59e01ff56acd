"""Blocked right-looking Cholesky factorization A = L Lᵀ (paper §2, SPD path).

Same delayed-update structure as the LU: per block step, a small replicated
(nb × nb) Cholesky of the diagonal block, a block TRSM for the panel below
it, and a rank-``nb`` SYRK trailing update — the Level-3 hot spot that runs
on the MXU (or the Pallas kernels with ``backend="pallas"``).

Like :mod:`repro.core.lu`, block stepping is a fixed-shape
``lax.fori_loop`` over masked, statically-shaped windows of the full
matrix, so trace/compile cost is O(1) in ``n``; non-block-multiple sizes
are identity-padded (exact — see :mod:`repro.core.blocking`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.scipy.linalg import solve_triangular
from jax.sharding import PartitionSpec as P

from repro.core import blocking, dist, pblas
from repro.resilience import inject
from repro.telemetry import comm as telem_comm


def cholesky_factor(a: jax.Array, block_size: int = 128, mesh=None,
                    backend: str = "ref", fuse_panel: bool = True
                    ) -> jax.Array:
    """Returns L (lower triangular) with A = L @ L.T.  A must be SPD."""
    blocking.check_backend(backend, mesh)
    backend = blocking.effective_backend(backend, a.dtype)
    a, nb, n = blocking.pad_system(a, block_size)
    rows = jnp.arange(n)[:, None]
    if backend == "pallas":
        from repro.kernels import factor_fused, gemm, trsm
        from repro.kernels.krylov_fused import _auto_interpret
        interp = _auto_interpret(None)

    def step(s, a):
        k = s * nb
        akk = jax.lax.dynamic_slice(a, (k, k), (nb, nb))
        lkk = inject.tap("panel", jnp.linalg.cholesky(akk), step=s)
        a = jax.lax.dynamic_update_slice(a, lkk.astype(a.dtype), (k, k))
        if backend == "pallas" and fuse_panel:
            # L21 = A21 @ L11^{-T} via the pre-inverted diagonal block
            linv = solve_triangular(lkk, jnp.eye(nb, dtype=a.dtype),
                                    lower=True)
            a = factor_fused.cholesky_panel_update(a, linv, k, nb=nb,
                                                   interpret=interp)
        else:
            # L21 = A21 @ L11^{-T}  (right-side TRSM), masked to the rows
            # below the panel; history rows / diag block pass through
            colblk = jax.lax.dynamic_slice(a, (0, k), (n, nb))
            if backend == "pallas":
                l21_full = trsm.trsm_lower(lkk, colblk.T, sb=nb, bc=nb,
                                           interpret=interp).T
            else:
                l21_full = solve_triangular(lkk, colblk.T, lower=True).T
            l21 = jnp.where(rows >= k + nb, l21_full.astype(a.dtype), colblk)
            a = jax.lax.dynamic_update_slice(a, l21, (0, k))
            # trailing SYRK (delayed rank-nb update, masked full GEMM)
            l21m = jnp.where(rows >= k + nb, l21, 0)
            if backend == "pallas":
                a = a - gemm.matmul(l21m, l21m.T, bm=nb, bn=nb, bk=nb,
                                    interpret=interp)
            else:
                a = a - l21m @ l21m.T
        a = inject.tap("trailing", a, step=s)
        if mesh is not None:
            a = dist.constrain_matrix(a, mesh)
        return a

    a = jax.lax.fori_loop(0, n // nb, step, a)
    return jnp.tril(a)


def cholesky_solve(l: jax.Array, b: jax.Array, block_size: int = 128,
                   mesh=None, backend: str = "ref") -> jax.Array:
    """Solve A x = b given L from :func:`cholesky_factor`.

    Accepts a ``b`` shorter than the (padded) factor — pad rows solve to
    exact zeros and are sliced away.
    """
    from repro.core.triangular import solve_lower_blocked, solve_upper_blocked
    n0 = b.shape[0]
    bp = blocking.pad_rhs(b, l.shape[0])
    y = solve_lower_blocked(l, bp, block_size=block_size, mesh=mesh,
                            backend=backend)
    # Ux = y with U = L.T : reuse the blocked upper solve on Lᵀ
    x = solve_upper_blocked(l.T, y, block_size=block_size, mesh=mesh,
                            backend=backend)
    return x[:n0]


def cholesky_factor_state(a: jax.Array, *, block_size: int = 128, mesh=None,
                          backend: str = "ref") -> tuple[jax.Array]:
    """Registry ``factor`` entry: one-tuple state for :func:`cholesky_apply`."""
    return (cholesky_factor(a, block_size=block_size, mesh=mesh,
                            backend=backend),)


def cholesky_apply(state, b: jax.Array, *, block_size: int = 128, mesh=None,
                   backend: str = "ref") -> jax.Array:
    """Registry ``apply`` entry: solve from a factored state."""
    (l,) = state
    return cholesky_solve(l, b, block_size=block_size, mesh=mesh,
                          backend=backend)


def solve(a: jax.Array, b: jax.Array, block_size: int = 128, mesh=None,
          backend: str = "ref") -> jax.Array:
    l = cholesky_factor(a, block_size=block_size, mesh=mesh, backend=backend)
    return cholesky_solve(l, b, block_size=block_size, mesh=mesh,
                          backend=backend)


# --------------------------------------------------------------------------
# Distributed-memory Cholesky: block-cyclic columns, ONE shard_map.
#
# Same owner-factors / split-update / lookahead structure as the
# distributed LU (see :mod:`repro.core.lu`), minus pivoting: per block
# step the OWNER alone computes the (nb, nb) diagonal Cholesky + panel
# TRSM of its local column block (``lax.cond`` on the flat rank) and
# broadcasts the factored panel — one (n, nb) collective, no perm column
# to pack.  The rank-nb SYRK trailing update is split exactly like the
# LU's: the NEXT panel's block column is updated eagerly (owner-only
# cond) so its factorization can overlap the bulk update, and the rest
# runs as the masked Level-3 GEMM over each process's local block
# columns (gathering the L21 rows matching its global column set — the
# SYRK's "transpose side" of the cyclic layout).  ``lookahead=True``
# (default) factors panel k+1 inside step k's eager branch; both
# schedules consume byte-identical panel inputs, so the factors agree
# BITWISE, and the lookahead trace carries exactly one extra
# pipeline-fill broadcast.  The cyclic column permutation is pure
# STORAGE: the body indexes blocks by global position, so the math
# eliminates natural A in natural order — SPD-ness is untouched and
# b/x need no permuting.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CholeskySpmdState:
    """L factor of the padded system, columns stored in cyclic
    (process-major) order: ``state.l == L[:, layout.colperm]``.

    ``abft_err`` (set by ``cholesky_factor_spmd(..., abft=True)``) is
    the relative Huang–Abraham checksum residual
    ``max|c − Lᵀ·e| / max‖L‖`` — a replicated scalar; validate it with
    :func:`repro.resilience.abft.verify`."""
    layout: dist.CyclicLayout
    l: jax.Array
    abft_err: jax.Array | None = None


def cholesky_factor_spmd(a: jax.Array, *, block_size: int = 128, mesh=None,
                         backend: str = "ref", lookahead: bool = True,
                         abft: bool = False) -> CholeskySpmdState:
    """Block-cyclic distributed Cholesky (ONE shard_map).

    ``lookahead=True`` factors+broadcasts panel k+1 during step k's bulk
    SYRK update (pipeline overlap; see the section comment) — the
    resulting factor is bitwise identical to ``lookahead=False``.

    ``abft=True`` carries a Huang–Abraham checksum column ``c = A·e``
    through the same left-transforms the elimination applies (per step:
    ``c[k:k+nb] ← Lkk⁻¹ c[k:k+nb]``, ``c −= L21·c[k:k+nb]`` — replicated
    O(n·nb) work, no extra collectives), so at exit ``c = L⁻¹A·e = Lᵀ·e``
    — the column sums of L.  The relative mismatch lands in
    ``CholeskySpmdState.abft_err`` (one extra psum total); validate with
    :func:`repro.resilience.abft.verify`.  ``abft=False`` traces the
    byte-identical original program.
    """
    from repro.core.lu import _spmd_prep
    a, lay, backend = _spmd_prep(a, block_size, mesh, backend)
    nb, n, procs = lay.nb, lay.n, lay.nprocs
    nblocks = lay.nblocks
    row, col = dist.solver_axes(mesh)
    q = mesh.shape[col]
    axes = (row, col)
    rows_g = jnp.arange(n)[:, None]
    if backend == "pallas":
        from repro.kernels import gemm
        from repro.kernels.krylov_fused import _auto_interpret
        interp = _auto_interpret(None)

    def _chol_panel(raw, k):
        """Diag Cholesky + panel TRSM of one (n, nb) block column: rows
        below the panel become L21, the diag block becomes Lkk, history
        rows pass through."""
        akk = jax.lax.dynamic_slice(raw, (k, 0), (nb, nb))
        lkk = jnp.linalg.cholesky(akk)
        pan0 = jax.lax.dynamic_update_slice(raw, lkk.astype(raw.dtype),
                                            (k, 0))
        l21_full = solve_triangular(lkk, pan0.T, lower=True).T
        return jnp.where(rows_g >= k + nb, l21_full.astype(raw.dtype), pan0)

    def body(a_loc, *c0):
        d = pblas.flat_index_local(row, col, q)
        gcol = lay.local_gcol(d, a_loc.shape[1])

        def factor_bcast(a_loc, s, its: int = 1):
            """Owner-only panel factorization of global block column ``s``
            + ONE (n, nb) broadcast (no perm to pack, unlike the LU).
            ``its`` is the telemetry loop-trip multiplier for calls traced
            inside the fori_loop body."""
            owner, t = lay.owner_of(s), lay.slot_of(s)
            pan = jax.lax.cond(
                d == owner,
                lambda _: _chol_panel(
                    jax.lax.dynamic_slice(a_loc, (0, t * nb), (n, nb)),
                    s * nb),
                lambda _: jnp.zeros((n, nb), a_loc.dtype), None)
            with telem_comm.site("chol_panel_bcast", iters=its):
                pan = pblas.bcast_local(pan, owner, d, axes)
            return inject.tap("panel", pan, step=s, rank=d)

        def consume(carry, pan, s, factor_next: bool):
            """Owner store + SPLIT rank-nb SYRK: next panel's block column
            eagerly (owner-only cond, with the lookahead factorization
            when ``factor_next``), rest via the masked Level-3 GEMM."""
            if abft:
                a_loc, c = carry
            else:
                (a_loc,) = carry
            k = s * nb
            owner, t = lay.owner_of(s), lay.slot_of(s)
            owner2, t2 = lay.owner_of(s + 1), lay.slot_of(s + 1)
            k2 = k + nb
            valid = s + 1 < nblocks
            a_loc = jnp.where(
                d == owner,
                jax.lax.dynamic_update_slice(a_loc, pan.astype(a_loc.dtype),
                                             (0, t * nb)),
                a_loc)
            l21m = jnp.where(rows_g >= k + nb, pan, 0).astype(a_loc.dtype)
            if abft:
                # checksum rides the elimination's LEFT transforms
                # (c[k:k+nb] ← Lkk⁻¹·, trailing −= L21·) so at exit
                # c = L⁻¹A·e = Lᵀ·e; replicated, no collectives
                lkk = jax.lax.dynamic_slice(pan, (k, 0), (nb, nb))
                c_blk = jax.lax.dynamic_slice(c, (k,), (nb,))
                u_c = solve_triangular(
                    lkk, c_blk[:, None], lower=True)[:, 0].astype(c.dtype)
                c = jax.lax.dynamic_update_slice(c, u_c, (k,))
                c = c - l21m @ u_c
            # -- eager update of the NEXT panel's block column ------------
            sel = (d == owner2) & valid

            def eager(_):
                raw2 = jax.lax.dynamic_slice(a_loc, (0, t2 * nb), (n, nb))
                lrow2 = jax.lax.dynamic_slice(l21m, (k2, 0), (nb, nb))
                nxt = raw2 - l21m @ lrow2.T
                if factor_next:
                    return nxt, _chol_panel(nxt, k2)
                return nxt

            def skip(_):
                z = jnp.zeros((n, nb), a_loc.dtype)
                return (z, z) if factor_next else z

            out = jax.lax.cond(sel, eager, skip, None)
            nxt = out[0] if factor_next else out
            a_loc = jnp.where(
                sel, jax.lax.dynamic_update_slice(a_loc, nxt, (0, t2 * nb)),
                a_loc)
            # -- rest of the SYRK (in-flight columns excluded) ------------
            is_next = valid & (gcol >= k2) & (gcol < k2 + nb)
            l21_cols = jnp.take(l21m, gcol, axis=0)       # rows j = my cols
            l21_rest = jnp.where(is_next[:, None], 0, l21_cols)
            if backend == "pallas":
                a_loc = a_loc - gemm.matmul(l21m, l21_rest.T, bm=nb, bn=nb,
                                            bk=nb, interpret=interp)
            else:
                a_loc = a_loc - l21m @ l21_rest.T
            a_loc = inject.tap("trailing", a_loc, step=s, rank=d)
            base = (a_loc, c) if abft else (a_loc,)
            if not factor_next:
                return base
            with telem_comm.site("chol_panel_bcast", iters=nblocks):
                pan2 = pblas.bcast_local(out[1], owner2, d, axes)
            return base + (inject.tap("panel", pan2, step=s + 1, rank=d),)

        init = (a_loc,) + ((c0[0],) if abft else ())
        keep = 2 if abft else 1
        if lookahead:
            def step(s, carry):
                return consume(carry[:keep], carry[keep], s,
                               factor_next=True)

            pan1 = factor_bcast(a_loc, 0)                 # pipeline fill
            fin = jax.lax.fori_loop(0, nblocks, step, init + (pan1,))[:keep]
        else:
            def step(s, carry):
                pan = factor_bcast(carry[0], s, its=nblocks)
                return consume(carry, pan, s, factor_next=False)

            fin = jax.lax.fori_loop(0, nblocks, step, init)
        # global tril on the cyclic layout: keep (i, gcol) with i >= gcol
        l_fin = jnp.where(rows_g >= gcol[None, :], fin[0], 0)
        if not abft:
            return l_fin
        # exit invariant: c = Lᵀ·e (column sums of L).  Scatter my
        # columns' mismatch + scale into a global vector — ONE psum.
        dv = jnp.zeros((2, n), l_fin.dtype)
        dv = dv.at[0, gcol].set(jnp.abs(fin[1][gcol] - jnp.sum(l_fin, 0)))
        dv = dv.at[1, gcol].set(jnp.sum(jnp.abs(l_fin), 0))
        dv = pblas.psum(dv, axes)
        scale = jnp.maximum(jnp.max(dv[1]), jnp.asarray(1.0, l_fin.dtype))
        return l_fin, jnp.max(dv[0]) / scale

    spec = lay.matrix_spec()
    if abft:
        # checksum seed c0 = A·e (row sums), replicated — the cyclic
        # column permutation is storage-only, natural-order sums apply
        l_cyc, err = shard_map(body, mesh=mesh, in_specs=(spec, P()),
                               out_specs=(spec, P()), check_vma=False)(
            a[:, lay.colperm], jnp.sum(a, axis=1))
        return CholeskySpmdState(lay, l_cyc, err)
    l_cyc = shard_map(body, mesh=mesh, in_specs=(spec,),
                      out_specs=spec, check_vma=False)(a[:, lay.colperm])
    return CholeskySpmdState(lay, l_cyc)


def cholesky_apply_spmd(state: CholeskySpmdState, b: jax.Array, *,
                        block_size: int = 128, mesh=None,
                        backend: str = "ref") -> jax.Array:
    """Distributed L y = b then Lᵀ x = y from :func:`cholesky_factor_spmd`
    (both substitutions inside one shard_map)."""
    from repro.core import triangular as tri
    lay = state.layout
    mesh = lay.mesh
    n0 = b.shape[0]
    bp, vec = tri._as_2d(blocking.pad_rhs(b, lay.n))
    row, col = dist.solver_axes(mesh)
    q = mesh.shape[col]
    procs = lay.nprocs

    def body(a_loc, b_rep):
        d = pblas.flat_index_local(row, col, q)
        gcol = lay.local_gcol(d, a_loc.shape[1])
        kw = dict(nb=lay.nb, procs=procs, d=d, axes=(row, col))
        y = tri.fsub_cyclic_local(a_loc, b_rep, **kw)
        return tri.bsub_t_cyclic_local(a_loc, y, gcol=gcol, **kw)

    x = tri._cyclic_call(mesh, lay, body, state.l, bp)[:n0]
    return x[:, 0] if vec else x


def solve_spmd(a: jax.Array, b: jax.Array, *, block_size: int = 128,
               mesh=None, backend: str = "ref") -> jax.Array:
    """One-shot distributed SPD solve (factor + substitutions)."""
    state = cholesky_factor_spmd(a, block_size=block_size, mesh=mesh,
                                 backend=backend)
    return cholesky_apply_spmd(state, b)
