"""Blocked right-looking LU factorization with partial pivoting (paper §2).

This is the paper's *delayed-update* (Level-3 BLAS) LU: ``k`` rank-1 updates
are replaced by a single rank-``nb`` update so the hot loop is a large GEMM
— on TPU that is the MXU hot spot, optionally executed by the Pallas
kernels (``backend="pallas"``).

Block stepping runs in a few ``lax.fori_loop`` stages
(:func:`stage_bounds`), each on a static trailing window.  Stage ``i``
runs block steps ``[s0, s1)`` on ``w = a[k0:, k0:]`` with ``k0 = s0·nb``;
inside it every step works on statically-shaped windows of ``w`` (masked
panel, masked TRSM, masked rank-``nb`` trailing update — ScaLAPACK-style),
whose masked regions contribute exact zeros.  Rows above ``k0`` are final
and the columns left of it are L history, which the stage's swaps only
permute, so the stage applies its accumulated row permutation to
``a[k0:, :k0]`` once at its end and writes the ``(n − k0, n)`` row band
back in one update: the factors are bitwise those of one whole-matrix
loop.  The row gather, the rank-``nb`` update and XLA's layout copy for
the panel slice then pass over the window, not the whole matrix: summed
over the steps, 0.42 of the area at ``n/nb = 224``.

The first stage is the whole matrix and ends at the first block boundary
where the remaining width is at most ``n/√2``.  A stage's loop holds about
four window-sized buffers beside the matrix, ``1 + 4r²`` matrices of
temporaries for a window ``r·n`` wide, so with ``r ≤ 1/√2`` no later stage
holds more than the whole-matrix loop's three.  The rest is split into at
most 15 equal stages, so trace/compile cost is O(stages), at most 16 loop
bodies, where a Python-unrolled loop would be O(n / nb).  Systems of fewer
than 32 block steps, and the gspmd ``mesh=`` path (a static window would
cut across its 2-D block sharding), run one stage over the whole matrix.

``backend="pallas"`` executes the step body with the Pallas kernels: by
default the fused panel-update kernel (TRSM + rank-nb GEMM in one
``pallas_call``, :mod:`repro.kernels.factor_fused`), or with
``fuse_panel=False`` the separate :mod:`repro.kernels.trsm` /
:mod:`repro.kernels.gemm` kernels.  Off-TPU the kernels run in interpret
mode (same dispatch rule as the iterative path).

Distribution — two engines, mirroring the iterative path:

* ``mesh=`` (gspmd): the matrix is a global array in the 2-D block layout
  (``dist.matrix_spec``); the factorization is written against the *global*
  view and the XLA SPMD partitioner inserts the row-broadcasts / pivot-swap
  collectives the MPI version performed explicitly.
* :func:`lu_factor_spmd` (``api.solve(..., engine="spmd")``): the
  MPI-faithful block-cyclic factorization — column blocks distributed
  cyclically over the flattened process ring, panel broadcast and trailing
  update with hand-written collectives, ONE ``shard_map`` around the whole
  ``fori_loop``.

The per-column swap sequence is accumulated into a single row permutation
applied as one gather per panel.  Every such gather promises its indices
in bounds (:func:`_permute_rows`): a permutation of ``arange(n)`` never
leaves the matrix, so the out-of-bounds fill of ``jnp.take``'s default
mode, a select over the whole matrix on every step, has nothing to do.

Device scopes: each block step names its work with ``jax.named_scope`` —
``lu.panel`` (the pivoted panel factorization), ``lu.pivot`` (the row
gather and panel store), ``lu.update`` (the row-block TRSM and the
rank-``nb`` trailing update), ``lu.bcast`` (the distributed panel
broadcast) — and the substitutions are ``lu.fsub`` / ``lu.bsub``.  The
distributed engine's change into its cyclic layout, once per
factorization, is ``lu.distribute``: the column gather ``a[:, colperm]``,
into which the partitioner folds the collectives that carry the 2-D
blocks into the ``shard_map``'s column shards.  The
scopes are metadata only: each lands in the ``op_name`` of the compiled
program's instructions, so a profiler's op names map back to them.

``lu_factor`` returns (LU_packed, perm) with ``A[perm] = L @ U`` — i.e.
``perm`` is the accumulated row permutation (paper's ipiv, converted to
permutation form).  When ``n`` is not a block multiple the factors are of
the identity-padded system (see :mod:`repro.core.blocking`); ``lu_solve``
pads/slices the right-hand side transparently.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.scipy.linalg import solve_triangular
from jax.sharding import PartitionSpec as P

from repro.core import blocking, dist, pblas
from repro.resilience import inject
from repro.telemetry import comm as telem_comm
from repro.telemetry import metrics as telem_metrics
from repro.telemetry import trace as telem_trace

_MAX_STAGES = 16
_MIN_STAGED_BLOCKS = 32


def _permute_rows(x: jax.Array, perm: jax.Array) -> jax.Array:
    """``x[perm]`` for a permutation ``perm`` of ``arange(x.shape[0])``.

    Every index is in bounds, so the gather is told so: ``jnp.take``'s
    default ``mode="fill"`` would follow it with a select over all of
    ``x`` that puts NaN in out-of-bounds rows, of which there are none.
    ``x`` may be a NumPy array: a right-hand side that needs no padding
    reaches ``lu_solve`` as the caller passed it.
    """
    return jnp.asarray(x).at[perm].get(mode="promise_in_bounds")


def _panel_factor(pan: jax.Array, k):
    """LU with partial pivoting of the full (n, nb) column block.

    Rows below the (possibly traced) step offset ``k`` are active; rows
    above hold U history and pass through untouched (pivot search, swaps,
    scaling and the rank-1 updates are all masked to the active window).
    Returns the packed block and the global row permutation ``perm`` (n,)
    — identity outside ``[k, n)`` — with pan_in[perm] = L @ U.
    """
    n, nb = pan.shape
    rows = jnp.arange(n)

    def col_step(j, carry):
        pan, perm = carry
        g = k + j                      # global pivot row/column
        col = pan[:, j]
        # -- pivot search: largest |entry| among active rows >= g ----------
        cand = jnp.where(rows >= g, jnp.abs(col), -jnp.inf)
        p = jnp.argmax(cand)
        # -- row swap g <-> p (also recorded in perm) -----------------------
        row_g, row_p = pan[g, :], pan[p, :]
        pan = pan.at[g, :].set(row_p).at[p, :].set(row_g)
        pg, pp = perm[g], perm[p]
        perm = perm.at[g].set(pp).at[p].set(pg)
        # -- scale multipliers ----------------------------------------------
        pivot = pan[g, j]
        safe = jnp.where(pivot == 0, jnp.asarray(1, pan.dtype), pivot)
        col = pan[:, j]
        mcol = jnp.where(rows > g, col / safe, col)
        pan = pan.at[:, j].set(mcol)
        # -- rank-1 update of the panel's trailing block (masked) -----------
        urow = pan[g, :]
        mmask = jnp.where(rows > g, mcol, 0)
        umask = jnp.where(jnp.arange(nb) > j, urow, 0)
        pan = pan - jnp.outer(mmask, umask)
        return pan, perm

    return jax.lax.fori_loop(0, nb, col_step, (pan, jnp.arange(n)))


def stage_bounds(nblocks: int, mesh=None) -> tuple[int, ...]:
    """Block-step boundaries ``(0, s1, ..., nblocks)`` of :func:`lu_factor`'s
    stages: stage ``i`` runs steps ``[b[i], b[i+1])`` on the trailing window
    from block ``b[i]``.

    The first stage ends at the first boundary whose remaining width is at
    most ``nblocks/√2`` (its window then holds no more memory than the
    whole matrix's stage); the rest is split into equal stages, at most
    ``_MAX_STAGES`` in all.  One stage with a ``mesh`` or below
    ``_MIN_STAGED_BLOCKS`` block steps, where the whole-matrix passes a
    window saves are short beside each step's fixed cost and every stage
    is one more loop body to compile.
    """
    if mesh is not None or nblocks < _MIN_STAGED_BLOCKS:
        return (0, nblocks)
    s1 = nblocks - math.isqrt(nblocks * nblocks // 2)
    rest = nblocks - s1
    k = min(_MAX_STAGES - 1, rest)
    return (0,) + tuple(s1 + rest * i // k for i in range(k + 1))


def window_area_share(bounds: tuple[int, ...]) -> float:
    """The share of one whole-matrix loop's area (``nblocks`` steps over
    the whole matrix) that the stages' windows cover, summed over steps."""
    nblocks = bounds[-1]
    return sum((s1 - s0) * (nblocks - s0) ** 2
               for s0, s1 in zip(bounds, bounds[1:])) / nblocks ** 3


def lu_factor(a: jax.Array, block_size: int = 128, mesh=None,
              backend: str = "ref", fuse_panel: bool = True
              ) -> tuple[jax.Array, jax.Array]:
    """Blocked LU with partial pivoting.  Returns (LU_packed, perm)."""
    blocking.check_backend(backend, mesh)
    backend = blocking.effective_backend(backend, a.dtype)
    a, nb, n = blocking.pad_system(a, block_size)
    bounds = stage_bounds(n // nb, mesh)
    if telem_trace.active() is not None:
        telem_metrics.gauge_set("lu.stages", len(bounds) - 1)
        telem_metrics.gauge_set("lu.window_area_share",
                                window_area_share(bounds))
    if backend == "pallas":
        from repro.kernels import factor_fused, gemm, trsm
        from repro.kernels.krylov_fused import _auto_interpret
        interp = _auto_interpret(None)

    def stage(w, s0, s1):
        """Steps ``[s0, s1)`` on the trailing window ``w`` from block
        ``s0``; returns ``w`` and the stage's row permutation of it."""
        m, k0 = w.shape[0], s0 * nb
        rows = jnp.arange(m)[:, None]
        cols = jnp.arange(m)[None, :]

        def step(s, carry):
            a, perm_total = carry
            k = s * nb - k0
            # ---- panel: one pivoted factorization of the column block ----
            with jax.named_scope("lu.panel"):
                colblk = jax.lax.dynamic_slice(a, (0, k), (m, nb))
                if mesh is not None:
                    # gather the panel across process COLUMNS before the
                    # column loop (rows stay sharded): the nb-step pivoted
                    # factorization then runs on the row-sharded panel
                    # with small psum/argmax rounds — the paper's "panel on
                    # one process column" pattern
                    row_ax, _ = dist.solver_axes(mesh)
                    colblk = dist.constrain(
                        colblk, mesh, jax.sharding.PartitionSpec(row_ax, None))
                pan, perm = _panel_factor(colblk, k)
                pan = inject.tap("panel", pan, step=s)
            # one gather applies the whole panel's swap sequence (identity
            # on the already-factored rows) to L history + trailing matrix
            with jax.named_scope("lu.pivot"):
                a = _permute_rows(a, perm)
                a = jax.lax.dynamic_update_slice(a, pan, (0, k))
                perm_total = _permute_rows(perm_total, perm)
            # ---- TRSM of the panel row block + rank-nb trailing update ---
            with jax.named_scope("lu.update"):
                l11 = jax.lax.dynamic_slice(a, (k, k), (nb, nb))
                if backend == "pallas" and fuse_panel:
                    linv = solve_triangular(l11, jnp.eye(nb, dtype=a.dtype),
                                            lower=True, unit_diagonal=True)
                    a = factor_fused.lu_panel_update(a, linv, k, nb=nb,
                                                     interpret=interp)
                else:
                    rowblk = jax.lax.dynamic_slice(a, (k, 0), (nb, m))
                    if backend == "pallas":
                        u_full = trsm.trsm_lower(l11, rowblk,
                                                 unit_diagonal=True, sb=nb,
                                                 bc=nb, interpret=interp)
                    else:
                        u_full = solve_triangular(l11, rowblk, lower=True,
                                                  unit_diagonal=True)
                    u_keep = jnp.where(cols >= k + nb, u_full, rowblk)
                    a = jax.lax.dynamic_update_slice(
                        a, u_keep.astype(a.dtype), (k, 0))
                    # delayed rank-nb update — the Level-3 hot spot (masked
                    # GEMM over the window: inactive rows/cols contribute
                    # exact zeros)
                    l21 = jnp.where(rows >= k + nb,
                                    jax.lax.dynamic_slice(a, (0, k), (m, nb)),
                                    0)
                    u12 = jnp.where(cols >= k + nb, u_full, 0).astype(a.dtype)
                    if backend == "pallas":
                        a = a - gemm.matmul(l21.astype(a.dtype), u12, bm=nb,
                                            bn=nb, bk=nb, interpret=interp)
                    else:
                        a = a - l21 @ u12
                a = inject.tap("trailing", a, step=s)
                if mesh is not None:
                    a = dist.constrain_matrix(a, mesh)
            return a, perm_total

        return jax.lax.fori_loop(s0, s1, step, (w, jnp.arange(m)))

    # the first stage's window is the whole matrix and its permutation the
    # whole one so far
    a, perm_total = stage(a, 0, bounds[1])
    for s0, s1 in zip(bounds[1:], bounds[2:]):
        k0 = s0 * nb
        w, perm = stage(a[k0:, k0:], s0, s1)
        # rows above k0 are final, and the stage's swaps only permuted the
        # L history left of k0: apply them there once, and write the row
        # band back whole
        with jax.named_scope("lu.pivot"):
            hist = _permute_rows(a[k0:, :k0], perm)
            a = jax.lax.dynamic_update_slice(
                a, jnp.concatenate([hist, w], axis=1), (k0, 0))
            perm_total = jax.lax.dynamic_update_slice(
                perm_total, _permute_rows(perm_total[k0:], perm), (k0,))
    return a, perm_total


def unpack(lu: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split packed LU into (unit-lower L, upper U)."""
    l = jnp.tril(lu, -1) + jnp.eye(lu.shape[0], dtype=lu.dtype)
    u = jnp.triu(lu)
    return l, u


def lu_solve(lu: jax.Array, perm: jax.Array, b: jax.Array,
             block_size: int = 128, mesh=None, backend: str = "ref"
             ) -> jax.Array:
    """Solve A x = b given (LU, perm) from :func:`lu_factor`.

    Accepts a ``b`` shorter than the (padded) factor — pad rows solve to
    exact zeros and are sliced away.
    """
    from repro.core.triangular import solve_lower_blocked, solve_upper_blocked
    n0 = b.shape[0]
    with jax.named_scope("lu.fsub"):
        bp = _permute_rows(blocking.pad_rhs(b, lu.shape[0]), perm)
        y = solve_lower_blocked(lu, bp, unit_diagonal=True,
                                block_size=block_size, mesh=mesh,
                                backend=backend)
    with jax.named_scope("lu.bsub"):
        x = solve_upper_blocked(lu, y, block_size=block_size, mesh=mesh,
                                backend=backend)
    return x[:n0]


def lu_apply(state, b: jax.Array, *, block_size: int = 128, mesh=None,
             backend: str = "ref") -> jax.Array:
    """Registry ``apply`` entry: solve from a :func:`lu_factor` state."""
    lu, perm = state
    return lu_solve(lu, perm, b, block_size=block_size, mesh=mesh,
                    backend=backend)


def solve(a: jax.Array, b: jax.Array, block_size: int = 128, mesh=None,
          backend: str = "ref") -> jax.Array:
    """Direct dense solve via blocked, pivoted LU (paper's two-step method)."""
    lu, perm = lu_factor(a, block_size=block_size, mesh=mesh, backend=backend)
    return lu_solve(lu, perm, b, block_size=block_size, mesh=mesh,
                    backend=backend)


# --------------------------------------------------------------------------
# Distributed-memory LU: block-cyclic columns, ONE shard_map (paper §2–3,
# the MPI half; ScaLAPACK's right-looking block-cyclic factorization)
# --------------------------------------------------------------------------
#
# Layout: column blocks distributed cyclically over the flattened process
# ring (``dist.CyclicLayout``) — each process owns FULL columns, so the
# pivoted panel factorization needs no communication beyond one panel
# broadcast per step.  Pivoting strategy: genuine partial pivoting.  The
# column-cyclic layout keeps every panel entirely on its owning process,
# so the pivot search runs at full accuracy locally (no tournament
# approximation needed); the per-column swap sequence is accumulated into
# one row permutation and applied by every process to its local columns as
# a single gather per panel — the MPI original's pivot-swap traffic,
# collapsed into the panel broadcast.
#
# Per block step, entirely inside one ``lax.fori_loop`` inside ONE
# ``shard_map`` (no per-step re-entry, no host round-trips):
#   1. the OWNER alone factors its local pivoted panel (``lax.cond`` on
#      the flat rank — no collectives inside the branch) and the packed
#      result (panel ‖ pivot permutation) broadcasts ring-wide in one
#      masked psum — factor-then-broadcast, O(n·nb²) panel work done
#      once instead of P times;
#   2. every process applies the swap gather + writes the panel if owner;
#   3. every process TRSMs ITS row block, then applies the rank-nb
#      trailing update SPLIT in two: the next panel's column block is
#      updated eagerly (a small GEMM on its owner only, again under
#      ``lax.cond``), and the rest of the local columns take the masked
#      Level-3 GEMM — per-shard Pallas when ``backend="pallas"``.
#
# ``lookahead=True`` (default) exploits the split for the classic
# ScaLAPACK/HPL lookahead pipeline: the owner of panel k+1 factors and
# broadcasts it right after the eager update — i.e. while every other
# rank is still busy with step k's bulk trailing GEMM — and the factored
# panel rides in the loop carry to be consumed next step.
# ``lookahead=False`` runs the same split computation but factors the
# panel at the top of its own step; both schedules consume byte-identical
# panel inputs, so the factors agree BITWISE (the parity is a test
# invariant).  Broadcast count per factorization is identical too, plus
# one pipeline-fill broadcast for the lookahead prologue.


@dataclasses.dataclass(frozen=True)
class LuSpmdState:
    """Factor state of the distributed LU: the packed factor of the padded
    system, stored with its columns in cyclic (process-major) order —
    ``state.lu == packed_factor[:, layout.colperm]`` — plus the pivot row
    permutation.  The storage permutation is invisible to the math: the
    factorization/substitution bodies index blocks by their *global*
    position, so the factor, right-hand sides and solutions all live in
    natural row/column order.

    ``abft_err`` (set by ``lu_factor_spmd(..., abft=True)``) is the
    relative Huang–Abraham checksum residual ``max|c − U·e| / max‖U‖`` —
    a replicated scalar; validate it with
    :func:`repro.resilience.abft.verify`."""
    layout: dist.CyclicLayout
    lu: jax.Array
    perm: jax.Array
    abft_err: jax.Array | None = None


def _spmd_prep(a, block_size, mesh, backend):
    if mesh is None:
        raise ValueError("the distributed direct path (engine='spmd') "
                         "requires a mesh")
    blocking.check_backend_name(backend)
    backend = blocking.effective_backend(backend, a.dtype)
    n0 = a.shape[0]
    a, nb, n = blocking.pad_system_spmd(a, block_size, dist.nprocs(mesh))
    return a, dist.cyclic_layout(mesh, n0, n, nb), backend


def lu_factor_spmd(a: jax.Array, *, block_size: int = 128, mesh=None,
                   backend: str = "ref", lookahead: bool = True,
                   abft: bool = False) -> LuSpmdState:
    """Block-cyclic distributed LU with partial pivoting (ONE shard_map).

    ``lookahead=True`` factors+broadcasts panel k+1 during step k's bulk
    trailing update (pipeline overlap; see the module comment) — the
    resulting factor is bitwise identical to ``lookahead=False``.

    ``abft=True`` carries a Huang–Abraham checksum column ``c = A·e``
    (row sums) through the factorization, embedded as one extra LOCAL
    column of the shard so the very same swap gather, TRSM and rank-nb
    GEMM transform it (a virtual trailing column — no extra collectives,
    no extra loop-carry element, ~nb/n extra flops); at exit it must
    equal the row sums of U up to rounding.  A second exit invariant,
    the Huang–Abraham product check (eᵀL)·U = eᵀA, covers the stored
    factor itself.  The combined relative mismatch lands in
    ``LuSpmdState.abft_err`` (two extra psums total); a silently
    corrupted panel/trailing element breaks an invariant by
    O(corruption) and is caught by :func:`repro.resilience.abft.verify`.
    The stored factor is bitwise identical to ``abft=False`` (the
    underlying kernels are per-column bitwise-stable).
    """
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

    def body(a_loc, *c0):
        d = pblas.flat_index_local(row, col, q)
        nloc0 = a_loc.shape[1]
        gcol0 = lay.local_gcol(d, nloc0)
        if abft:
            # The checksum c = A·e rides as ONE extra local column of
            # ``a_loc`` — a virtual trailing column whose out-of-range
            # global index keeps it "active" at every step, so the swap
            # gather, row-block TRSM and rank-nb GEMM transform it for
            # free (those kernels are per-column bitwise-stable, so the
            # stored factor stays bitwise equal to the unchecked run).
            # Crucially the loop carry keeps the exact (a_loc, perm)
            # structure of ``abft=False``: carrying the checksum as a
            # separate tuple element costs XLA the in-place reuse of
            # the local matrix buffer (12–17% at n=1024, measured) —
            # embedding it costs ~1/(nloc/nb) extra flops instead.
            a_loc = jnp.concatenate(
                [a_loc, c0[0][0][:, None].astype(a_loc.dtype)], axis=1)
            gcol = jnp.concatenate(
                [gcol0, jnp.full((1,), 2 * n, gcol0.dtype)])
        else:
            gcol = gcol0
        nloc = a_loc.shape[1]

        def pack(pan, perm):
            return jnp.concatenate(
                [pan, perm.astype(pan.dtype)[:, None]], axis=1)

        def factor_bcast(a_loc, s, its: int = 1):
            """Owner-only pivoted panel factorization of global block
            column ``s`` + ONE packed (panel ‖ perm) broadcast.  The perm
            rides as a float column — exact (integers < 2^24 even in
            f32).  ``its`` is the telemetry loop-trip multiplier: a call
            traced inside the fori_loop body executes nblocks times."""
            owner, t = lay.owner_of(s), lay.slot_of(s)

            def have(_):
                raw = jax.lax.dynamic_slice(a_loc, (0, t * nb), (n, nb))
                pan, perm = _panel_factor(raw, s * nb)
                return pack(pan, perm)

            with jax.named_scope("lu.panel"):
                packed = jax.lax.cond(
                    d == owner, have,
                    lambda _: jnp.zeros((n, nb + 1), a_loc.dtype), None)
            with jax.named_scope("lu.bcast"), \
                    telem_comm.site("lu_panel_bcast", iters=its):
                packed = pblas.bcast_local(packed, owner, d, axes)
            return (inject.tap("panel", packed[:, :nb], step=s, rank=d),
                    packed[:, nb].astype(jnp.int32))

        def consume(carry, pan, perm, s, factor_next: bool):
            """Apply the factored panel of step ``s``: swap gather, owner
            store, row-block TRSM, then the SPLIT trailing update — next
            panel's column eagerly (owner-only cond), rest via the masked
            Level-3 GEMM.  With ``factor_next`` the eager branch also
            factors the next panel (lookahead); the packed broadcast
            happens here either way only in that mode."""
            a_loc, perm_total = carry
            k = s * nb
            owner, t = lay.owner_of(s), lay.slot_of(s)
            owner2, t2 = lay.owner_of(s + 1), lay.slot_of(s + 1)
            valid = s + 1 < nblocks
            # -- swap gather on local columns; owner stores the panel ------
            with jax.named_scope("lu.pivot"):
                a_loc = _permute_rows(a_loc, perm)
                perm_total = _permute_rows(perm_total, perm)
                a_loc = jnp.where(
                    d == owner,
                    jax.lax.dynamic_update_slice(
                        a_loc, pan.astype(a_loc.dtype), (0, t * nb)),
                    a_loc)
            with jax.named_scope("lu.update"):
                # -- TRSM of MY row block ----------------------------------
                l11 = jax.lax.dynamic_slice(pan, (k, 0), (nb, nb))
                rowblk = jax.lax.dynamic_slice(a_loc, (k, 0), (nb, nloc))
                u_full = solve_triangular(l11, rowblk, lower=True,
                                          unit_diagonal=True)
                active = (gcol >= k + nb)[None, :]
                a_loc = jax.lax.dynamic_update_slice(
                    a_loc,
                    jnp.where(active, u_full, rowblk).astype(a_loc.dtype),
                    (k, 0))
                l21 = jnp.where(rows_g >= k + nb, pan, 0).astype(a_loc.dtype)
                # -- eager update of the NEXT panel's column (owner-only) --
                sel = (d == owner2) & valid

                def eager(_):
                    raw2 = jax.lax.dynamic_slice(a_loc, (0, t2 * nb),
                                                 (n, nb))
                    u2 = jax.lax.dynamic_slice(
                        u_full, (0, t2 * nb), (nb, nb)).astype(a_loc.dtype)
                    nxt = raw2 - l21 @ u2
                    if factor_next:
                        with jax.named_scope("lu.panel"):
                            return nxt, pack(*_panel_factor(nxt, k + nb))
                    return nxt

                def skip(_):
                    z = jnp.zeros((n, nb), a_loc.dtype)
                    return (z, jnp.zeros((n, nb + 1), a_loc.dtype)) \
                        if factor_next else z

                out = jax.lax.cond(sel, eager, skip, None)
                nxt = out[0] if factor_next else out
                a_loc = jnp.where(
                    sel,
                    jax.lax.dynamic_update_slice(a_loc, nxt, (0, t2 * nb)),
                    a_loc)
                # -- rest of the rank-nb update (in-flight columns excluded)
                rest = active & ((gcol >= k + 2 * nb)[None, :] | ~valid)
                u12 = jnp.where(rest, u_full, 0).astype(a_loc.dtype)
                if backend == "pallas":
                    a_loc = a_loc - gemm.matmul(l21, u12, bm=nb, bn=nb,
                                                bk=nb, interpret=interp)
                else:
                    a_loc = a_loc - l21 @ u12
                a_loc = inject.tap("trailing", a_loc, step=s, rank=d)
            base = (a_loc, perm_total)
            if not factor_next:
                return base
            with jax.named_scope("lu.bcast"), \
                    telem_comm.site("lu_panel_bcast", iters=nblocks):
                packed = pblas.bcast_local(out[1], owner2, d, axes)
            return base + (inject.tap("panel", packed[:, :nb],
                                      step=s + 1, rank=d),
                           packed[:, nb].astype(jnp.int32))

        def finish(carry, w):
            """Exit invariants (two psums total):

            1. carried column checksum == row sums of U — catches
               corruption of the factorization's *transforms*;
            2. Huang–Abraham product check (eᵀL)·U == eᵀPA == eᵀA —
               column sums are invariant under row permutations, so the
               seed ``w`` needs no perm tracking; catches corruption of
               the *stored* factor (either triangle), including an
               element hit after its last checksum update."""
            if not abft:
                return carry
            a_aug, perm_fin = carry
            a_fin, c_fin = a_aug[:, :nloc0], a_aug[:, nloc0]
            u_loc = jnp.where(rows_g <= gcol0[None, :], a_fin, 0)
            au = jnp.abs(u_loc)
            red1 = jnp.zeros((3, n), a_fin.dtype)
            red1 = red1.at[0].set(jnp.sum(u_loc, axis=1))          # U·e
            red1 = red1.at[1].set(jnp.sum(au, axis=1))
            # eᵀL per local column (+1 for the implicit unit diagonal):
            # column sums of the strict-lower part = colsum(A) − colsum(U)
            red1 = red1.at[2, gcol0].set(jnp.sum(a_fin, axis=0)
                                         - jnp.sum(u_loc, axis=0) + 1)
            red1 = pblas.psum(red1, axes)
            ue, uabs, v = red1[0], red1[1], red1[2]
            # 2-row GEMMs, not GEMVs: XLA:CPU only dispatches a dot on a
            # COMPUTED operand to the fast GEMM kernel when the lhs has
            # >= 2 rows — a vector dot lowers to a ~40x slower loop here
            # (10ms vs 0.7ms at n=1024, measured)
            vv = jnp.stack([v, jnp.abs(v)])
            red2 = jnp.zeros((2, n), a_fin.dtype)
            red2 = red2.at[0, gcol0].set(
                jnp.abs((vv @ u_loc)[0] - w[gcol0]))
            red2 = red2.at[1, gcol0].set((vv @ au)[1])
            red2 = pblas.psum(red2, axes)
            one = jnp.asarray(1.0, a_fin.dtype)
            err1 = jnp.max(jnp.abs(c_fin - ue)) \
                / jnp.maximum(jnp.max(uabs), one)
            err2 = jnp.max(red2[0]) / jnp.maximum(jnp.max(red2[1]), one)
            return a_fin, perm_fin, jnp.maximum(err1, err2)

        perm0 = jnp.arange(n)
        init = (a_loc, perm0)
        w = c0[0][1] if abft else None
        if lookahead:
            def step(s, carry):
                return consume(carry[:2], carry[2], carry[3],
                               s, factor_next=True)

            pan1, perm1 = factor_bcast(a_loc, 0)     # pipeline fill
            return finish(jax.lax.fori_loop(
                0, nblocks, step, init + (pan1, perm1))[:2], w)

        def step(s, carry):
            pan, perm = factor_bcast(carry[0], s, its=nblocks)
            return consume(carry, pan, perm, s, factor_next=False)

        return finish(jax.lax.fori_loop(0, nblocks, step, init), w)

    spec = lay.matrix_spec()
    with jax.named_scope("lu.distribute"):
        a_cyc = a[:, lay.colperm]
    if abft:
        # checksum seeds, replicated: c0 = A·e (row sums, the carried
        # column) and w = eᵀA (column sums, the exit product check) —
        # the cyclic column permutation is storage-only, natural-order
        # sums apply
        lu_cyc, perm, err = shard_map(
            body, mesh=mesh, in_specs=(spec, P()),
            out_specs=(spec, P(), P()), check_vma=False)(
            a_cyc,
            jnp.stack([jnp.sum(a, axis=1), jnp.sum(a, axis=0)]))
        return LuSpmdState(lay, lu_cyc, perm, err)
    lu_cyc, perm = shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=(spec, P()), check_vma=False)(
        a_cyc)
    return LuSpmdState(lay, lu_cyc, perm)


def lu_apply_spmd(state: LuSpmdState, b: jax.Array, *, block_size: int = 128,
                  mesh=None, backend: str = "ref") -> jax.Array:
    """Distributed two-step solve from :func:`lu_factor_spmd`: forward and
    backward substitution on the cyclic layout, both inside one shard_map.
    ``block_size``/``mesh``/``backend`` are carried by the factor state;
    the keywords exist for registry-signature uniformity."""
    from repro.core import triangular as tri
    lay = state.layout
    mesh = lay.mesh
    n0 = b.shape[0]
    with jax.named_scope("lu.fsub"):
        bp = _permute_rows(blocking.pad_rhs(b, lay.n), state.perm)
    bp, vec = tri._as_2d(bp)
    row, col = dist.solver_axes(mesh)
    q = mesh.shape[col]

    def body(a_loc, b_rep):
        d = pblas.flat_index_local(row, col, q)
        kw = dict(nb=lay.nb, procs=lay.nprocs, d=d, axes=(row, col))
        with jax.named_scope("lu.fsub"):
            y = tri.fsub_cyclic_local(a_loc, b_rep, unit_diagonal=True, **kw)
        with jax.named_scope("lu.bsub"):
            return tri.bsub_cyclic_local(a_loc, y, **kw)

    x = tri._cyclic_call(mesh, lay, body, state.lu, bp)[:n0]
    return x[:, 0] if vec else x


def solve_spmd(a: jax.Array, b: jax.Array, *, block_size: int = 128,
               mesh=None, backend: str = "ref") -> jax.Array:
    """One-shot distributed direct solve (factor + substitution)."""
    state = lu_factor_spmd(a, block_size=block_size, mesh=mesh,
                           backend=backend)
    return lu_apply_spmd(state, b)
