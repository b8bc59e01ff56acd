"""Parallel BLAS (paper: the CUPLSS API's "parallel BLAS operations").

Two engines coexist — this is the JAX transliteration of the paper's
layer-2 "architecture independence":

* ``*_spmd``  — ``shard_map`` bodies with *explicit* ``lax`` collectives.
  These are the honest analogue of the paper's MPI broadcasts/reductions:
  every byte that crosses the network is written out by hand.
* ``*_gspmd`` — global ``jnp`` ops under ``jit`` with sharding constraints;
  the XLA SPMD partitioner schedules (and overlaps) the collectives.

The dry-run/roofline work compares both engines on the same math
(EXPERIMENTS.md §Perf).

Data layouts are those of ``repro.core.dist``:
  matrix P(row, col) blocks;  vector P(row) block-rows replicated over cols.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import dist
from repro.resilience import inject
from repro.telemetry import comm as _telem_comm

# --------------------------------------------------------------------------
# Collective counters.  Tallied at TRACE time: every solver loop here is a
# fixed-shape ``fori_loop``/``while_loop`` whose body traces exactly once,
# so the counts are per-loop-iteration collective counts plus the one-off
# setup/prologue collectives — precisely the "reductions per iteration"
# number the communication-avoiding methods are about.  Kinds:
#
#   "psum"       every psum on the wire (including those under the kinds
#                below — the raw collective count),
#   "all_gather" every all_gather,
#   "ppermute"   point-to-point ring shifts,
#   "all_to_all" full shuffles,
#   "dots"       reduction rounds that carry inner products (dot/dots/
#                dotm/gram — the latency-bound synchronizations a Krylov
#                iteration pays),
#   "bcast"      masked-psum broadcasts (panel broadcasts of the direct
#                path).
#
# The tally dict is KIND-COMPLETE: every key in ``KINDS`` is present from
# the start (zeroed), so ``c["ppermute"] == 0`` is a valid assertion even
# when nothing permuted — tests compare whole dicts.
# --------------------------------------------------------------------------

KINDS = ("psum", "all_gather", "ppermute", "all_to_all", "dots", "bcast")

_COUNTS: dict | None = None


@contextlib.contextmanager
def collective_counts():
    """Context manager yielding a live tally dict of the collectives issued
    (at trace time) by the pblas primitives while the context is open::

        with pblas.collective_counts() as c:
            api.solve(a, b, method="cg", mesh=mesh, engine="spmd")
        assert c["dots"] == 4   # 2 setup + 2 per loop body (traced once)
    """
    global _COUNTS
    prev = _COUNTS
    _COUNTS = {k: 0 for k in KINDS}
    try:
        yield _COUNTS
    finally:
        _COUNTS = prev


def _tally(kind: str, n: int = 1) -> None:
    if _COUNTS is not None:
        _COUNTS[kind] = _COUNTS.get(kind, 0) + n


def psum(x, axes):
    """Counted ``lax.psum`` — every pblas reduction goes through here.
    Also an injection site ("psum"): a corrupted all-reduce payload is
    the classic dropped-rank/transient-network fault."""
    _tally("psum")
    _telem_comm.record("psum", x)
    return inject.tap("psum", jax.lax.psum(x, axes))


def all_gather(x, axis, **kw):
    """Counted ``lax.all_gather`` (injection site "all_gather")."""
    _tally("all_gather")
    _telem_comm.record("all_gather", x)
    return inject.tap("all_gather", jax.lax.all_gather(x, axis, **kw))


def ppermute(x, axis, perm):
    """Counted ``lax.ppermute`` — the point-to-point ring shift (halo
    exchanges, systolic SUMMA variants)."""
    _tally("ppermute")
    _telem_comm.record("ppermute", x)
    return jax.lax.ppermute(x, axis, perm)


def all_to_all(x, axis, split_axis: int, concat_axis: int, **kw):
    """Counted ``lax.all_to_all`` — the full shuffle (block-layout
    transposes / redistribution)."""
    _tally("all_to_all")
    _telem_comm.record("all_to_all", x)
    return jax.lax.all_to_all(x, axis, split_axis, concat_axis, **kw)


# --------------------------------------------------------------------------
# Local primitives (the bodies that run INSIDE shard_map).  These are what
# the operator layer (repro.core.operator.SpmdLocalOperator) consumes — the
# explicit-SPMD Krylov engine is built entirely from them.
# --------------------------------------------------------------------------

def matvec_local(a_loc: jax.Array, x_loc: jax.Array,
                 row: str, col: str, q: int) -> jax.Array:
    """y = A @ x on local blocks.

    MPI analogue: all-gather x along process-grid columns (so every process
    column owns the slice of x matching its block of A's columns), local
    GEMV, then sum-reduce partial results along process-grid rows.
    """
    x_full = all_gather(x_loc, row, tiled=True)                # (n,)
    j = jax.lax.axis_index(col)
    nq = x_full.shape[0] // q
    x_j = jax.lax.dynamic_slice_in_dim(x_full, j * nq, nq)     # my col slice
    y_part = a_loc @ x_j                                       # local GEMV
    return psum(y_part, col)                                   # reduce rows


def matvec_t_local(a_loc: jax.Array, x_loc: jax.Array,
                   row: str, col: str, p: int) -> jax.Array:
    """y = Aᵀ @ x on local blocks (BiCG's dual communication pattern)."""
    y_part = a_loc.T @ x_loc                                   # (n/q,)
    # sum partial column-results along rows, then redistribute from the
    # column layout back to the row layout.
    y_col = psum(y_part, row)                                  # (n/q,) col block
    y_full = all_gather(y_col, col, tiled=True)                # (n,)
    i = jax.lax.axis_index(row)
    np_ = y_full.shape[0] // p
    return jax.lax.dynamic_slice_in_dim(y_full, i * np_, np_)


def dot_local(u: jax.Array, v: jax.Array, row: str) -> jax.Array:
    """Global inner product of block-row vectors (MPI_Allreduce)."""
    _tally("dots")
    return psum(jnp.vdot(u, v), row)


def dots_local(pairs, row: str):
    """Several inner products in ONE psum — the single-synchronization
    reduction that pipelined CG is built on (one allreduce per iteration
    instead of one per dot)."""
    _tally("dots")
    partial = jnp.stack([jnp.vdot(u, v) for u, v in pairs])
    total = psum(partial, row)
    return tuple(total[i] for i in range(len(pairs)))


def dotm_local(m: jax.Array, w: jax.Array, row: str) -> jax.Array:
    """Stacked dots m @ w for a (k, n_loc) local row-stack (GMRES Gram)."""
    _tally("dots")
    return psum(m @ w, row)


def gram_local(vs: jax.Array, row: str) -> jax.Array:
    """Full Gram matrix G = V Vᴴ of a (k, n_loc) local row-stack in ONE
    psum — the block reduction of the s-step/communication-avoiding Krylov
    methods: all k² inner products of one outer step in a single
    synchronization (vs. one reduction per iteration for pipelined CG and
    two for classic CG)."""
    _tally("dots")
    return psum(vs.conj() @ vs.T, row)


def flat_index_local(row: str, col: str, q: int) -> jax.Array:
    """This process's index in the flattened 1-D ring (row-major over the
    2-D grid) — the block-cyclic direct path's process coordinate."""
    return jax.lax.axis_index(row) * q + jax.lax.axis_index(col)


def bcast_local(x: jax.Array, src, d, axes) -> jax.Array:
    """Broadcast ``x`` from the process whose flat index ``d`` equals
    ``src`` to every process on ``axes`` (MPI_Bcast as a masked psum — the
    same collective idiom as SUMMA's panel broadcasts).  Non-source values
    are ignored.  Injection site "bcast": the received payload — a
    corrupted panel broadcast poisons every rank's trailing update."""
    _tally("bcast")
    return inject.tap("bcast",
                      psum(jnp.where(d == src, x, jnp.zeros_like(x)), axes))


# --------------------------------------------------------------------------
# shard_map engine (explicit collectives, MPI-style)
# --------------------------------------------------------------------------

def _wrap(mesh: Mesh, body, in_specs, out_specs, check_vma: bool = True):
    return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=check_vma)


def pmatvec_spmd(a: jax.Array, x: jax.Array, mesh: Mesh) -> jax.Array:
    """y = A @ x with explicit collectives (see ``matvec_local``)."""
    row, col = dist.solver_axes(mesh)
    q = mesh.shape[col]

    def body(a_loc, x_loc):
        return matvec_local(a_loc, x_loc, row, col, q)

    return _wrap(mesh, body, (P(row, col), P(row)), P(row))(a, x)


def pmatvec_t_spmd(a: jax.Array, x: jax.Array, mesh: Mesh) -> jax.Array:
    """y = Aᵀ @ x (needed by BiCG).  Dual communication pattern."""
    row, col = dist.solver_axes(mesh)
    p = mesh.shape[row]

    def body(a_loc, x_loc):
        return matvec_t_local(a_loc, x_loc, row, col, p)

    # the all_gather along `col` leaves the result replicated over `col`,
    # which the static VMA checker cannot infer — disable the check.
    return _wrap(mesh, body, (P(row, col), P(row)), P(row),
                 check_vma=False)(a, x)


def pdot_spmd(x: jax.Array, y: jax.Array, mesh: Mesh) -> jax.Array:
    """Global inner product of two block-row vectors (MPI_Allreduce)."""
    row, _ = dist.solver_axes(mesh)

    def body(x_loc, y_loc):
        return dot_local(x_loc, y_loc, row)

    return _wrap(mesh, body, (P(row), P(row)), P())(x, y)


def pnorm_spmd(x: jax.Array, mesh: Mesh) -> jax.Array:
    return jnp.sqrt(pdot_spmd(x, x, mesh))


def paxpy_spmd(alpha, x: jax.Array, y: jax.Array, mesh: Mesh) -> jax.Array:
    """y ← αx + y — embarrassingly local in the block-row layout."""
    row, _ = dist.solver_axes(mesh)

    def body(x_loc, y_loc):
        return alpha * x_loc + y_loc

    return _wrap(mesh, body, (P(row), P(row)), P(row))(x, y)


def pgemm_summa(a: jax.Array, b: jax.Array, mesh: Mesh,
                panels: int | None = None) -> jax.Array:
    """C = A @ B via SUMMA on the 2-D process grid (the paper's distributed
    GEMM pattern).

    Per outer step t: the process column owning A's t-th column-panel
    broadcasts it along its process row; the process row owning B's t-th
    row-panel broadcasts it along its process column; every process runs a
    local GEMM-accumulate.  Broadcasts are expressed as masked ``psum`` —
    byte-identical to an MPI_Bcast along the axis (up to the reduction
    combiner).
    """
    row, col = dist.solver_axes(mesh)
    p, q = mesh.shape[row], mesh.shape[col]
    steps = panels or max(p, q)

    def body(a_loc, b_loc):
        m_loc, k_a = a_loc.shape          # (m/p, k/q)
        k_b, n_loc = b_loc.shape          # (k/p, n/q)
        k = k_a * q
        kp = k // steps                   # panel width (must divide k)
        i = jax.lax.axis_index(row)
        j = jax.lax.axis_index(col)

        def step(t, c_acc):
            # --- broadcast A(:, t) panel along rows -----------------------
            src_col = (t * kp) // k_a                    # owner process column
            off_a = t * kp - src_col * k_a
            a_pan = jax.lax.dynamic_slice_in_dim(a_loc, off_a, kp, axis=1)
            a_pan = jnp.where(j == src_col, a_pan, jnp.zeros_like(a_pan))
            a_pan = jax.lax.psum(a_pan, col)             # bcast == masked psum
            # --- broadcast B(t, :) panel along cols -----------------------
            src_row = (t * kp) // k_b
            off_b = t * kp - src_row * k_b
            b_pan = jax.lax.dynamic_slice_in_dim(b_loc, off_b, kp, axis=0)
            b_pan = jnp.where(i == src_row, b_pan, jnp.zeros_like(b_pan))
            b_pan = jax.lax.psum(b_pan, row)
            return c_acc + a_pan @ b_pan                 # local GEMM (MXU)

        c0 = jnp.zeros((m_loc, n_loc), jnp.promote_types(a_loc.dtype, b_loc.dtype))
        c0 = jax.lax.pcast(c0, (row, col), to="varying")  # carry varies
        return jax.lax.fori_loop(0, steps, step, c0)

    return _wrap(mesh, body, (P(row, col), P(row, col)), P(row, col))(a, b)


# --------------------------------------------------------------------------
# GSPMD engine (compiler-scheduled collectives)
# --------------------------------------------------------------------------

def pmatvec_gspmd(a: jax.Array, x: jax.Array, mesh: Mesh) -> jax.Array:
    y = a @ dist.constrain_vector(x, mesh)
    return dist.constrain_vector(y, mesh)


def pgemm_gspmd(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    c = dist.constrain_matrix(a, mesh) @ dist.constrain_matrix(b, mesh)
    return dist.constrain_matrix(c, mesh)


def pdot_gspmd(x: jax.Array, y: jax.Array, mesh: Mesh) -> jax.Array:
    return jnp.vdot(dist.constrain_vector(x, mesh),
                    dist.constrain_vector(y, mesh))
