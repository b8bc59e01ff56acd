"""LinearOperator layer — the paper's three distributed primitives as one
abstraction.

The paper (§2–§3) builds every iterative solver from mat-vec, inner product
and axpy.  This module makes that architecture literal: a ``LinearOperator``
exposes the primitive set

* ``matvec`` / ``matvec_t``    — y = A x and y = Aᵀ x,
* ``dot`` / ``dots`` / ``dotm``— global inner products (``dots`` performs
  several in ONE reduction — the single-synchronization primitive the
  pipelined solvers rely on, per Rupp et al. 1410.4054),
* ``update``                   — the fused x += αp; r -= αAp; ⟨r,r⟩ pass
  (the memory-bound hot spot; Pallas-fused on the dense engine),
* ``scale`` / ``norm`` / ``reduce_any`` — layout-aware helpers,

and every Krylov driver in :mod:`repro.core.krylov` is written ONCE against
it.  Engines:

* :class:`DenseOperator`     — single device; ``backend="pallas"`` routes the
  hot-loop update through :mod:`repro.kernels.krylov_fused` (interpret mode
  on CPU, auto-padded to the 128-lane constraint).
* :class:`GspmdOperator`     — sharded global arrays; XLA schedules the
  collectives (compiler-scheduled engine).
* :class:`SpmdLocalOperator` — the MPI-faithful engine: constructed *inside*
  one ``shard_map`` over local blocks, every collective written by hand via
  :mod:`repro.core.pblas` local primitives.  :func:`spmd_solve` wraps a
  whole driver in that shard_map.
* :class:`BatchedOperator`   — many independent systems at once (leading
  batch axis); scalars become per-system vectors.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import blocking, dist, pblas
from repro.core import precond as precond_mod
from repro.resilience import inject
from repro.telemetry import comm as telem_comm
from repro.telemetry import convergence as telem_conv


class LinearOperator:
    """Primitive set shared by all engines.  Subclasses override the
    communication-bearing primitives; elementwise algebra stays in the
    drivers (it is layout-agnostic)."""

    has_transpose = False
    supports_gram = True      # dotm (GMRES basis Gram products)
    batched = False

    def prepare(self, requires: tuple = ()) -> None:
        """Hook called by ``api.solve`` with the method's declared
        capability needs — lets an engine build optional state (e.g. a
        transposed sparse structure) once, outside the solver loop."""

    # -- communication-bearing primitives ---------------------------------
    def matvec(self, v: jax.Array) -> jax.Array:
        raise NotImplementedError

    def matvec_t(self, v: jax.Array) -> jax.Array:
        raise NotImplementedError(f"{type(self).__name__} has no Aᵀx")

    def dot(self, u: jax.Array, v: jax.Array) -> jax.Array:
        raise NotImplementedError

    def dots(self, pairs: Sequence[tuple[jax.Array, jax.Array]]):
        """Several inner products; engines override to use ONE reduction."""
        return tuple(self.dot(u, v) for u, v in pairs)

    def dotm(self, m: jax.Array, w: jax.Array) -> jax.Array:
        """Stacked dots ``m @ w`` for a (k, n) row-stack m (GMRES Gram)."""
        raise NotImplementedError

    def block_dots(self, vs: jax.Array) -> jax.Array:
        """Gram matrix G = V Vᴴ of a (k, n) row-stack — ALL k² basis inner
        products in one reduction.  This is the s-step/communication-
        avoiding block primitive: one call replaces the ~2s dot-product
        synchronizations of s classical Krylov iterations."""
        return inject.tap("gram", vs.conj() @ vs.T)

    # -- derived / layout helpers ------------------------------------------
    def norm(self, v: jax.Array) -> jax.Array:
        return jnp.sqrt(self.dot(v, v))

    def scale(self, s, v: jax.Array) -> jax.Array:
        """s * v with s a solver scalar (per-system vector when batched)."""
        return s * v

    def reduce_any(self, mask) -> jax.Array:
        """Collapse a per-system predicate to the loop predicate."""
        return mask

    def update(self, x, r, p, ap, alpha):
        """Fused Krylov update: (x + αp, r − αAp, ⟨r', r'⟩).
        Injection site "update": the new residual carry — the fault the
        recurrence silently propagates until the monitor trips."""
        xn = x + self.scale(alpha, p)
        rn = inject.tap("update", r - self.scale(alpha, ap))
        return xn, rn, self.dot(rn, rn)

    def axpy_pair(self, x, p, r, q, alpha):
        """(x + αp, r − αq) — the paired axpys of the least-squares
        iterations (CGLS).  ``x``/``p`` live in the solution space and
        ``r``/``q`` in the residual space, so unlike :meth:`update` the
        two pairs may have different lengths; engines fuse the pass when
        the shapes allow."""
        return x + self.scale(alpha, p), r - self.scale(alpha, q)

    def pipelined_dots(self, r, u, w):
        """(⟨r,u⟩, ⟨w,u⟩, ⟨r,r⟩) — the single fused reduction of pipelined
        CG (Chronopoulos–Gear); one pass / one synchronization."""
        return self.dots(((r, u), (w, u), (r, r)))


# --------------------------------------------------------------------------
# Dense (single device) — optional Pallas-fused hot loop
# --------------------------------------------------------------------------

class DenseOperator(LinearOperator):
    """Global arrays on one device.  ``backend="pallas"`` fuses the update
    and the pipelined reduction into single memory passes (float32 only:
    another dtype raises on a TPU and uses the jnp reference path
    off-TPU — :func:`repro.core.blocking.pallas_float32`)."""

    has_transpose = True

    def __init__(self, a: jax.Array | None = None, *,
                 matvec: Callable | None = None,
                 matvec_t: Callable | None = None,
                 backend: str = "ref"):
        if backend not in ("ref", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        if a is None and matvec is None:
            raise ValueError("need a matrix or a matvec callable")
        self.a = a
        self._matvec = matvec
        self._matvec_t = matvec_t
        self.backend = backend
        if a is None and matvec_t is None:
            self.has_transpose = False

    def matvec(self, v):
        y = self._matvec(v) if self._matvec is not None else self.a @ v
        return inject.tap("matvec", y)

    def matvec_t(self, v):
        if self._matvec_t is not None:
            return self._matvec_t(v)
        if self.a is None:
            return super().matvec_t(v)
        return self.a.T @ v

    def dot(self, u, v):
        return jnp.vdot(u, v)

    def dotm(self, m, w):
        return m @ w

    def _fusable(self, v):
        return self.backend == "pallas" and blocking.pallas_float32(v.dtype)

    def update(self, x, r, p, ap, alpha):
        if self._fusable(x):
            from repro.kernels import krylov_fused
            xn, rn, rr = krylov_fused.fused_cg_update_auto(x, r, p, ap, alpha)
            hurt = inject.tap("update", rn)
            if hurt is not rn:          # armed: re-derive the carried ⟨r,r⟩
                rn, rr = hurt, self.dot(hurt, hurt)
            return xn, rn, rr
        return super().update(x, r, p, ap, alpha)

    def pipelined_dots(self, r, u, w):
        if self._fusable(r):
            from repro.kernels import krylov_fused
            return krylov_fused.fused_pipelined_dots_auto(r, u, w)
        return super().pipelined_dots(r, u, w)

    def block_dots(self, vs):
        if self._fusable(vs):
            from repro.kernels import krylov_fused
            return inject.tap("gram", krylov_fused.fused_gram_auto(vs))
        return super().block_dots(vs)

    def axpy_pair(self, x, p, r, q, alpha):
        # one fused memory pass when both pairs share a shape (square
        # systems); the rectangular case falls back to two jnp axpys
        if self._fusable(x) and x.shape == r.shape:
            from repro.kernels import krylov_fused
            xn, rn, _ = krylov_fused.fused_cg_update_auto(x, r, p, q, alpha)
            return xn, rn
        return super().axpy_pair(x, p, r, q, alpha)


def as_operator(op, *, matvec_t: Callable | None = None) -> LinearOperator:
    """Adapt a bare matvec callable (the historical driver input) into the
    operator interface; pass operators through unchanged."""
    if isinstance(op, LinearOperator):
        return op
    if callable(op):
        return DenseOperator(matvec=op, matvec_t=matvec_t)
    raise TypeError(f"expected LinearOperator or callable, got {type(op)}")


# --------------------------------------------------------------------------
# GSPMD (compiler-scheduled collectives on sharded global arrays)
# --------------------------------------------------------------------------

class GspmdOperator(LinearOperator):
    has_transpose = True

    def __init__(self, a: jax.Array, mesh):
        self.a = a
        self.mesh = mesh

    def matvec(self, v):
        return inject.tap("matvec", pblas.pmatvec_gspmd(self.a, v, self.mesh))

    def matvec_t(self, v):
        return pblas.pmatvec_gspmd(self.a.T, v, self.mesh)

    def dot(self, u, v):
        return pblas.pdot_gspmd(u, v, self.mesh)

    def dotm(self, m, w):
        return m @ dist.constrain_vector(w, self.mesh)

    def block_dots(self, vs):
        # shard the stack's column (vector) axis so XLA lowers the Gram
        # contraction to local mm + one all-reduce
        row, _ = dist.solver_axes(self.mesh)
        vs = jax.lax.with_sharding_constraint(
            vs, jax.sharding.NamedSharding(self.mesh, P(None, row)))
        return inject.tap("gram", vs.conj() @ vs.T)


# --------------------------------------------------------------------------
# Explicit SPMD (inside one shard_map; hand-written collectives)
# --------------------------------------------------------------------------

class SpmdLocalOperator(LinearOperator):
    """Local-block view with explicit collectives.  Only valid inside a
    ``shard_map`` whose specs match ``repro.core.dist`` layouts; build one
    via :func:`spmd_solve`."""

    has_transpose = True

    def __init__(self, a_loc: jax.Array, row: str, col: str, q: int, p: int):
        self.a_loc = a_loc
        self.row, self.col, self.q, self.p = row, col, q, p

    # telem_comm.site labels attribute trace-time collective BYTES to the
    # operator primitive that issued them (innermost label wins; pure
    # host-side bookkeeping, zero ops in any jaxpr)

    def matvec(self, v):
        with telem_comm.site("matvec"):
            return inject.tap("matvec", pblas.matvec_local(
                self.a_loc, v, self.row, self.col, self.q))

    def matvec_t(self, v):
        with telem_comm.site("matvec_t"):
            return pblas.matvec_t_local(self.a_loc, v, self.row, self.col,
                                        self.p)

    def dot(self, u, v):
        with telem_comm.site("dot"):
            return pblas.dot_local(u, v, self.row)

    def dots(self, pairs):
        with telem_comm.site("dots_fused"):
            return pblas.dots_local(pairs, self.row)  # ONE psum, all pairs

    def dotm(self, m, w):
        with telem_comm.site("dotm"):
            return pblas.dotm_local(m, w, self.row)

    def block_dots(self, vs):
        # ONE psum for the Gram
        with telem_comm.site("gram"):
            return inject.tap("gram", pblas.gram_local(vs, self.row))


def spmd_named_precond(precond, *, rows: int | None = None,
                       mesh_rows: int | None = None) -> tuple[str, tuple]:
    """Shared ``engine='spmd'`` preconditioner validation → (kind, data).
    Only named preconditioners carry state that can cross a shard_map.
    ``rows``/``mesh_rows`` additionally validate that block_jacobi factors
    tile the engine's sharded row space (k·nb == rows, k % mesh_rows == 0)
    — misaligned factors would silently precondition wrong per shard."""
    if precond is not None and (
            not isinstance(precond, precond_mod.Preconditioner)
            or precond.kind == "custom"):
        raise ValueError("engine='spmd' needs a named preconditioner "
                         "('jacobi'/'block_jacobi'), not a custom callable "
                         "— callables cannot cross the shard_map boundary")
    if precond is None:
        return "identity", ()
    if precond.kind == "block_jacobi":
        k, nb = precond.data[1].shape
        if rows is not None and k * nb != rows:
            raise ValueError(
                f"block_jacobi factors cover {k * nb} rows but the spmd "
                f"engine shards {rows} rows — they cannot align; choose a "
                "block size that tiles the sharded row space")
        if mesh_rows is not None and k % mesh_rows:
            raise ValueError(
                f"block_jacobi has {k} blocks, not divisible by the "
                f"{mesh_rows}-way mesh row axis — choose a block size so "
                "that the block count divides the mesh rows")
    return precond.kind, precond.data


def result_leaves(res):
    """Flatten a :class:`SolveResult` to the leaves a shard_map body
    returns: the dict-valued ``info`` cannot cross the boundary, so the
    monitor's two scalars travel as replicated int32 outputs (zeros for
    an unmonitored driver).  With an armed telemetry session the
    convergence history's two extra leaves (the residual ring, computed
    from already-reduced scalars, hence replicated; and iters_to_tol)
    ride along — :func:`spmd_run` checks the same trace-time flag, so
    body outputs and out_specs always agree."""
    info = res.info or {}
    zero = jnp.zeros((), jnp.int32)
    code = info.get("fail_code", zero)
    fail_iter = info.get("fail_iter", zero)
    base = (res.x, res.iterations, res.residual, res.converged,
            code, fail_iter)
    hist = info.get("residual_history")
    if hist is not None:
        base += (hist, info["iters_to_tol"])
    return base


def spmd_run(body, mesh, row: str, in_specs: tuple, *operands):
    """shard_map wrapper shared by the dense and sparse spmd engines.

    while_loop has no replication rule on this JAX — disable the check;
    out_specs pin the (documented) replication of the scalar outputs.
    The body returns :func:`result_leaves`; the health monitor's
    fail_code/fail_iter scalars (and, under an armed telemetry session,
    the convergence-history leaves) are re-packed into
    ``SolveResult.info``.
    """
    armed = telem_conv.armed()
    out_specs = (P(row), P(), P(), P(), P(), P())
    if armed:
        out_specs += (P(), P())      # residual ring + iters_to_tol (repl.)
    f = shard_map(body, mesh=mesh, in_specs=in_specs,
                  out_specs=out_specs, check_vma=False)
    from repro.core.krylov import SolveResult
    out = f(*operands)
    x, iters, res, conv, code, fail_iter = out[:6]
    info = {"fail_code": code, "fail_iter": fail_iter}
    if armed:
        info["residual_history"] = out[6]
        info["iters_to_tol"] = out[7]
    return SolveResult(x, iters, res, conv, info)


def spmd_solve(method: Callable, a: jax.Array, b: jax.Array, mesh, *,
               x0: jax.Array | None = None,
               tol: float = 1e-6, maxiter: int = 1000,
               precond: "precond_mod.Preconditioner | None" = None,
               **extra):
    """Run a single-source Krylov driver with its ENTIRE iteration inside one
    ``shard_map`` (the MPI-faithful engine).  ``method`` is any driver from
    :mod:`repro.core.krylov` — the same code that runs on the dense engine.

    Preconditioner state crosses into the shard_map as extra sharded
    operands (see :func:`repro.core.precond.make`); custom callables cannot
    cross the shard_map boundary and are rejected.  ``x0`` (a warm start —
    the escalation policy's restart-from-best-iterate) enters as one more
    block-row-sharded operand.
    """
    row, col = dist.solver_axes(mesh)
    p, q = mesh.shape[row], mesh.shape[col]
    pkind, pdata = spmd_named_precond(precond, rows=a.shape[0], mesh_rows=p)
    pspecs = precond_mod.data_specs(pkind, row)

    if x0 is None:
        def body(a_loc, b_loc, *pdata_loc):
            op = SpmdLocalOperator(a_loc, row, col, q, p)
            apply_m = precond_mod.local_apply(pkind, pdata_loc)
            res = method(op, b_loc, tol=tol, maxiter=maxiter,
                         precond=apply_m, **extra)
            return result_leaves(res)

        return spmd_run(body, mesh, row, (P(row, col), P(row)) + pspecs,
                        a, b, *pdata)

    def body(a_loc, b_loc, x0_loc, *pdata_loc):
        op = SpmdLocalOperator(a_loc, row, col, q, p)
        apply_m = precond_mod.local_apply(pkind, pdata_loc)
        res = method(op, b_loc, x0_loc, tol=tol, maxiter=maxiter,
                     precond=apply_m, **extra)
        return result_leaves(res)

    return spmd_run(body, mesh, row, (P(row, col), P(row), P(row)) + pspecs,
                    a, b, x0, *pdata)


# --------------------------------------------------------------------------
# Batched (many independent systems, leading batch axis)
# --------------------------------------------------------------------------

class BatchedOperator(LinearOperator):
    """a: (B, n, n), vectors (B, n); solver scalars become (B,) vectors.
    The loop runs until EVERY system converges (``reduce_any``); per-system
    division guards in the drivers keep converged systems inert."""

    has_transpose = True
    supports_gram = False
    batched = True

    def __init__(self, a: jax.Array):
        if a.ndim != 3 or a.shape[-1] != a.shape[-2]:
            raise ValueError(f"batched operator wants (B, n, n), got {a.shape}")
        self.a = a

    def matvec(self, v):
        return inject.tap("matvec", jnp.einsum("bij,bj->bi", self.a, v))

    def matvec_t(self, v):
        return jnp.einsum("bji,bj->bi", self.a, v)

    def dot(self, u, v):
        return jnp.einsum("bi,bi->b", u.conj(), v)   # vdot semantics

    def scale(self, s, v):
        return jnp.asarray(s)[..., None] * v

    def reduce_any(self, mask):
        return jnp.any(mask)


# --------------------------------------------------------------------------
# Engine selection
# --------------------------------------------------------------------------

def make_operator(a: jax.Array, *, mesh=None,
                  backend: str = "ref") -> LinearOperator:
    """Pick the engine from the data: sparse → SparseOperator, batched
    (B,n,n) → BatchedOperator, mesh given → GspmdOperator, else
    DenseOperator(backend)."""
    if getattr(a, "is_sparse", False):
        if mesh is not None:
            raise ValueError("distributed sparse solves are block-row SPMD "
                             "— use engine='spmd' (repro.sparse.operator"
                             ".spmd_solve), not a gspmd operator")
        from repro.sparse.operator import SparseOperator
        return SparseOperator(a, backend=backend)
    if a.ndim == 3:
        if backend == "pallas":
            raise ValueError("backend='pallas' is dense-only (2-D A)")
        return BatchedOperator(a)
    if mesh is not None:
        if backend == "pallas":
            raise ValueError("backend='pallas' is single-device only; "
                             "drop mesh= or use backend='ref'")
        return GspmdOperator(a, mesh)
    return DenseOperator(a, backend=backend)
