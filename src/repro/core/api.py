"""CUPLSS level-4 user API (paper §3: "the parallelism is hidden from the
user" — one entry point, opaque distribution).

    >>> x = solve(a, b)                          # serial / single device
    >>> x = solve(a, b, method="gmres", mesh=m)  # distributed
    >>> r = solve(a, b, method="cg", return_info=True)   # full SolveResult
    >>> x = solve(a, b, method="cg", backend="pallas")   # fused hot loop

Methods live in a registry (``register_method``) — adding a solver is one
driver function written against the :class:`repro.core.operator
.LinearOperator` primitive set plus one registration line; it then runs on
every engine:

* ``engine="gspmd"``  — compiler-scheduled collectives (default),
* ``engine="spmd"``   — explicit collectives inside one ``shard_map``
  (MPI-faithful): every iterative method (preconditioned) runs its whole
  loop in one shard_map, and the direct methods run the block-cyclic
  distributed factorization (one shard_map-wrapped fori_loop; ScaLAPACK
  layout) plus distributed triangular substitutions,
* batched             — pass ``a`` of shape (B, n, n) and ``b`` (B, n);
  direct methods vmap their fixed-shape fori_loop factorizations,
* sparse              — pass a :class:`repro.sparse.BSR` / ``ELL`` matrix;
  every iterative method runs unchanged (matrix-free preconditioners
  included), distributed solves shard block rows through ``engine="spmd"``,
* ``backend="pallas"``— fused Pallas update kernels in the iterative hot
  loop, the scalar-prefetch SpMV kernel for BSR systems, and Pallas
  GEMM/TRSM/fused-panel kernels in the direct factorizations (all
  interpret-mode off-TPU).

Direct methods are registered with a factor/solve split
(``factor=``/``apply=``), which is what :func:`factorize` dispatches on.

Rectangular (m, n) systems are least squares and opt in explicitly:
``method="qr"`` (blocked Householder QR; distributed TSQR under
``engine="spmd"``) or ``method="lsqr"``/``"cgls"`` (iterative,
matrix-free — sparse matrices included).  Spectral problems go through
:func:`eigsolve` (Lanczos / Arnoldi on the same operator engine).
"""
from __future__ import annotations

import dataclasses
import functools
import time as _time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as _np

from repro.core import blocking as _blocking
from repro.core import cholesky as _chol
from repro.core import dist, krylov, lu as _lu, operator as _operator
from repro.core import precond as _precond
from repro.core import qr as _qr
from repro.core.blocking import BACKENDS
from repro.core.krylov import SolveResult
from repro.resilience import monitor as _monitor
from repro.telemetry import convergence as _conv
from repro.telemetry import perf as _perf
from repro.telemetry import trace as _trace

ENGINES = ("gspmd", "spmd")

# capabilities of the explicit-SPMD local operator (checked pre-shard_map,
# since the operator itself only exists inside the shard_map body)
_SPMD_CAPS = frozenset({"matvec_t", "gram"})

# Matrix products of every solve, factorization and substitution.  At
# XLA's default TPU precision an f32 product is one bf16 pass, and f32
# solves then fail HPL's residual check (docs/solvers.md, "Precision");
# CPU and GPU compute f32 products in f32 either way.
MATMUL_PRECISION = "highest"


def _at_precision(fn: Callable) -> Callable:
    """``fn`` traced and run under :data:`MATMUL_PRECISION` (the jit
    caches key on it, so it holds for every trace the call makes)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return fn(*args, **kwargs)
    return wrapped


@dataclasses.dataclass(frozen=True)
class SolverEntry:
    name: str
    fn: Callable
    kind: str = "iterative"       # "iterative" | "direct"
    requires: tuple = ()          # subset of {"matvec_t", "gram"}
    extra: tuple = ()             # accepted solver-specific kwargs
    factor: Callable | None = None   # direct: a -> opaque factor state
    apply: Callable | None = None    # direct: (state, b) -> x
    spmd_factor: Callable | None = None  # direct, engine="spmd" split
    spmd_apply: Callable | None = None
    rectangular: bool = False        # accepts (m, n) m != n (least squares)


_REGISTRY: dict[str, SolverEntry] = {}


def register_method(name: str, fn: Callable, *, kind: str = "iterative",
                    requires: tuple = (), extra: tuple = (),
                    factor: Callable | None = None,
                    apply: Callable | None = None,
                    spmd_factor: Callable | None = None,
                    spmd_apply: Callable | None = None,
                    rectangular: bool = False) -> SolverEntry:
    """Register a solver.  Iterative ``fn(op, b, *, tol, maxiter, precond,
    **extra) -> SolveResult``.  Direct methods register a factor/solve
    split: ``factor(a, *, block_size, mesh, backend) -> state`` and
    ``apply(state, b, *, block_size, mesh, backend) -> x`` (``fn`` remains
    the one-shot convenience composition), plus optionally the distributed
    pair ``spmd_factor``/``spmd_apply`` (same signatures; mesh required)
    that ``engine="spmd"`` dispatches to — one shard_map-wrapped
    block-cyclic factorization.  Re-registering a name overwrites it (lets
    users swap implementations)."""
    if kind == "direct" and (factor is None) != (apply is None):
        raise ValueError(f"direct method {name!r} needs BOTH factor= and "
                         "apply= (or neither)")
    if (spmd_factor is None) != (spmd_apply is None):
        raise ValueError(f"method {name!r} needs BOTH spmd_factor= and "
                         "spmd_apply= (or neither)")
    entry = SolverEntry(name, fn, kind=kind, requires=tuple(requires),
                        extra=tuple(extra), factor=factor, apply=apply,
                        spmd_factor=spmd_factor, spmd_apply=spmd_apply,
                        rectangular=rectangular)
    _REGISTRY[name] = entry
    return entry


def _spmd_direct_methods() -> tuple[str, ...]:
    return tuple(sorted(n for n, e in _REGISTRY.items()
                        if e.kind == "direct" and e.spmd_factor is not None))


def get_method(name: str) -> SolverEntry:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown method {name!r}; available: "
                         f"{sorted(_REGISTRY)}") from None


def available_methods(kind: str | None = None) -> tuple[str, ...]:
    return tuple(sorted(n for n, e in _REGISTRY.items()
                        if kind is None or e.kind == kind))


def register_fallback(method: str, fallback: str | None) -> None:
    """Set the ``policy="resilient"`` escalation target for ``method``
    (None removes it).  Thin forwarder to
    :func:`repro.resilience.policy.register_fallback` — imported lazily,
    the policy layer sits above this module."""
    from repro.resilience import policy as _rpolicy
    _rpolicy.register_fallback(method, fallback)


# the TSQR pair is imported lazily: repro.eigls sits above the core
# package, so module-level registration must not pull it in at import time
def _tsqr_factor(a, **kw):
    from repro.eigls import tsqr
    return tsqr.tsqr_factor_spmd(a, **kw)


def _tsqr_apply(state, b, **kw):
    from repro.eigls import tsqr
    return tsqr.tsqr_apply_spmd(state, b, **kw)


register_method("lu", _lu.solve, kind="direct",
                factor=_lu.lu_factor, apply=_lu.lu_apply,
                spmd_factor=_lu.lu_factor_spmd,
                spmd_apply=_lu.lu_apply_spmd)
register_method("cholesky", _chol.solve, kind="direct",
                factor=_chol.cholesky_factor_state, apply=_chol.cholesky_apply,
                spmd_factor=_chol.cholesky_factor_spmd,
                spmd_apply=_chol.cholesky_apply_spmd)
register_method("qr", _qr.solve, kind="direct", rectangular=True,
                factor=_qr.qr_factor_state, apply=_qr.qr_apply,
                spmd_factor=_tsqr_factor, spmd_apply=_tsqr_apply)
register_method("cg", krylov.cg)
register_method("pipelined_cg", krylov.pipelined_cg)
register_method("bicg", krylov.bicg, requires=("matvec_t",))
register_method("bicgstab", krylov.bicgstab)
register_method("gmres", krylov.gmres, requires=("gram",),
                extra=("restart",))
register_method("ca_cg", krylov.ca_cg, requires=("gram",), extra=("s",))
register_method("ca_gmres", krylov.ca_gmres, requires=("gram",),
                extra=("s",))
register_method("lsqr", krylov.lsqr, requires=("matvec_t",),
                rectangular=True)
register_method("cgls", krylov.cgls, requires=("matvec_t",),
                rectangular=True)

# kept as module-level introspection helpers (historical names)
DIRECT = available_methods("direct")
ITERATIVE = available_methods("iterative")


def _validate_inputs(a, b, method: str, sparse: bool) -> None:
    """Reject inputs no solver can recover from, with a pointer to the
    fix.  Concrete arrays only — inside jit everything is a tracer and
    the checks vanish (zero jaxpr overhead)."""
    vals = a.data if sparse else a
    for name, arr in (("a", vals), ("b", b)):
        if arr is None or isinstance(arr, jax.core.Tracer):
            continue
        if not bool(jnp.all(jnp.isfinite(jnp.asarray(arr)))):
            raise ValueError(
                f"{name!r} contains non-finite entries (NaN/Inf) — no "
                "solver can recover from a corrupted input; scrub it "
                "(jnp.nan_to_num) or fix the producing computation")
    if method == "cholesky" and not sparse \
            and not isinstance(a, jax.core.Tracer) \
            and getattr(a, "ndim", 0) == 2 and a.shape[0] == a.shape[1]:
        aj = jnp.asarray(a)
        d = jnp.diagonal(aj)
        if bool(jnp.any(d <= 0)):
            raise ValueError(
                "method='cholesky' needs an SPD matrix but the diagonal "
                "has non-positive entries — use method='lu' (general "
                "square systems) or fix the matrix assembly")
        asym = float(jnp.max(jnp.abs(aj - aj.T)))
        scale = float(jnp.max(jnp.abs(aj)))
        if asym > 1e-8 * max(scale, 1.0):
            raise ValueError(
                f"method='cholesky' needs a symmetric matrix but "
                f"max|A - Aᵀ| = {asym:.3e} — symmetrize with "
                "(a + a.T)/2 or use method='lu'")


def _info_schema(res, atol) -> dict:
    """The uniform info dict of a direct solve: the same
    ``fail_code``/``fail_iter`` keys the monitored iterative drivers
    emit (a factorization that returned is code OK at iteration 0), plus
    the convergence-history keys when a telemetry session is armed (a
    direct solve's "history" is its single final residual)."""
    zero = jnp.zeros(jnp.shape(res.residual), jnp.int32)
    info = {"fail_code": zero, "fail_iter": zero}
    if _conv.armed():
        info["residual_history"] = jnp.asarray(res.residual)[None]
        info["iters_to_tol"] = jnp.where(res.residual <= atol, 0, -1
                                         ).astype(jnp.int32)
    return info


def _with_fail_reason(result: SolveResult) -> SolveResult:
    """Uniform info schema: every ``return_info=True`` result carries
    ``fail_code`` / ``fail_iter`` / ``fail_reason``.  ``fail_reason`` is
    the host-side classification (``monitor.classify``) — ``None`` under
    tracing, where the code is an abstract value (``None`` is a
    zero-leaf pytree node, so jitted callers see no structure change
    between runs)."""
    info = dict(result.info) if result.info else {}
    code = info.get("fail_code")
    if code is None or isinstance(code, jax.core.Tracer):
        info["fail_reason"] = None
    else:
        arr = _np.asarray(code)
        info["fail_reason"] = _monitor.classify(int(arr)) if arr.ndim == 0 \
            else [_monitor.classify(int(c)) for c in arr.reshape(-1)]
    return result._replace(info=info)


@_at_precision
def _solve_impl(a: jax.Array, b: jax.Array, *, method: str = "lu",
                mesh=None, engine: str = "gspmd", backend: str = "ref",
                block_size: int = 128, tol: float = 1e-6,
                maxiter: int = 1000, restart: int = 32,
                precond: str | Callable | None = None,
                x0: jax.Array | None = None, policy: str | None = None,
                validate: bool = True, abft: bool = False,
                return_info: bool = False, **method_kwargs):
    """Dispatch core of :func:`solve` (same contract, no telemetry)."""
    entry = get_method(method)
    sparse_in = getattr(a, "is_sparse", False)
    if validate:
        _validate_inputs(a, b, method, sparse_in)
    if policy not in (None, "none", "resilient"):
        raise ValueError(f"unknown policy {policy!r}; expected "
                         "'resilient' (or None)")
    if policy == "resilient":
        from repro.resilience import policy as _rpolicy
        return _rpolicy.resilient_solve(
            a, b, method=method, mesh=mesh, engine=engine, backend=backend,
            block_size=block_size, tol=tol, maxiter=maxiter,
            restart=restart, precond=precond, x0=x0,
            return_info=return_info, **method_kwargs)
    unknown = set(method_kwargs) - set(entry.extra)
    if unknown:
        raise TypeError(f"method {method!r} does not accept "
                        f"{sorted(unknown)}; declared extras: "
                        f"{list(entry.extra)}")
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected {ENGINES}")
    # the distributed direct path runs the Pallas kernels per-shard, so
    # backend='pallas' + mesh is legal there (name check only)
    direct_spmd = entry.kind == "direct" and engine == "spmd"
    _blocking.check_backend(backend, None if direct_spmd else mesh)
    sparse = sparse_in
    if entry.kind == "direct" and x0 is not None:
        raise ValueError(f"x0 is an iterative-method initial guess; "
                         f"direct method {method!r} ignores it — drop x0 "
                         "or pick an iterative method")
    if abft and not (direct_spmd and method in ("lu", "cholesky")):
        raise ValueError(
            "abft=True is the distributed factorization checksum — it "
            "requires engine='spmd' with method='lu' or 'cholesky'")

    # -- non-square audit: least squares is an explicit opt-in -------------
    rect = len(a.shape) >= 2 and a.shape[-2] != a.shape[-1]
    if rect:
        if not entry.rectangular:
            raise ValueError(
                f"matrix is non-square {tuple(a.shape)}; method {method!r} "
                "solves square systems only — rectangular least squares: "
                "method='qr' (direct, TSQR under engine='spmd') or "
                "method='lsqr'/'cgls' (iterative, matrix-free)")
        if precond is not None:
            raise ValueError(
                "preconditioners are square-operator state; the "
                "least-squares path runs unpreconditioned (cgls accepts a "
                "normal-equations M via the driver API)")
        if engine == "spmd" and entry.kind != "direct":
            raise ValueError(
                "rectangular engine='spmd' is the TSQR factorization — "
                "use method='qr'; the iterative least-squares drivers run "
                "on engine='gspmd' (sharded or local)")

    if mesh is not None and not sparse:
        if a.ndim == 3:
            raise ValueError("batched solves are single-device (mesh=None)")
        if not direct_spmd:
            # the spmd direct path pads + lays out cyclically itself (a
            # non-block-multiple n cannot pre-shard on the 2-D layout)
            a = dist.shard_matrix(a, mesh)
            b = dist.shard_vector(b, mesh)
            if x0 is not None:
                x0 = dist.shard_vector(x0, mesh)

    if entry.kind == "direct":
        if sparse:
            raise ValueError(f"direct method {method!r} is dense-only; "
                             "sparse systems use the iterative methods "
                             "(or densify explicitly with a.to_dense())")
        kw = dict(block_size=block_size, mesh=mesh, backend=backend)
        if engine == "spmd":
            if mesh is None:
                raise ValueError("engine='spmd' requires a mesh")
            if entry.spmd_factor is None:
                raise ValueError(
                    f"direct method {method!r} has no distributed "
                    f"(engine='spmd') factorization; methods with one: "
                    f"{_spmd_direct_methods()} — engine='gspmd' runs any "
                    "direct method on sharded global arrays")
            if abft:
                from repro.resilience import abft as _abft
                state = entry.spmd_factor(a, abft=True, **kw)
                _abft.verify(state)       # raises FactorCorruption
            else:
                state = entry.spmd_factor(a, **kw)
            x = entry.spmd_apply(state, b, **kw)
        elif entry.factor is None:
            # legacy one-shot registration (no factor/apply split)
            if a.ndim == 3:
                raise ValueError(f"method {method!r} has no factor/apply "
                                 "split; batched direct solves need one")
            if backend != "ref":
                raise ValueError(f"method {method!r} has no factor/apply "
                                 f"split; backend={backend!r} unsupported")
            x = entry.fn(a, b, block_size=block_size, mesh=mesh)
        elif a.ndim == 3:
            # batched direct solve: vmap the fixed-shape fori_loop
            # factorization over the leading axis
            if b.ndim < 2 or b.shape[0] != a.shape[0]:
                raise ValueError(f"batched a {a.shape} needs b of shape "
                                 f"(B, n[, k]), got {b.shape}")
            x = jax.vmap(lambda A, B: entry.apply(
                entry.factor(A, **kw), B, **kw))(a, b)
        else:
            x = entry.apply(entry.factor(a, **kw), b, **kw)
        if not return_info:
            return x
        ax = a @ x if x.ndim == a.ndim else (a @ x[..., None])[..., 0]
        rvec, refvec = b - ax, b
        if rect:
            # least squares: ‖b − Ax‖ does not vanish at the solution —
            # report the normal-equations residual ‖Aᵀ(b − Ax)‖ instead
            at = jnp.swapaxes(a, -1, -2)
            proj = (lambda v: at @ v) if x.ndim == a.ndim else (
                lambda v: (at @ v[..., None])[..., 0])
            rvec, refvec = proj(rvec), proj(b)
        axis = None if a.ndim == 2 else tuple(range(1, rvec.ndim))
        res = jnp.linalg.norm(rvec, axis=axis)
        bnorm = jnp.linalg.norm(refvec, axis=axis)
        atol = tol * jnp.where(bnorm == 0, 1.0, bnorm)
        iters = jnp.zeros(res.shape, jnp.int32) if a.ndim == 3 \
            else jnp.asarray(0)
        result = SolveResult(x, iters, res, res <= atol)
        return _with_fail_reason(
            result._replace(info=_info_schema(result, atol)))

    pc = _precond.make(precond, a, block_size)
    extra = {"restart": restart} if "restart" in entry.extra else {}
    extra.update(method_kwargs)

    if engine == "spmd":
        if mesh is None:
            raise ValueError("engine='spmd' requires a mesh")
        if backend == "pallas":
            raise ValueError("backend='pallas' is single-device only; "
                             "engine='spmd' runs the ref update")
        missing = set(entry.requires) - _SPMD_CAPS
        if missing:
            raise ValueError(f"method {method!r} needs {sorted(missing)} "
                             "which the spmd engine lacks")
        if sparse:
            from repro.sparse import operator as _sparse_operator
            result = _sparse_operator.spmd_solve(
                entry.fn, a, b, mesh, x0=x0, tol=tol, maxiter=maxiter,
                precond=pc, **extra)
        else:
            result = _operator.spmd_solve(entry.fn, a, b, mesh, x0=x0,
                                          tol=tol, maxiter=maxiter,
                                          precond=pc, **extra)
    else:
        op = _operator.make_operator(a, mesh=mesh, backend=backend)
        if "matvec_t" in entry.requires and not op.has_transpose:
            raise ValueError(f"method {method!r} needs Aᵀx on this engine")
        if "gram" in entry.requires and not op.supports_gram:
            raise ValueError(f"method {method!r} does not support batching")
        op.prepare(entry.requires)
        result = entry.fn(op, b, x0, tol=tol, maxiter=maxiter,
                          precond=pc.apply if pc is not None else None,
                          **extra)
    return _with_fail_reason(result) if return_info else result.x


def _record_solve(sess, a, method, engine, backend, out) -> None:
    """Append a per-solve record to the session (concrete values only —
    under jit the result is tracers and the record stays shape-only)."""
    n = int(a.shape[-1]) if getattr(a, "shape", None) else 0
    dtype = str(getattr(a, "dtype", "?"))
    rec = {"method": method, "engine": engine, "backend": backend,
           "n": n, "dtype": dtype,
           "key": f"{method}/{engine}/{backend}/n{n}/{dtype}"}
    if isinstance(out, SolveResult) and not isinstance(out.x,
                                                       jax.core.Tracer):
        try:
            rec["iterations"] = int(jnp.max(out.iterations))
            rec["residual"] = float(jnp.max(out.residual))
            rec["converged"] = bool(jnp.all(out.converged))
            info = out.info or {}
            itt = info.get("iters_to_tol")
            if itt is not None and not isinstance(itt, jax.core.Tracer):
                rec["iters_to_tol"] = int(jnp.max(jnp.asarray(itt)))
            if info.get("fail_reason") is not None:
                rec["fail_reason"] = info["fail_reason"]
        except Exception:       # never let bookkeeping sink a solve
            pass
    sess.record_solve(**rec)


def solve(a: jax.Array, b: jax.Array, *, method: str = "lu",
          mesh=None, engine: str = "gspmd", backend: str = "ref",
          block_size: int = 128, tol: float = 1e-6, maxiter: int = 1000,
          restart: int = 32, precond: str | Callable | None = None,
          x0: jax.Array | None = None, policy: str | None = None,
          validate: bool = True, abft: bool = False,
          return_info: bool = False, **method_kwargs):
    """Solve A x = b.  Returns x, or the full :class:`SolveResult`
    (iterations / residual / converged / info) when ``return_info=True``.
    ``**method_kwargs`` forwards solver-specific options declared in the
    method's registry ``extra`` tuple (anything else is a TypeError).

    ``return_info=True`` results always carry the uniform info schema
    ``fail_code`` / ``fail_iter`` / ``fail_reason`` (see
    docs/observability.md); under an armed
    ``telemetry.session()`` they additionally carry
    ``residual_history`` / ``iters_to_tol``, and the solve is recorded
    as a span (``solve`` → ``dispatch``/``execute``) plus a per-solve
    convergence record.  With no session armed the telemetry layer adds
    ZERO overhead — one module-global check, identical jaxprs.

    Resilience knobs (all off by default, zero overhead when off):

    * ``x0`` — initial guess for the iterative methods (all engines);
    * ``policy="resilient"`` — classify failures (health monitor, ABFT,
      residual audit) and escalate: restart from the best iterate, drop
      pallas→ref, walk the registered method fallback chain
      (:func:`register_fallback`); the attempt history rides out in
      ``SolveResult.info["attempts"]``;
    * ``validate`` — reject non-finite / structurally unusable concrete
      inputs up front (skipped under jit, where inputs are tracers);
    * ``abft=True`` — carry the Huang–Abraham checksum column through
      the distributed factorization (``engine='spmd'`` lu/cholesky) and
      verify it at factor exit, raising
      :class:`repro.resilience.abft.FactorCorruption` on mismatch.
    """
    kw = dict(method=method, mesh=mesh, engine=engine, backend=backend,
              block_size=block_size, tol=tol, maxiter=maxiter,
              restart=restart, precond=precond, x0=x0, policy=policy,
              validate=validate, abft=abft, return_info=return_info,
              **method_kwargs)
    sess = _trace.active()
    if sess is None:
        return _solve_impl(a, b, **kw)
    attrs = {"method": method, "engine": engine, "backend": backend,
             "n": int(a.shape[-1]) if getattr(a, "shape", None) else 0}
    if policy:
        attrs["policy"] = policy
    obs = sess.perf
    with _trace.span("solve", **attrs):
        pexec = None
        with _trace.span("dispatch"):
            if obs is not None and obs.eligible(a, b, kw):
                # the observatory's AOT path: the whole solve becomes
                # ONE compiled executable there is an artifact to
                # analyze.  Validation normally runs eagerly inside
                # _solve_impl but vanishes under jit — run it here so
                # the routed path rejects the same inputs.
                if validate:
                    _validate_inputs(a, b, method,
                                     getattr(a, "is_sparse", False))
                # return_info=True inside the executable: the iteration
                # count is computed by the loop either way, and the
                # attribution needs it to scale the while-trip model to
                # the iterations that actually ran
                jkw = dict(kw, validate=False, return_info=True)
                pexec = obs.prepare(
                    a, b, jkw,
                    lambda: jax.jit(lambda A, B: _solve_impl(A, B, **jkw)),
                    kind=get_method(method).kind)
            # time enqueue + wait together: on synchronous backends
            # (CPU) the work happens inside the call, so the execute
            # span alone under-measures by the whole device time
            t0 = _time.perf_counter()
            out = pexec.fn(a, b) if pexec is not None \
                else _solve_impl(a, b, **kw)
        with _trace.span("execute"):
            arrivals = _perf.shard_arrivals(out) if pexec is not None \
                else None
            out = _trace.block(out)
            t_run = _time.perf_counter() - t0
        _record_solve(sess, a, method, engine, backend, out)
        if pexec is not None and sess.solves:
            try:
                obs.attribute(sess.solves[-1], pexec, t_run, arrivals)
            except Exception:       # attribution must never sink a solve
                pass
        if pexec is not None and not return_info \
                and isinstance(out, SolveResult):
            out = out.x
    return out


def make_executable(*, method: str = "lu", mode: str = "solve",
                    batch: int | None = None, engine: str = "gspmd",
                    backend: str = "ref", block_size: int = 128,
                    tol: float = 1e-6, maxiter: int = 1000,
                    restart: int = 32, precond: str | None = None,
                    **method_kwargs) -> Callable:
    """Build a jit-compiled solve executable with every dispatch decision
    baked into a static closure — the cache-aware hook the serving layer
    (:mod:`repro.serve.cache`) keys on
    ``(method, engine, backend, padded shape, dtype, precond spec)``.

    * ``mode="solve"``  — ``fn(a, b) -> SolveResult`` (any method; batched
      ``(B, n, n)`` inputs go through the normal vmap/BatchedOperator
      dispatch),
    * ``mode="factor"`` — ``fn(a) -> state`` (direct methods with a
      factor/apply split; ``batch=B`` vmaps over a leading axis),
    * ``mode="apply"``  — ``fn(state, b) -> x`` (the matching solve half;
      states stack/slice as pytrees, so a cached per-request factor can
      be re-batched under a different ``batch=``).

    The returned callable is a plain ``jax.jit`` function: the first call
    with a given shape/dtype compiles, later calls reuse the executable.
    For eager prefill, pair with jax.jit's AOT path
    (``fn.lower(*shaped_args).compile()`` — what
    :meth:`repro.serve.cache.ExecutableCache.warm` does).  Single-process
    only (``mesh=`` solves dispatch through :func:`solve`).
    """
    entry = get_method(method)
    if precond is not None and not isinstance(precond, str):
        raise ValueError(
            "executables are keyed on the precond *spec*; pass a string "
            "('jacobi', 'block_jacobi', 'ssor') — callables are not "
            "cache-keyable")
    if mode == "solve":
        kw = dict(method=method, engine=engine, backend=backend,
                  block_size=block_size, tol=tol, maxiter=maxiter,
                  restart=restart, precond=precond, validate=False,
                  return_info=True, **method_kwargs)
        return jax.jit(lambda a, b: _solve_impl(a, b, **kw))
    if mode not in ("factor", "apply"):
        raise ValueError(f"unknown mode {mode!r}; expected "
                         "'solve' | 'factor' | 'apply'")
    if entry.kind != "direct" or entry.factor is None:
        raise ValueError(f"mode={mode!r} needs a direct method with a "
                         f"factor/apply split; available: "
                         f"{tuple(n for n, e in sorted(_REGISTRY.items()) if e.factor is not None)}")
    fkw = dict(block_size=block_size, mesh=None, backend=backend)
    if mode == "factor":
        factor = lambda a: _at_precision(entry.factor)(a, **fkw)
        return jax.jit(factor if batch is None else jax.vmap(factor))
    apply = lambda s, b: _at_precision(entry.apply)(s, b, **fkw)
    return jax.jit(apply if batch is None else jax.vmap(apply))


@_at_precision
def _factorize_impl(a: jax.Array, *, method: str = "lu", mesh=None,
                    block_size: int = 128, backend: str = "ref",
                    engine: str = "gspmd", validate: bool = True,
                    abft: bool = False):
    if getattr(a, "is_sparse", False):
        raise ValueError("factorize is dense-only; sparse systems use the "
                         "iterative methods (or densify with a.to_dense())")
    entry = get_method(method)
    if validate:
        _validate_inputs(a, None, method, False)
    if abft and not (engine == "spmd" and method in ("lu", "cholesky")):
        raise ValueError(
            "abft=True is the distributed factorization checksum — it "
            "requires engine='spmd' with method='lu' or 'cholesky'")
    with_split = tuple(sorted(n for n, e in _REGISTRY.items()
                              if e.kind == "direct" and e.factor is not None))
    if entry.kind != "direct":
        raise ValueError(f"factorize needs a direct method; {method!r} is "
                         f"{entry.kind}; available: {with_split}")
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected {ENGINES}")
    # spmd dispatch happens before the local-split check: a method may
    # legitimately register ONLY the distributed pair
    if engine == "spmd":
        if mesh is None:
            raise ValueError("engine='spmd' requires a mesh")
        if entry.spmd_factor is None:
            raise ValueError(
                f"direct method {method!r} has no distributed "
                f"(engine='spmd') factorization; methods with one: "
                f"{_spmd_direct_methods()}")
        _blocking.check_backend_name(backend)
        if a.ndim == 3:
            raise ValueError("batched solves are single-device (mesh=None)")
        fkw = dict(block_size=block_size, mesh=mesh, backend=backend)
        if abft:
            from repro.resilience import abft as _abft
            state = entry.spmd_factor(a, abft=True, **fkw)
            _abft.verify(state)           # raises FactorCorruption
        else:
            state = entry.spmd_factor(a, **fkw)
        return functools.partial(_at_precision(entry.spmd_apply), state,
                                 **fkw)
    if entry.factor is None:
        raise ValueError(f"direct method {method!r} has no factor/apply "
                         f"split; methods with one: {with_split}")
    _blocking.check_backend(backend, mesh)
    if a.ndim == 3:
        if mesh is not None:
            raise ValueError("batched solves are single-device (mesh=None)")
        kw = dict(block_size=block_size, mesh=None, backend=backend)
        state = jax.vmap(lambda A: entry.factor(A, **kw))(a)
        return _at_precision(lambda b: jax.vmap(
            lambda s, B: entry.apply(s, B, **kw))(state, b))
    if mesh is not None:
        a = dist.shard_matrix(a, mesh)
    state = entry.factor(a, block_size=block_size, mesh=mesh, backend=backend)
    return functools.partial(_at_precision(entry.apply), state,
                             block_size=block_size, mesh=mesh,
                             backend=backend)


def factorize(a: jax.Array, *, method: str = "lu", mesh=None,
              block_size: int = 128, backend: str = "ref",
              engine: str = "gspmd", validate: bool = True,
              abft: bool = False):
    """Factor once, solve many (paper's two-step direct method, step 1).

    Any method registered with ``kind="direct"`` and a factor/apply split
    works; the returned callable maps ``b -> x``.  Batched ``a`` of shape
    (B, n, n) returns a solver over (B, n[, k]) right-hand sides.
    ``engine="spmd"`` (mesh required) factors once with the block-cyclic
    distributed factorization; the returned solver runs the distributed
    substitutions against the sharded factor state.  ``abft=True``
    (engine='spmd' lu/cholesky) carries the checksum column and verifies
    it at factor exit — see :func:`solve`.  Under an armed
    ``telemetry.session()`` the factorization records a
    ``factorize`` → ``dispatch``/``execute`` span pair.
    """
    kw = dict(method=method, mesh=mesh, block_size=block_size,
              backend=backend, engine=engine, validate=validate, abft=abft)
    sess = _trace.active()
    if sess is None:
        return _factorize_impl(a, **kw)
    with _trace.span("factorize", method=method, engine=engine,
                     backend=backend,
                     n=int(a.shape[-1]) if getattr(a, "shape", None) else 0):
        with _trace.span("dispatch"):
            out = _factorize_impl(a, **kw)
        with _trace.span("execute"):
            # the factor state rides inside the returned partial; block
            # on it so "execute" reflects device time, not enqueue time
            _trace.block(getattr(out, "args", None))
    return out


@_at_precision
def eigsolve(a, k: int = 6, *, which: str = "LA", method: str = "lanczos",
             mesh=None, backend: str = "ref", ncv=None, v0=None,
             tol: float = 1e-8, n=None, dtype=None, validate: bool = True):
    """Compute ``k`` eigenpairs of ``a`` — the spectral half of the
    level-4 API.  Same opaque-engine contract as :func:`solve`: dense /
    sparse (BSR, matrix-free) / operator / bare-matvec inputs,
    ``mesh=`` for the GSPMD-sharded engine, ``backend="pallas"`` for the
    fused kernels, and a method registry
    (:func:`repro.eigls.eigen.register_eig_method`) holding ``"lanczos"``
    (symmetric/SPD) and ``"arnoldi"`` (general).  Returns an
    :class:`repro.eigls.eigen.EigResult`.
    """
    from repro.eigls import eigen
    if validate and (getattr(a, "is_sparse", False)
                     or getattr(a, "ndim", None) == 2):
        _validate_inputs(a, v0, method, getattr(a, "is_sparse", False))
    kw = {} if dtype is None else {"dtype": dtype}
    sess = _trace.active()
    if sess is None:
        return eigen.eigsolve(a, k, which=which, method=method, mesh=mesh,
                              backend=backend, ncv=ncv, v0=v0, tol=tol, n=n,
                              **kw)
    with _trace.span("eigsolve", method=method, backend=backend, k=k,
                     n=n if n is not None
                     else (int(a.shape[-1]) if getattr(a, "shape", None)
                           else 0)):
        with _trace.span("dispatch"):
            out = eigen.eigsolve(a, k, which=which, method=method,
                                 mesh=mesh, backend=backend, ncv=ncv,
                                 v0=v0, tol=tol, n=n, **kw)
        with _trace.span("execute"):
            out = _trace.block(out)
    return out
