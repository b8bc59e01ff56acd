"""Distributed tall-skinny QR (TSQR) — the communication-avoiding
factorization for the least-squares path (Demmel et al., "Communication-
optimal parallel and sequential QR and LU factorizations").

Layout: block *rows* of the (m, n) matrix sharded over the flattened
process ring (both mesh axes jointly — the same row-major flatten as the
block-cyclic direct path).  Everything happens inside ONE ``shard_map``:

1. every process QR-factors its local (m/P, n) row block —
   communication-free, the whole point of TSQR;
2. the P small (n, n) R factors are combined in one ``all_gather``
   (the flat-tree reduction — at these P the classic binary tree and the
   flat tree move the same bytes per link, and one collective beats
   log₂P latency-bound rounds on a TPU mesh);
3. every process QR-factors the stacked (P·n, n) R pile *replicated*
   (tiny, and lockstep keeps the sign canonicalization identical
   everywhere), then reconstitutes its slice of the global thin Q with
   one local GEMM.

The result is canonicalized to a non-negative R diagonal, which makes
the factorization *unique* — the distributed factor equals the local
:func:`repro.core.qr.reduced` factor to rounding, which is what the
parity battery asserts.

Registered as the ``spmd_factor=``/``spmd_apply=`` pair of
``method="qr"``, so ``api.solve(a, b, method="qr", engine="spmd")`` and
``api.factorize(..., engine="spmd")`` run end to end: apply is one
shard_map computing ``Qᵀ b`` (local skinny GEMM + one psum) followed by
the blocked triangular R solve (Pallas-backed under
``backend="pallas"``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import blocking, dist, pblas


@dataclasses.dataclass(frozen=True)
class TsqrState:
    """Factor state: the thin Q (row-sharded over the flattened ring,
    zero rows for the row pad) and the replicated (n, n) R, both
    canonicalized to a non-negative R diagonal."""
    mesh: object
    q: jax.Array         # (m_pad, n) sharded P((row, col), None)
    r: jax.Array         # (n, n) replicated
    m0: int
    n0: int


def _canon_sign(r: jax.Array) -> jax.Array:
    s = jnp.where(jnp.diagonal(r) < 0, -1, 1).astype(r.dtype)
    return s


def _prep(a, mesh):
    if mesh is None:
        raise ValueError("TSQR (engine='spmd') requires a mesh; the local "
                         "blocked factorization is repro.core.qr")
    m, n = a.shape
    if m < n:
        raise ValueError(
            f"underdetermined system {a.shape} (m < n): the QR/TSQR path "
            "solves least squares for m >= n")
    procs = dist.nprocs(mesh)
    m_pad = -(-m // procs) * procs
    m_loc = m_pad // procs
    if m_loc < n:
        raise ValueError(
            f"TSQR needs a tall-skinny local block: m/P = {m_loc} < n = {n} "
            f"on the {procs}-process ring — this matrix is not tall enough "
            "to row-shard; use the local path (engine='gspmd', mesh=None) "
            "or fewer devices")
    if m_pad != m:
        a = jnp.pad(a, ((0, m_pad - m), (0, 0)))   # zero rows: R unchanged
    return a, m_pad


def tsqr(a: jax.Array, mesh) -> tuple[jax.Array, jax.Array]:
    """Distributed thin QR: (m, n) -> (Q sharded (m, n), R (n, n)),
    canonical non-negative R diagonal.  ONE shard_map."""
    m0, n0 = a.shape
    state = tsqr_factor_spmd(a, mesh=mesh)
    return state.q[:m0], state.r


def tsqr_factor_spmd(a: jax.Array, *, block_size: int = 128, mesh=None,
                     backend: str = "ref") -> TsqrState:
    """Registry ``spmd_factor`` entry for ``method="qr"``."""
    blocking.check_backend_name(backend)
    m0, n0 = a.shape
    a, m_pad = _prep(a, mesh)
    row, col = dist.solver_axes(mesh)
    axes = (row, col)
    q = mesh.shape[col]
    n = n0

    def body(a_loc):
        # 1. local QR of my row block (communication-free)
        q1, r1 = jnp.linalg.qr(a_loc)                  # (m_loc, n), (n, n)
        # 2. flat-tree reduction: one all_gather of the P small Rs
        rstack = jax.lax.all_gather(r1, axes, tiled=True)   # (P*n, n)
        # 3. replicated QR of the R pile + canonical sign
        q2, r2 = jnp.linalg.qr(rstack)                 # (P*n, n), (n, n)
        s = _canon_sign(r2)
        r2 = r2 * s[:, None]
        q2 = q2 * s[None, :]
        # 4. reconstitute my slice of the global thin Q: one local GEMM
        d = pblas.flat_index_local(row, col, q)
        mine = jax.lax.dynamic_slice_in_dim(q2, d * n, n)
        return q1 @ mine, r2

    f = shard_map(body, mesh=mesh, in_specs=(P((row, col), None),),
                  out_specs=(P((row, col), None), P()), check_vma=False)
    q_glob, r = f(a)
    return TsqrState(mesh=mesh, q=q_glob, r=r, m0=m0, n0=n0)


def tsqr_apply_spmd(state: TsqrState, b: jax.Array, *,
                    block_size: int = 128, mesh=None,
                    backend: str = "ref") -> jax.Array:
    """Registry ``spmd_apply``: least-squares solve from a TSQR factor —
    ``Qᵀ b`` in one shard_map (local skinny GEMM + one psum), then the
    blocked R solve."""
    from repro.core.triangular import solve_upper_blocked
    mesh = state.mesh
    row, col = dist.solver_axes(mesh)
    m_pad = state.q.shape[0]
    bp = blocking.pad_rhs(b, m_pad)
    bv, vec = (bp[:, None], True) if bp.ndim == 1 else (bp, False)

    def body(q_loc, b_loc):
        return jax.lax.psum(q_loc.T @ b_loc, (row, col))

    qtb = shard_map(body, mesh=mesh,
                    in_specs=(P((row, col), None), P((row, col), None)),
                    out_specs=P(), check_vma=False)(state.q, bv)
    x = solve_upper_blocked(state.r, qtb, block_size=block_size,
                            backend=backend)
    return x[:, 0] if vec else x


def solve_spmd(a: jax.Array, b: jax.Array, *, block_size: int = 128,
               mesh=None, backend: str = "ref") -> jax.Array:
    """One-shot distributed least-squares solve (TSQR factor + apply)."""
    state = tsqr_factor_spmd(a, block_size=block_size, mesh=mesh,
                             backend=backend)
    return tsqr_apply_spmd(state, b, block_size=block_size, mesh=mesh,
                           backend=backend)
