"""Level-3 data-distribution layer (paper: "data distribution model").

The paper (CUPLSS §3) distributes dense matrices over a *logical
bidimensional mesh of processors* and hides the distribution behind opaque
objects.  Here the 2-D process mesh is the last two axes of a ``jax.Mesh``
(named ``"data"`` = mesh rows, ``"model"`` = mesh columns) and the opaque
object is simply a global ``jax.Array`` carrying a ``NamedSharding`` — JAX's
global-view arrays play the role of PLSS's distributed-matrix descriptors.

Layouts
-------
* matrix  A : ``P(ROW_AXIS, COL_AXIS)``  — 2-D block distribution
* vector  x : ``P(ROW_AXIS)``            — block rows, replicated over columns
* scalar  s : ``P()``                    — replicated

``long``-lived solver state always stays in these layouts; conversions are
explicit (see ``pblas``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROW_AXIS = "data"   # mesh rows  (process-grid i)
COL_AXIS = "model"  # mesh cols  (process-grid j)


def solver_axes(mesh: Mesh) -> tuple[str, str]:
    """The (row, col) process-grid axes of ``mesh`` (its last two axes)."""
    names = mesh.axis_names
    if ROW_AXIS in names and COL_AXIS in names:
        return (ROW_AXIS, COL_AXIS)
    if len(names) >= 2:
        return (names[-2], names[-1])
    return (names[-1], names[-1])


def grid_shape(mesh: Mesh) -> tuple[int, int]:
    r, c = solver_axes(mesh)
    return (mesh.shape[r], mesh.shape[c])


def matrix_spec(mesh: Mesh) -> P:
    r, c = solver_axes(mesh)
    return P(r, c)


def vector_spec(mesh: Mesh) -> P:
    r, _ = solver_axes(mesh)
    return P(r)


def matrix_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, matrix_spec(mesh))


def vector_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, vector_spec(mesh))


def shard_matrix(a: jax.Array, mesh: Mesh) -> jax.Array:
    """Place a global (n, n) matrix in the 2-D block layout."""
    return jax.device_put(a, matrix_sharding(mesh))


def shard_vector(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Place a global (n,) vector in the block-row layout."""
    return jax.device_put(x, vector_sharding(mesh))


def constrain(x: jax.Array, mesh: Mesh, spec: P) -> jax.Array:
    """Sharding constraint that is a no-op outside jit / with trivial mesh."""
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_matrix(a: jax.Array, mesh: Mesh) -> jax.Array:
    return constrain(a, mesh, matrix_spec(mesh))


def constrain_vector(x: jax.Array, mesh: Mesh) -> jax.Array:
    return constrain(x, mesh, vector_spec(mesh))


def single_device_mesh() -> Mesh:
    """A (1, 1) mesh over the first device — lets every code path that wants a
    mesh run unchanged on one CPU device (tests)."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), (ROW_AXIS, COL_AXIS), devices=jax.devices()[:1])


def divisible(n: int, mesh: Mesh) -> bool:
    p, q = grid_shape(mesh)
    return n % p == 0 and n % q == 0


def pad_to_grid(a: jax.Array, mesh: Mesh) -> tuple[jax.Array, int]:
    """Pad an (n, n) system so both dims divide the process grid.  Padding is
    an identity extension (diag 1) so solves are unaffected; returns the
    padded matrix and the original n."""
    n = a.shape[0]
    p, q = grid_shape(mesh)
    block = p * q // _gcd(p, q) if (p and q) else 1
    m = -(-n // block) * block if block else n
    if m == n:
        return a, n
    pad = m - n
    a2 = jnp.zeros((m, m), a.dtype).at[:n, :n].set(a)
    a2 = a2.at[jnp.arange(n, m), jnp.arange(n, m)].set(jnp.ones((pad,), a.dtype))
    return a2, n


def _gcd(a: int, b: int) -> int:
    while b:
        a, b = b, a % b
    return a


# --------------------------------------------------------------------------
# Block-cyclic column layout (distributed direct path, ScaLAPACK-style)
# --------------------------------------------------------------------------
#
# The distributed factorizations flatten the 2-D process mesh into a 1-D
# ring of P = p·q processes and distribute COLUMN blocks cyclically:
# global block g lives on process g % P as its local block g // P.  Every
# process therefore owns full columns — the pivot search of the panel
# factorization is communication-free — and the cyclic assignment keeps
# the trailing-update work balanced as the factorization shrinks the
# active window (the reason ScaLAPACK is cyclic, not contiguous).
#
# ``shard_map`` hands each process a CONTIGUOUS chunk of the array, so the
# cyclic assignment is realized by a static column permutation: the global
# matrix is stored with process 0's blocks first, then process 1's, etc.
# (``colperm``), which makes chunk d exactly process d's cyclic block set.


@dataclasses.dataclass(frozen=True)
class CyclicLayout:
    """Static description of a block-cyclic column distribution.

    ``colperm`` maps permuted → original column index (``a_cyclic =
    a[:, colperm]``); ``inv_colperm`` undoes it (``x = x_cyclic[inv_colperm]``
    for column/solution vectors).  Both are concrete NumPy (the layout is
    static structure, like a BSR sparsity pattern).
    """
    mesh: Mesh
    nprocs: int        # P = p * q flattened processes
    nb: int            # block size
    n0: int            # logical system size
    n: int             # padded size (multiple of nb * P)
    colperm: np.ndarray
    inv_colperm: np.ndarray

    @property
    def nblocks(self) -> int:
        return self.n // self.nb

    def owner_of(self, s):
        """Flat ring rank owning global block column ``s`` (``s`` may be a
        traced loop index)."""
        return s % self.nprocs

    def slot_of(self, s):
        """Local block slot of global block column ``s`` on its owner."""
        return s // self.nprocs

    def local_gcol(self, d, nloc: int) -> jax.Array:
        """Global (natural-order) column index of each local column slot,
        for the process with (traced) flat index ``d`` — the inverse of
        the :func:`cyclic_col_perm` storage map, used inside shard_map
        bodies.  Local slot ``t*nb + w`` holds global column
        ``(d + t*P)*nb + w``."""
        t = jnp.arange(nloc) // self.nb
        return (d + t * self.nprocs) * self.nb + jnp.arange(nloc) % self.nb

    def matrix_spec(self) -> P:
        """Columns sharded jointly over both mesh axes (row-major flatten,
        matching ``flat_index_local``); rows fully local."""
        r, c = solver_axes(self.mesh)
        return P(None, (r, c))


def nprocs(mesh: Mesh) -> int:
    p, q = grid_shape(mesh)
    return p * q


def cyclic_col_perm(nblocks: int, nb: int, procs: int) -> np.ndarray:
    """Permuted → original column map putting each process's cyclic block
    set (g ≡ d mod P, ascending g) in one contiguous chunk."""
    order = [g for d in range(procs) for g in range(d, nblocks, procs)]
    return np.concatenate(
        [np.arange(g * nb, (g + 1) * nb) for g in order]) if order \
        else np.arange(0)


def cyclic_layout(mesh: Mesh, n0: int, n_pad: int, nb: int) -> CyclicLayout:
    procs = nprocs(mesh)
    if n_pad % (nb * procs):
        raise ValueError(f"padded size {n_pad} is not a multiple of "
                         f"nb*P = {nb}*{procs}")
    colperm = cyclic_col_perm(n_pad // nb, nb, procs)
    return CyclicLayout(mesh=mesh, nprocs=procs, nb=nb, n0=n0, n=n_pad,
                        colperm=colperm, inv_colperm=np.argsort(colperm))
