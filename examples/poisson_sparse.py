"""Sparse quickstart: 2-D Poisson → BSR → preconditioned pipelined CG.

The end-to-end workload the sparse subsystem exists for — a stencil
operator stored as nb×nb bricks, solved matrix-free with the
single-reduction pipelined CG and a block-SSOR preconditioner extracted
straight from the BSR structure (never densified).

    PYTHONPATH=src python examples/poisson_sparse.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.sparse import BSR, problems
from repro.launch import mesh as mesh_lib

# 5-point Laplacian on a 64×64 grid → n = 4096, five nonzeros per row
nx = 64
a_dense = problems.poisson_2d(nx)                  # concrete (structure!)
b = jnp.asarray(problems.smooth_rhs(nx * nx))
bsr = BSR.from_dense(a_dense, block_size=nx)
print(f"{bsr}  density={bsr.density:.3f}")

# every registered Krylov method runs on sparse A unchanged
r = api.solve(bsr, b, method="pipelined_cg", tol=1e-6, maxiter=4000,
              return_info=True)
print(f"pipelined_cg            iters={int(r.iterations)} "
      f"residual={float(r.residual):.2e}")

# matrix-free block-SSOR from the BSR bricks cuts the iteration count
r = api.solve(bsr, b, method="pipelined_cg", tol=1e-6, maxiter=4000,
              precond="ssor", return_info=True)
print(f"pipelined_cg + ssor     iters={int(r.iterations)} "
      f"residual={float(r.residual):.2e}")

# backend="pallas": the scalar-prefetch SpMV kernel in the hot loop
r = api.solve(bsr, b, method="pipelined_cg", tol=1e-6, maxiter=4000,
              precond="ssor", backend="pallas", return_info=True)
print(f"pallas backend          iters={int(r.iterations)} "
      f"residual={float(r.residual):.2e}")

# the O(nnz) vs O(n²) win at matched n
f_sparse = jax.jit(lambda m, v: api.solve(m, v, method="cg", tol=1e-6,
                                          maxiter=4000))
f_dense = jax.jit(lambda A, v: api.solve(A, v, method="cg", tol=1e-6,
                                         maxiter=4000))
aj = jnp.asarray(a_dense)
jax.block_until_ready(f_sparse(bsr, b)); jax.block_until_ready(f_dense(aj, b))
t0 = time.perf_counter(); jax.block_until_ready(f_sparse(bsr, b))
ts = time.perf_counter() - t0
t0 = time.perf_counter(); jax.block_until_ready(f_dense(aj, b))
td = time.perf_counter() - t0
print(f"cg wall: sparse {ts*1e3:.1f} ms vs dense {td*1e3:.1f} ms "
      f"({td/ts:.1f}x)")

# distributed: block rows shard over the mesh row axis (engine='spmd')
mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
x = api.solve(bsr, b, method="cg", tol=1e-6, mesh=mesh, engine="spmd",
              precond="block_jacobi")
err = float(np.linalg.norm(np.asarray(x) -
                           np.linalg.solve(a_dense.astype(np.float64),
                                           np.asarray(b))))
print(f"spmd block-row solve    |x - x*| = {err:.2e}")
