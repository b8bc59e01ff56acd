"""Least-squares & eigenvalue quickstart: rectangular solves three ways
(blocked Householder QR, TSQR, LSQR) and matrix-free Lanczos on a stencil.

    PYTHONPATH=src python examples/lstsq_eig.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.sparse import BSR, problems
from repro.launch import mesh as mesh_lib

# an overdetermined (m, n) system: least squares min ||b - A x||
rng = np.random.default_rng(0)
m, n = 2048, 256
a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
b = jnp.asarray(rng.standard_normal(m).astype(np.float32))
xo = np.linalg.lstsq(np.asarray(a), np.asarray(b), rcond=None)[0]

# direct: blocked Householder QR (compact-WY fori_loop; backend="pallas"
# fuses the panel update into one kernel launch)
x = api.solve(a, b, method="qr", backend="pallas")
print(f"qr (pallas)   |x - x*| = {np.abs(np.asarray(x) - xo).max():.2e}")

# factor once, solve many — the same two-step contract as LU/Cholesky
solver = api.factorize(a, method="qr")
x = solver(b)
print(f"qr factorize  |x - x*| = {np.abs(np.asarray(x) - xo).max():.2e}")

# distributed: communication-avoiding TSQR inside ONE shard_map
mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
x = api.solve(a, b, method="qr", engine="spmd", mesh=mesh)
print(f"tsqr (spmd)   |x - x*| = {np.abs(np.asarray(x) - xo).max():.2e}")

# iterative & matrix-free: LSQR / CGLS need only matvec + matvec_t, so
# sparse rectangular systems solve without densifying
d = rng.standard_normal((m, n)).astype(np.float32)
d[np.abs(d) < 1.0] = 0
bsr = BSR.from_dense(d, block_size=16)                 # rectangular BSR
r = api.solve(bsr, b, method="lsqr", tol=1e-5, maxiter=300,
              return_info=True)
xs = np.linalg.lstsq(d, np.asarray(b), rcond=None)[0]
print(f"lsqr (BSR)    |x - x*| = {np.abs(np.asarray(r.x) - xs).max():.2e} "
      f"iters={int(r.iterations)}")

# eigenvalues: Lanczos on the 2-D Poisson stencil, matrix-free (the SpMV
# kernel is the hot loop under backend="pallas")
pa = problems.poisson_2d(48)                           # n = 2304
pb = BSR.from_dense(pa, block_size=16)
res = api.eigsolve(pb, k=5, which="LA", ncv=200)
wtrue = np.linalg.eigvalsh(pa.astype(np.float64))[::-1][:5]
got = np.sort(np.asarray(res.eigenvalues))[::-1]
print(f"lanczos top-5 λ = {np.round(got, 5)}")
print(f"       vs eigh  = {np.round(wtrue, 5)}  "
      f"(max err {np.abs(got - wtrue).max():.1e})")

# general (non-symmetric) spectra go through Arnoldi — the same Krylov
# core GMRES runs on
g = rng.standard_normal((400, 400)).astype(np.float32) / 20.0
res = api.eigsolve(jnp.asarray(g), k=3, which="LM", method="arnoldi",
                   ncv=120)
print(f"arnoldi |λ|   = {np.round(np.abs(np.asarray(res.eigenvalues)), 4)}")
