import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Multi-device battery, run in a subprocess by tests/test_multidevice.py
(so the main pytest process keeps its single-device view).

Covers on an 8-virtual-device mesh:
  1. distributed direct + iterative solvers vs the numpy oracle,
  2. explicit-SPMD (shard_map) solvers == GSPMD solvers, including the
     block-row-sharded sparse (BSR) engine,
  2b. least squares & eigenvalues: distributed TSQR == local blocked QR,
     LSQR on the sharded engine, Lanczos through the gspmd operator,
  3. SUMMA pgemm vs local matmul,
  4. checkpoint save of sharded LU factors → elastic restore onto a
     smaller mesh → identical factors and solution.
Prints "SELFTEST PASS" at the end; any assertion kills the process.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import api, dist, pblas
from repro.checkpoint import CheckpointManager
from repro.launch import mesh as mesh_lib


def check(name, ok):
    if not ok:
        raise AssertionError(f"selftest failed: {name}")
    print(f"  ok: {name}", flush=True)


def test_solvers(mesh):
    rng = np.random.default_rng(0)
    n = 256
    a = rng.standard_normal((n, n)).astype(np.float32) + n * np.eye(
        n, dtype=np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    spd = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
    x_lu = np.linalg.solve(a, b)
    x_sp = np.linalg.solve(spd, b)

    out = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu", mesh=mesh,
                    block_size=64)
    check("dist LU", np.allclose(out, x_lu, atol=1e-3))
    out = api.solve(jnp.asarray(spd), jnp.asarray(b), method="cholesky",
                    mesh=mesh, block_size=64)
    check("dist Cholesky", np.allclose(out, x_sp, atol=1e-3))
    # block-cyclic SPMD direct path (ONE shard_map factorization) == the
    # gspmd/local path (f64 parity battery: repro.launch.selftest_direct)
    for method, ref in (("lu", x_lu), ("cholesky", x_sp)):
        mat = a if method == "lu" else spd
        out = api.solve(jnp.asarray(mat), jnp.asarray(b), method=method,
                        mesh=mesh, engine="spmd", block_size=32)
        check(f"spmd direct {method} == oracle",
              np.allclose(out, ref, atol=1e-3))
    solver = api.factorize(jnp.asarray(spd), method="cholesky", mesh=mesh,
                           engine="spmd", block_size=32)
    check("spmd factorize reuse",
          np.allclose(solver(jnp.asarray(b)), x_sp, atol=1e-3))
    for method in ("cg", "pipelined_cg", "bicgstab", "gmres", "bicg"):
        mat = spd if method in ("cg", "pipelined_cg") else a
        ref = x_sp if method in ("cg", "pipelined_cg") else x_lu
        out = api.solve(jnp.asarray(mat), jnp.asarray(b), method=method,
                        mesh=mesh, tol=1e-8)
        check(f"dist {method}", np.allclose(out, ref, atol=1e-3))
    # explicit-SPMD engine (single-source drivers inside one shard_map)
    # equals the GSPMD engine / oracle
    for method in ("cg", "pipelined_cg", "bicgstab", "bicg", "gmres"):
        mat = spd if method in ("cg", "pipelined_cg") else a
        ref = x_sp if method in ("cg", "pipelined_cg") else x_lu
        r = api.solve(jnp.asarray(mat), jnp.asarray(b), method=method,
                      mesh=mesh, engine="spmd", tol=1e-6, return_info=True)
        check(f"spmd {method} == oracle", np.allclose(r.x, ref, atol=1e-3))
    # spmd preconditioning (historically silently ignored) actually applies
    r_plain = api.solve(jnp.asarray(spd), jnp.asarray(b), method="cg",
                        mesh=mesh, engine="spmd", tol=1e-8, return_info=True)
    r_pc = api.solve(jnp.asarray(spd), jnp.asarray(b), method="cg",
                     mesh=mesh, engine="spmd", tol=1e-8, precond="jacobi",
                     return_info=True)
    check("spmd cg jacobi converged",
          bool(r_pc.converged)
          and int(r_pc.iterations) <= int(r_plain.iterations) + 5)
    c = pblas.pgemm_summa(jnp.asarray(a), jnp.asarray(spd), mesh)
    check("SUMMA pgemm", np.allclose(c, a @ spd, rtol=2e-4, atol=2e-1))


def test_ca_krylov(mesh):
    """Communication-avoiding s-step Krylov cell on the real (4, 2) mesh:
    ca_cg/ca_gmres through the explicit-SPMD engine match the oracle, and
    the trace-time collective tally shows ONE Gram reduction per s-step
    block vs cg's two reductions per iteration."""
    rng = np.random.default_rng(3)
    n = 256
    a = rng.standard_normal((n, n)).astype(np.float32) + n * np.eye(
        n, dtype=np.float32)
    spd = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    x_sp = np.linalg.solve(spd, b)
    x_lu = np.linalg.solve(a, b)
    out = api.solve(jnp.asarray(spd), jnp.asarray(b), method="ca_cg", s=4,
                    mesh=mesh, engine="spmd", tol=1e-6)
    check("spmd ca_cg(s=4) == oracle", np.allclose(out, x_sp, atol=1e-3))
    out = api.solve(jnp.asarray(a), jnp.asarray(b), method="ca_gmres", s=8,
                    mesh=mesh, engine="spmd", tol=1e-6)
    check("spmd ca_gmres(s=8) == oracle", np.allclose(out, x_lu, atol=1e-3))
    kw = dict(mesh=mesh, engine="spmd", tol=1e-6)
    with pblas.collective_counts() as c_cg:
        api.solve(jnp.asarray(spd), jnp.asarray(b), method="cg", **kw)
    with pblas.collective_counts() as c_ca:
        api.solve(jnp.asarray(spd), jnp.asarray(b), method="ca_cg", s=4,
                  **kw)
    check("ca_cg: ONE Gram reduction per s-step body (trace tally)",
          c_cg["dots"] == 4 and c_ca["dots"] == 3)


def test_sparse(mesh):
    """Block-row-sharded sparse SPMD engine on a real (4, 2) mesh: the
    all_gather mat-vec, the scatter+psum Aᵀx (bicg), and sharded
    preconditioner state — vs the numpy oracle."""
    from repro.sparse import BSR, problems
    a = problems.poisson_2d(16)                 # n = 256; nbr = 16, p = 4
    b = problems.smooth_rhs(a.shape[0])
    bsr = BSR.from_dense(a, block_size=16)
    ref = np.linalg.solve(a.astype(np.float64), b)
    for method in ("cg", "pipelined_cg", "bicg", "bicgstab", "gmres"):
        x = api.solve(bsr, jnp.asarray(b), method=method, mesh=mesh,
                      engine="spmd", tol=1e-7, maxiter=2000)
        check(f"sparse spmd {method}", np.allclose(x, ref, atol=1e-3))
    r = api.solve(bsr, jnp.asarray(b), method="cg", mesh=mesh,
                  engine="spmd", tol=1e-7, maxiter=2000,
                  precond="block_jacobi", return_info=True)
    check("sparse spmd cg block_jacobi",
          bool(r.converged) and np.allclose(r.x, ref, atol=1e-3))


def test_eigls(mesh):
    """Least-squares & eigenvalue cell: TSQR on the real (4, 2) mesh
    (distributed factor == lstsq oracle) and Lanczos through the sharded
    gspmd operator."""
    from repro.core import qr
    from repro.eigls import tsqr
    from repro.sparse import problems
    rng = np.random.default_rng(4)
    m, n = 512, 32
    a = rng.standard_normal((m, n)).astype(np.float32)
    b = rng.standard_normal(m).astype(np.float32)
    qd, rd = tsqr.tsqr(jnp.asarray(a), mesh)
    ql, rl = qr.reduced(jnp.asarray(a), block_size=16)
    check("tsqr == local blocked QR",
          np.abs(np.asarray(qd) - np.asarray(ql)).max() <= 1e-4
          and np.abs(np.asarray(rd) - np.asarray(rl)).max() <= 1e-3)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr",
                  engine="spmd", mesh=mesh)
    xo = np.linalg.lstsq(a, b, rcond=None)[0]
    check("tsqr api solve == lstsq oracle",
          np.abs(np.asarray(x) - xo).max() <= 1e-4)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lsqr", mesh=mesh,
                  tol=1e-6, maxiter=200)
    check("lsqr on mesh == lstsq oracle",
          np.abs(np.asarray(x) - xo).max() <= 1e-3)
    pa = problems.poisson_2d(16)                   # n = 256, f32
    res = api.eigsolve(jnp.asarray(pa), k=3, which="LA", ncv=100, mesh=mesh)
    wtrue = np.linalg.eigvalsh(pa.astype(np.float64))[::-1][:3]
    check("lanczos on mesh: 3 extreme eigenvalues",
          np.abs(np.sort(np.asarray(res.eigenvalues))[::-1]
                 - wtrue).max() <= 1e-3)


def test_checkpoint_elastic(mesh):
    """LU factors of a sharded system, checkpointed on the (4, 2) mesh and
    restored onto a (2, 2) mesh (after losing hosts), solve as before."""
    from repro.core import lu
    rng = np.random.default_rng(6)
    n = 256
    a = rng.standard_normal((n, n)).astype(np.float32) + n * np.eye(
        n, dtype=np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    packed, perm = lu.lu_factor(dist.shard_matrix(jnp.asarray(a), mesh),
                                block_size=64, mesh=mesh)
    state = {"lu": packed, "perm": perm}
    x_big = lu.lu_apply((packed, perm),
                        dist.shard_vector(jnp.asarray(b), mesh),
                        block_size=64, mesh=mesh)

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, state, blocking=True)
        small = mesh_lib.make_mesh((2, 2), ("data", "model"),
                                   devices=jax.devices()[:4])
        restored, step = mgr.restore(
            jax.eval_shape(lambda: state),
            shardings={"lu": dist.matrix_sharding(small),
                       "perm": NamedSharding(small, P())})
    check("elastic restore step", step == 1)
    check("elastic restore onto the (2, 2) mesh",
          restored["lu"].sharding.mesh.devices.size == 4)
    check("elastic restore factors bitwise",
          np.array_equal(np.asarray(restored["lu"]), np.asarray(packed))
          and np.array_equal(np.asarray(restored["perm"]), np.asarray(perm)))
    x_small = lu.lu_apply((restored["lu"], restored["perm"]),
                          dist.shard_vector(jnp.asarray(b), small),
                          block_size=64, mesh=small)
    check("elastic restore solve match",
          np.allclose(np.asarray(x_small), np.asarray(x_big), atol=1e-5)
          and np.allclose(np.asarray(x_small), np.linalg.solve(a, b),
                          atol=1e-3))


def test_resilience(mesh):
    """Fault-injection smoke cell (the full battery is
    repro.launch.selftest_resilience / tests/test_resilience.py): a NaN
    injected into every matvec is classified and retried to convergence
    by ``policy="resilient"``, and a corrupted trailing update in the
    distributed LU trips the ABFT checksum verifier."""
    from repro.core import lu
    from repro.resilience import abft, inject
    rng = np.random.default_rng(5)
    n = 256
    a = rng.standard_normal((n, n)).astype(np.float32)
    spd = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    with inject.inject(site="matvec", mode="nan") as ses:
        r = api.solve(jnp.asarray(spd), jnp.asarray(b), method="cg",
                      mesh=mesh, tol=1e-6, policy="resilient",
                      return_info=True)
    check("resilient cg recovers from injected matvec NaN",
          ses.fired >= 1
          and r.info["attempts"][0]["reason"] == "non_finite"
          and np.allclose(r.x, np.linalg.solve(spd, b), atol=1e-3))
    gen = a + n * np.eye(n, dtype=np.float32)
    with inject.inject(site="trailing", mode="scale", at_rank=0,
                       at_step=1) as ses:
        st = lu.lu_factor_spmd(jnp.asarray(gen), block_size=32, mesh=mesh,
                               abft=True)
    detected = False
    try:
        abft.verify(st)
    except abft.FactorCorruption:
        detected = True
    check("spmd LU ABFT detects corrupted trailing update",
          ses.fired >= 1 and detected)


def main():
    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    print(f"devices: {len(jax.devices())}", flush=True)
    test_solvers(mesh)
    test_ca_krylov(mesh)
    test_sparse(mesh)
    test_eigls(mesh)
    test_resilience(mesh)
    test_checkpoint_elastic(mesh)
    print("SELFTEST PASS", flush=True)


if __name__ == "__main__":
    main()
