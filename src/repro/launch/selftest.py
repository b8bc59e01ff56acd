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
  4. sharded train step for one arch per family (loss decreases),
  5. int8 ring all-reduce == psum (within quantization tolerance),
  6. checkpoint save → elastic restore onto a smaller mesh → identical
     forward outputs.
Prints "SELFTEST PASS" at the end; any assertion kills the process.
"""
import tempfile

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import api, pblas
from repro.checkpoint import CheckpointManager
from repro.models import registry
from repro.train import sharding as sh, steps as S
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


def test_train(mesh):
    shape = ShapeConfig("tiny", 64, 8, "train")
    for arch in ("qwen3-1.7b", "dbrx-132b", "mamba2-780m", "hymba-1.5b",
                 "whisper-small", "llama-3.2-vision-90b"):
        cfg = get_config(arch, reduced=True)
        step_fn, sspecs, bspecs, opt = S.make_train_step(
            cfg, mesh, shape, donate=False)
        state = S.init_train_state(cfg, opt, jax.random.key(0))
        state = jax.device_put(state, sh.shardings_of(sspecs, mesh))
        batch = registry.make_batch(cfg, shape.global_batch, shape.seq_len)
        batch = jax.device_put(batch, sh.shardings_of(bspecs, mesh))
        _, m0 = step_fn(state, batch)
        state, _ = step_fn(state, batch)
        for _ in range(3):
            state, m = step_fn(state, batch)
        check(f"train {arch} loss {float(m0['loss']):.3f}->"
              f"{float(m['loss']):.3f}",
              float(m["loss"]) < float(m0["loss"]))


def test_compression(mesh):
    from repro.distributed import compression
    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, 1024)).astype(np.float32)

    def body(xl):
        return compression.ring_allreduce_int8(xl.sum(0), "data")

    f = shard_map(body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                  check_vma=False)
    got = np.asarray(f(jnp.asarray(x)))
    want = x.sum(axis=0)
    # int8 wire: error bounded by a few quant steps, measured against the
    # tensor scale (elementwise-relative explodes at zero crossings)
    rel = np.abs(got - want).max() / np.abs(want).max()
    check(f"int8 ring allreduce (scale-rel {rel:.4f})", rel < 0.02)


def test_checkpoint_elastic(mesh):
    cfg = get_config("qwen3-1.7b", reduced=True)
    shape = ShapeConfig("tiny", 64, 8, "train")
    step_fn, sspecs, bspecs, opt = S.make_train_step(cfg, mesh, shape,
                                                     donate=False)
    state = S.init_train_state(cfg, opt, jax.random.key(0))
    state = jax.device_put(state, sh.shardings_of(sspecs, mesh))
    batch = registry.make_batch(cfg, shape.global_batch, shape.seq_len)
    state, _ = step_fn(state, jax.device_put(
        batch, sh.shardings_of(bspecs, mesh)))

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, state, blocking=True)
        # elastic: restore onto a smaller (2,2) mesh = "after losing hosts"
        small = mesh_lib.make_mesh((2, 2), ("data", "model"),
                                   devices=jax.devices()[:4])
        small_specs = S.state_specs(cfg, small)
        restored, step = mgr.restore(
            jax.eval_shape(lambda: state),
            shardings=sh.shardings_of(small_specs, small))
        check("elastic restore step", step == 1)
        logits_a = registry.forward(
            jax.device_get(state["params"]), batch, cfg)
        logits_b = registry.forward(
            jax.device_get(restored["params"]), batch, cfg)
        check("elastic restore forward match",
              np.allclose(np.asarray(logits_a), np.asarray(logits_b)))


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
    test_train(mesh)
    test_compression(mesh)
    test_checkpoint_elastic(mesh)
    print("SELFTEST PASS", flush=True)


if __name__ == "__main__":
    main()
