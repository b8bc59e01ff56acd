"""Least-squares & eigenvalue battery (TSQR + LSQR/CGLS + Lanczos).

Run standalone (CI's spmd job) or by tests/test_eigls.py in a subprocess
per device count, so the main pytest process keeps its 1-device view.
Device count comes from $EIGLS_DEVICES (default 8 → a (4, 2) mesh);
everything runs in float64 and asserts the acceptance tolerance:
distributed TSQR == local blocked QR to <= 1e-10 and Lanczos extreme
eigenvalues to <= 1e-8.  Prints "EIGLS PASS".
"""
import os
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count="
    + os.environ.get("EIGLS_DEVICES", "8"))

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import jax.numpy as jnp

from repro.core import api, dist, qr
from repro.launch import mesh as mesh_lib

TOL = 1e-10


def check(name, ok):
    if not ok:
        raise AssertionError(f"selftest_eigls failed: {name}")
    print(f"  ok: {name}", flush=True)


def make_mesh():
    ndev = len(jax.devices())
    if ndev >= 8:
        return mesh_lib.make_mesh((4, 2), ("data", "model"),
                                  devices=jax.devices()[:8])
    if ndev >= 2:
        return mesh_lib.make_mesh((2, 1), ("data", "model"),
                                  devices=jax.devices()[:2])
    return dist.single_device_mesh()


def main():
    mesh = make_mesh()
    print(f"devices: {len(jax.devices())}  mesh: {dict(mesh.shape)}",
          flush=True)
    rng = np.random.default_rng(0)

    # -- TSQR: distributed == local blocked QR == lstsq oracle -------------
    from repro.eigls import tsqr
    m, n = 512, 32              # m/P = 64 >= n even on the 8-rank ring
    a = rng.standard_normal((m, n))
    b = rng.standard_normal(m)
    qd, rd = tsqr.tsqr(jnp.asarray(a), mesh)
    ql, rl = qr.reduced(jnp.asarray(a), block_size=16)
    check("tsqr Q == local blocked Q (<= 1e-10)",
          np.abs(np.asarray(qd) - np.asarray(ql)).max() <= TOL)
    check("tsqr R == local blocked R (<= 1e-10)",
          np.abs(np.asarray(rd) - np.asarray(rl)).max() <= TOL)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr",
                  engine="spmd", mesh=mesh)
    xo = np.linalg.lstsq(a, b, rcond=None)[0]
    check("api qr engine=spmd == lstsq oracle",
          np.abs(np.asarray(x) - xo).max() <= TOL)
    # padded rows (m % P != 0) + factorize reuse
    m2 = 250
    a2 = rng.standard_normal((m2, n))
    solver = api.factorize(jnp.asarray(a2), method="qr", engine="spmd",
                           mesh=mesh)
    for _ in range(2):
        b2 = rng.standard_normal(m2)
        xo2 = np.linalg.lstsq(a2, b2, rcond=None)[0]
        check("tsqr factorize reuse (padded m=250)",
              np.abs(np.asarray(solver(jnp.asarray(b2))) - xo2).max() <= TOL)

    # -- iterative least squares on the sharded gspmd engine ---------------
    for method in ("lsqr", "cgls"):
        r = api.solve(jnp.asarray(a), jnp.asarray(b), method=method,
                      mesh=mesh, tol=1e-12, maxiter=300, return_info=True)
        check(f"{method} gspmd mesh == oracle",
              bool(r.converged)
              and np.abs(np.asarray(r.x) - xo).max() <= 1e-8)

    # -- Lanczos on a real mesh (gspmd operator) + matrix-free BSR ---------
    from repro.sparse import BSR, problems
    pa = problems.poisson_2d(32, dtype=np.float64)        # n = 1024
    wtrue = np.linalg.eigvalsh(pa)[::-1][:5]
    res = api.eigsolve(jnp.asarray(pa), k=5, which="LA", ncv=300, mesh=mesh)
    got = np.sort(np.asarray(res.eigenvalues))[::-1]
    check("lanczos on mesh: 5 extreme eigenvalues (<= 1e-8)",
          np.abs(got - wtrue).max() <= 1e-8)
    bsr = BSR.from_dense(pa, block_size=16)
    res = api.eigsolve(bsr, k=5, which="LA", ncv=300)
    got = np.sort(np.asarray(res.eigenvalues))[::-1]
    check("lanczos matrix-free BSR (<= 1e-8)",
          np.abs(got - wtrue).max() <= 1e-8)

    print("EIGLS PASS", flush=True)


if __name__ == "__main__":
    main()
