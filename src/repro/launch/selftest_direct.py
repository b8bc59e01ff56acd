import os
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count="
    + os.environ.get("DIRECT_SPMD_DEVICES", "8"))

"""Distributed direct-solver battery (block-cyclic SPMD LU/Cholesky).

Run standalone (CI's spmd job) or by tests/test_distributed_direct.py in a
subprocess per device count, so the main pytest process keeps its 1-device
view.  Device count comes from $DIRECT_SPMD_DEVICES (default 8 → a (4, 2)
mesh, selftest-shaped); everything runs in float64 and asserts the
acceptance tolerance: distributed == local/oracle to <= 1e-10.

Covers: LU + Cholesky solves vs the local path and the numpy oracle,
bitwise-level factor parity against the local fori_loop factorization
(modulo the cyclic storage permutation), the n % nb != 0 padded case
through core/blocking, multi-RHS, factorize() reuse, the distributed
triangular solves, and api.solve return_info.  Prints "DIRECT SPMD PASS".
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import jax.numpy as jnp

from repro.core import api, cholesky, dist, lu, triangular
from repro.launch import mesh as mesh_lib

TOL = 1e-10


def check(name, ok):
    if not ok:
        raise AssertionError(f"selftest_direct failed: {name}")
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
    n, nb = 256, 16            # 16 blocks: cyclic perm is non-trivial
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal(n)
    spd = a @ a.T / n + 4 * np.eye(n)
    aj, bj, sj = jnp.asarray(a), jnp.asarray(b), jnp.asarray(spd)

    # -- solve parity: spmd == local == oracle -----------------------------
    for method, mat, matj in (("lu", a, aj), ("cholesky", spd, sj)):
        x = api.solve(matj, bj, method=method, mesh=mesh, engine="spmd",
                      block_size=nb)
        x_loc = api.solve(matj, bj, method=method, block_size=nb)
        oracle = np.linalg.solve(mat, b)
        check(f"{method} spmd == local (<= {TOL})",
              np.abs(np.asarray(x) - np.asarray(x_loc)).max() <= TOL)
        check(f"{method} spmd == oracle (<= {TOL})",
              np.abs(np.asarray(x) - oracle).max() <= TOL)

    # -- factor parity: distributed factor == local factor, cyclic cols ---
    st = lu.lu_factor_spmd(aj, block_size=nb, mesh=mesh)
    lu_loc, perm_loc = lu.lu_factor(aj, block_size=nb)
    check("lu spmd factor == local factor (cyclic storage)",
          np.abs(np.asarray(st.lu)
                 - np.asarray(lu_loc)[:, st.layout.colperm]).max() <= TOL)
    check("lu spmd pivots == local pivots",
          bool((np.asarray(st.perm) == np.asarray(perm_loc)).all()))
    cst = cholesky.cholesky_factor_spmd(sj, block_size=nb, mesh=mesh)
    l_loc = cholesky.cholesky_factor(sj, block_size=nb)
    check("cholesky spmd factor == local factor (cyclic storage)",
          np.abs(np.asarray(cst.l)
                 - np.asarray(l_loc)[:, cst.layout.colperm]).max() <= TOL)

    # -- lookahead pipeline: BITWISE factor parity + one extra broadcast --
    from repro.core import pblas
    st_no = lu.lu_factor_spmd(aj, block_size=nb, mesh=mesh, lookahead=False)
    check("lu lookahead factor BITWISE == non-lookahead",
          np.array_equal(np.asarray(st.lu), np.asarray(st_no.lu))
          and np.array_equal(np.asarray(st.perm), np.asarray(st_no.perm)))
    cst_no = cholesky.cholesky_factor_spmd(sj, block_size=nb, mesh=mesh,
                                           lookahead=False)
    check("cholesky lookahead factor BITWISE == non-lookahead",
          np.array_equal(np.asarray(cst.l), np.asarray(cst_no.l)))
    with pblas.collective_counts() as c_la:
        lu.lu_factor_spmd(aj, block_size=nb, mesh=mesh, lookahead=True)
    with pblas.collective_counts() as c_no:
        lu.lu_factor_spmd(aj, block_size=nb, mesh=mesh, lookahead=False)
    check("lu lookahead trace = non-lookahead + 1 pipeline-fill bcast",
          c_la["bcast"] == c_no["bcast"] + 1)

    # -- padded case (n % nb != 0) through core/blocking -------------------
    n2 = 250
    a2 = rng.standard_normal((n2, n2)) + n2 * np.eye(n2)
    b2 = rng.standard_normal(n2)
    spd2 = a2 @ a2.T / n2 + 4 * np.eye(n2)
    for method, mat in (("lu", a2), ("cholesky", spd2)):
        x = api.solve(jnp.asarray(mat), jnp.asarray(b2), method=method,
                      mesh=mesh, engine="spmd", block_size=32)
        x_loc = api.solve(jnp.asarray(mat), jnp.asarray(b2), method=method,
                          block_size=32)
        check(f"{method} spmd padded (n=250, nb=32) == local",
              np.abs(np.asarray(x) - np.asarray(x_loc)).max() <= TOL)

    # -- factorize() reuse + multi-RHS + return_info -----------------------
    solver = api.factorize(aj, method="lu", mesh=mesh, engine="spmd",
                           block_size=nb)
    bm = rng.standard_normal((n, 3))
    check("factorize spmd multi-rhs",
          np.abs(np.asarray(solver(jnp.asarray(bm)))
                 - np.linalg.solve(a, bm)).max() <= TOL)
    r = api.solve(sj, bj, method="cholesky", mesh=mesh, engine="spmd",
                  block_size=nb, return_info=True, tol=1e-8)
    check("spmd return_info SolveResult converged",
          bool(r.converged) and int(r.iterations) == 0)

    # -- distributed triangular solves (vs the local blocked path) ---------
    t = np.tril(rng.standard_normal((n, n))) / n + 4 * np.eye(n)
    y = triangular.solve_lower_spmd(jnp.asarray(t), bj, block_size=nb,
                                    mesh=mesh)
    y_loc = triangular.solve_lower_blocked(jnp.asarray(t), bj, block_size=nb)
    check("solve_lower_spmd == local",
          np.abs(np.asarray(y) - np.asarray(y_loc)).max() <= TOL)
    x = triangular.solve_upper_spmd(jnp.asarray(t.T), bj, block_size=nb,
                                    mesh=mesh)
    x_loc = triangular.solve_upper_blocked(jnp.asarray(t.T), bj,
                                           block_size=nb)
    check("solve_upper_spmd == local",
          np.abs(np.asarray(x) - np.asarray(x_loc)).max() <= TOL)

    print("DIRECT SPMD PASS", flush=True)


if __name__ == "__main__":
    main()
