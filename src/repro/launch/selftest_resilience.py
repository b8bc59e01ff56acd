import os
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count="
    + os.environ.get("RESILIENCE_DEVICES", "8"))

"""Resilience battery on a real multi-device view (PR 7).

Run standalone (CI's spmd job) or by tests/test_resilience.py in a
subprocess per device count, so the main pytest process keeps its
1-device view.  Device count comes from $RESILIENCE_DEVICES (default
8 → a (4, 2) mesh, selftest-shaped); everything runs in float64.

Covers, on the distributed engines: ABFT checksum factorizations clean
(err under threshold, factor BITWISE equal to the unchecked one) and
corrupted (trailing-update fault the unchecked path silently absorbs →
FactorCorruption), the psum-corruption → residual-audit → retry ladder,
the spmd direct ABFT → retry ladder, and injected-matvec recovery with
``policy="resilient"`` to the acceptance residual 1e-8.  Prints
"RESILIENCE PASS".
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import jax.numpy as jnp

from repro.core import api, cholesky, dist, lu
from repro.resilience import abft, inject
from repro.launch import mesh as mesh_lib

TOL = 1e-8


def check(name, ok):
    if not ok:
        raise AssertionError(f"selftest_resilience failed: {name}")
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


def resid(a, b, x):
    return float(np.linalg.norm(np.asarray(a) @ np.asarray(x)
                                - np.asarray(b))
                 / np.linalg.norm(np.asarray(b)))


def main():
    mesh = make_mesh()
    print(f"devices: {len(jax.devices())}  mesh: {dict(mesh.shape)}",
          flush=True)
    rng = np.random.default_rng(0)
    n, nb = 128, 16
    a = rng.standard_normal((n, n))
    a_lu = jnp.asarray(a + n * np.eye(n))
    a_spd = jnp.asarray(a @ a.T / n + 4.0 * np.eye(n))
    b = jnp.asarray(rng.standard_normal(n))

    # -- ABFT clean: err under threshold, factor bitwise-unchanged --------
    st0 = lu.lu_factor_spmd(a_lu, block_size=nb, mesh=mesh)
    st1 = lu.lu_factor_spmd(a_lu, block_size=nb, mesh=mesh, abft=True)
    thr = abft.checksum_threshold(st1.layout.n, st1.lu.dtype)
    check(f"lu abft clean err {float(st1.abft_err):.1e} <= {thr:.1e}",
          float(st1.abft_err) <= thr)
    check("lu abft factor BITWISE == unchecked factor",
          np.array_equal(np.asarray(st0.lu), np.asarray(st1.lu)))
    abft.verify(st1)
    c0 = cholesky.cholesky_factor_spmd(a_spd, block_size=nb, mesh=mesh)
    c1 = cholesky.cholesky_factor_spmd(a_spd, block_size=nb, mesh=mesh,
                                       abft=True)
    check(f"cholesky abft clean err {float(c1.abft_err):.1e}",
          float(c1.abft_err) <= abft.checksum_threshold(c1.layout.n,
                                                        c1.l.dtype))
    check("cholesky abft factor BITWISE == unchecked factor",
          np.array_equal(np.asarray(c0.l), np.asarray(c1.l)))

    # -- ABFT corrupted: silent on the unchecked path, detected with it ---
    drill = dict(site="trailing", mode="scale", seed=7, at_step=1,
                 at_rank=0)
    with inject.inject(**drill) as ses:
        st_bad = lu.lu_factor_spmd(a_lu, block_size=nb, mesh=mesh,
                                   abft=True)
    check("lu trailing fault fired", ses.fired >= 1)
    detected = False
    try:
        abft.verify(st_bad)
    except abft.FactorCorruption:
        detected = True
    check(f"lu abft detects corruption (err {float(st_bad.abft_err):.1e})",
          detected)
    with inject.inject(**drill):
        st_silent = lu.lu_factor_spmd(a_lu, block_size=nb, mesh=mesh)
    x_bad = lu.lu_apply_spmd(st_silent, b)
    check("unchecked path silently absorbs the same fault (finite, wrong)",
          bool(np.isfinite(np.asarray(x_bad)).all())
          and resid(a_lu, b, x_bad) > 1e-6)
    with inject.inject(site="trailing", mode="scale", seed=3, at_step=0,
                       at_rank=0):
        c_bad = cholesky.cholesky_factor_spmd(a_spd, block_size=nb,
                                              mesh=mesh, abft=True)
    detected = False
    try:
        abft.verify(c_bad)
    except abft.FactorCorruption:
        detected = True
    check("cholesky abft detects corruption", detected)

    # -- escalation ladder on the distributed engines ---------------------
    with inject.inject(site="psum", mode="inf") as ses:
        r = api.solve(a_spd, b, method="cg", tol=1e-10, mesh=mesh,
                      engine="spmd", policy="resilient", return_info=True)
    reasons = [t["reason"] for t in r.info["attempts"]]
    check(f"spmd cg psum-Inf recovered via {reasons}",
          ses.fired >= 1 and reasons[-1] == "ok"
          and resid(a_spd, b, r.x) <= TOL)
    with inject.inject(site="trailing", mode="scale", at_rank=0) as ses:
        r = api.solve(a_lu, b, method="lu", mesh=mesh, engine="spmd",
                      block_size=nb, policy="resilient", return_info=True)
    reasons = [t["reason"] for t in r.info["attempts"]]
    check("spmd lu ABFT-classified retry recovered",
          reasons[0] != "ok" and reasons[-1] == "ok"
          and resid(a_lu, b, r.x) <= TOL)
    with inject.inject(site="matvec", mode="nan") as ses:
        r = api.solve(a_spd, b, method="cg", tol=1e-10, mesh=mesh,
                      policy="resilient", return_info=True)
    check("gspmd-on-mesh cg matvec-NaN recovered",
          ses.fired >= 1
          and r.info["attempts"][0]["reason"] == "non_finite"
          and resid(a_spd, b, r.x) <= TOL)

    print("RESILIENCE PASS", flush=True)


if __name__ == "__main__":
    main()
