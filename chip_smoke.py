#!/usr/bin/env python3
"""Run the solver library's main path once on a TPU and check the answers.

    python chip_smoke.py              # one chip: every single-chip phase
    python chip_smoke.py --chips 4    # four chips: the distributed phases

Everything goes through the entry points a user calls —
``repro.core.api.solve`` / ``factorize`` and ``repro.serve.ServeClient`` —
in this one process (a chip belongs to one process at a time).  Every
system is generated on the device from ``--seed``.

One JSON line per phase: its name, n, backend, compile and run seconds
(run timed with ``block_until_ready``), iterations, the residual the
phase is checked on, and for Pallas phases whether the compiled program
holds a ``tpu_custom_call``.  The last line is
``{"ok": true, "device": {...}}`` when every phase passed.  The script
exits nonzero, printing no result, when JAX finds no TPU, and exits
nonzero when any phase fails its check.

Checks.  Direct solves: HPL's scaled residual
‖Ax−b‖∞ / (ε (‖A‖∞‖x‖∞ + ‖b‖∞) n) < 16, ε = float32 epsilon.  Krylov
solves: the solver reports convergence to ``TOL`` and the true residual
‖b−Ax‖₂/‖b‖₂ is at most 10·TOL (the recursive residual the solver stops
on drifts from the true one by float32 rounding).  Residuals are
computed at ``Precision.HIGHEST`` so that the check does not add the
error it is looking for.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

N = 16384             # dense systems: 1 GiB of float32 per matrix
NB = 128
TOL = 1e-6
HPL_BOUND = 16.0
KRYLOV_SLACK = 10.0
STENCIL = 24          # poisson_3d grid edge: n = 13824, built dense on host
SERVE_LU_N = (1000, 3500)      # three distinct matrices of each size
SERVE_CG_N = (2000, 4000)      # six systems of each size


# --------------------------------------------------------------------------
# systems, generated on the device
# --------------------------------------------------------------------------

def _uniform(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


def hpl_system(key, n):
    """HPL's matrix and right-hand side: entries uniform in [-0.5, 0.5]."""
    ka, kb = jax.random.split(key)
    return _uniform(ka, (n, n)), _uniform(kb, (n,))


def spd_system(key, n):
    """(R + Rᵀ)/2 + (√n/2)·I: the semicircle of the symmetric part has
    radius ≈ 0.41·√n, so the spectrum sits in [0.09, 0.91]·√n (κ ≈ 10)."""
    ka, kb = jax.random.split(key)
    r = _uniform(ka, (n, n))
    a = (r + r.T) * 0.5 + 0.5 * math.sqrt(n) * jnp.eye(n, dtype=r.dtype)
    return a, _uniform(kb, (n,))


def shifted_system(key, n):
    """R + √n·I: nonsymmetric, eigenvalues in a disk of radius ≈ 0.29·√n
    around √n — what BiCGSTAB converges on without a preconditioner."""
    ka, kb = jax.random.split(key)
    a = _uniform(ka, (n, n)) + math.sqrt(n) * jnp.eye(n, dtype=jnp.float32)
    return a, _uniform(kb, (n,))


# --------------------------------------------------------------------------
# checks (jitted: no eager op compiles, HIGHEST-precision products)
# --------------------------------------------------------------------------

def _matvec(a, x):
    return jnp.dot(a, x, precision=jax.lax.Precision.HIGHEST)


def _hpl_scale(a, x, b):
    norm_a = jnp.max(jnp.sum(jnp.abs(a), axis=1))
    return (jnp.finfo(jnp.float32).eps * a.shape[0]
            * (norm_a * jnp.max(jnp.abs(x)) + jnp.max(jnp.abs(b))))


def _hpl_ratio(a, x, b):
    return jnp.max(jnp.abs(_matvec(a, x) - b)) / _hpl_scale(a, x, b)


def _hpl_agreement(a, x, x_ref, b):
    return jnp.max(jnp.abs(_matvec(a, x - x_ref))) / _hpl_scale(a, x_ref, b)


def _rel_residual(a, x, b):
    return jnp.linalg.norm(b - _matvec(a, x)) / jnp.linalg.norm(b)


def _rel_agreement(a, x, x_ref, b):
    return jnp.linalg.norm(_matvec(a, x - x_ref)) / jnp.linalg.norm(b)


def _host_float(fn):
    """``fn`` jitted, returning a Python float."""
    jitted = jax.jit(fn)
    return lambda *args: float(jitted(*args))


# the agreement of two solutions is the same norm and scale applied to
# A (x - x_ref): by the triangle inequality two solutions that each pass
# a bound agree within twice that bound
hpl_ratio = _host_float(_hpl_ratio)
hpl_agreement = _host_float(_hpl_agreement)
rel_residual = _host_float(_rel_residual)
rel_agreement = _host_float(_rel_agreement)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def timed_solve(a, b, **solve_kw):
    """``api.solve`` compiled ahead of time: compile seconds, then one
    timed run.  Returns (SolveResult, record)."""
    from repro.core import api
    fn = jax.jit(lambda a, b: api.solve(a, b, return_info=True, **solve_kw))
    t0 = time.perf_counter()
    compiled = fn.lower(a, b).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = jax.block_until_ready(compiled(a, b))
    run_s = time.perf_counter() - t0
    rec = {"compile_s": compile_s, "run_s": run_s,
           "iterations": int(res.iterations)}
    if solve_kw.get("backend") == "pallas":
        rec["tpu_custom_call"] = "tpu_custom_call" in compiled.as_text()
    return res, rec


def direct_phase(method, backend, key):
    make = hpl_system if method == "lu" else spd_system
    a, b = make(key, N)
    res, rec = timed_solve(a, b, method=method, backend=backend,
                           block_size=NB)
    rec["residual"] = hpl_ratio(a, res.x, b)
    rec["check"] = f"hpl_ratio < {HPL_BOUND:g}"
    rec["ok"] = rec["residual"] < HPL_BOUND
    return rec


def krylov_phase(method, backend, key, precond=None):
    make = spd_system if method == "cg" else shifted_system
    a, b = make(key, N)
    res, rec = timed_solve(a, b, method=method, backend=backend,
                           precond=precond, tol=TOL, maxiter=1000)
    return _krylov_checked(rec, res, rel_residual(a, res.x, b))


def _krylov_checked(rec, res, residual):
    rec["residual"] = residual
    rec["converged"] = bool(res.converged)
    rec["check"] = f"converged and rel_residual <= {KRYLOV_SLACK * TOL:g}"
    rec["ok"] = rec["converged"] and residual <= KRYLOV_SLACK * TOL
    return rec


def sparse_phase(key):
    from repro.sparse import BSR, problems
    t0 = time.perf_counter()
    dense = problems.poisson_3d(STENCIL)
    a = BSR.from_dense(dense, block_size=NB)
    build_s = time.perf_counter() - t0
    n = dense.shape[0]
    b = _uniform(key, (n,))
    res, rec = timed_solve(a, b, method="cg", backend="pallas", tol=TOL,
                           maxiter=2000)
    rec = {"n": n, "grid": f"{STENCIL}^3", "nnz_bricks": int(a.data.shape[0]),
           "build_s": build_s, **rec}
    return _krylov_checked(rec, res, rel_residual(dense, res.x, b))


def serve_phase(key):
    """About 24 requests with n in [1000, 4000] through ``ServeClient``:
    an LU lane whose matrices come back with new right-hand sides (the
    factor-reuse path must serve them) and a batched CG lane."""
    from repro.serve import ServeClient

    keys = iter(jax.random.split(key, 64))

    def host(make, n):
        a, b = make(next(keys), n)
        return np.asarray(a), np.asarray(b)

    lu_mats = [host(hpl_system, n) for n in SERVE_LU_N for _ in range(3)]
    repeats = [(a, np.asarray(_uniform(next(keys), (a.shape[0],))))
               for a, _ in lu_mats]
    cg_sys = [host(spd_system, n) for n in SERVE_CG_N for _ in range(6)]

    t0 = time.perf_counter()
    with ServeClient(max_batch=8, max_delay_ms=50.0) as client:
        lu_first = client.solve_many(lu_mats, method="lu")
        lu_again = client.solve_many(repeats, method="lu")
        cg_res = client.solve_many(cg_sys, method="cg", tol=TOL,
                                   maxiter=1000)
        stats = client.stats()
    wall_s = time.perf_counter() - t0

    worst_hpl = max(hpl_ratio(a, r.x, b) for (a, b), r in
                    zip(lu_mats + repeats, lu_first + lu_again))
    worst_cg = max(rel_residual(a, r.x, b)
                   for (a, b), r in zip(cg_sys, cg_res))
    failures = [f"cg n={a.shape[0]} did not converge"
                for (a, _), r in zip(cg_sys, cg_res)
                if not np.all(r.converged)]
    served = len(lu_first) + len(lu_again) + len(cg_res)
    ok = (not failures and worst_hpl < HPL_BOUND
          and worst_cg <= KRYLOV_SLACK * TOL
          and stats["factor_reuses"] == len(repeats)
          and stats["requests_served"] == served)
    return {"n": f"{min(SERVE_LU_N)}-{max(SERVE_CG_N)}", "requests": served,
            "compile_s": stats["cache"]["compile_s_total"],
            "run_s": wall_s, "factorizations": stats["factorizations"],
            "factor_reuses": stats["factor_reuses"],
            "batches": stats["batches"], "residual": worst_hpl,
            "cg_residual": worst_cg,
            "check": f"every lu hpl_ratio < {HPL_BOUND:g}, every cg "
                     f"rel_residual <= {KRYLOV_SLACK * TOL:g}, "
                     f"factor_reuses == {len(repeats)}",
            "failures": failures, "ok": ok}


def bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def spmd_phases(run, key):
    """The distributed path on a (2, 2) mesh against the same systems
    solved on device 0 alone."""
    from jax.sharding import NamedSharding
    from repro.core import dist
    from repro.launch.mesh import solver_mesh

    devices = jax.devices()[:4]
    mesh = solver_mesh(devices)
    a_sh = NamedSharding(mesh, dist.matrix_spec(mesh))
    b_sh = NamedSharding(mesh, dist.vector_spec(mesh))
    k_lu, k_cg = jax.random.split(key)

    def placement(a, base):
        shard_devs = {s.device for s in a.addressable_shards}
        grew = [u > b0 for u, b0 in zip(bytes_in_use(devices), base)]
        return {"shard_devices": len(shard_devs),
                "shard_shape": list(a.addressable_shards[0].data.shape),
                "memory_grew": grew,
                "placed_ok": len(shard_devs) == 4 and all(grew)}

    def compare(method, make, key, check, agree, **kw):
        base = bytes_in_use(devices)
        a, b = jax.jit(lambda k: make(k, N),
                       out_shardings=(a_sh, b_sh))(key)
        spmd, rec = timed_solve(a, b, method=method, engine="spmd",
                                mesh=mesh, **kw)
        rec.update(placement(a, base))
        a0, b0 = jax.device_put((a, b), devices[0])
        local, rec0 = timed_solve(a0, b0, method=method, **kw)
        x_s = jax.device_put(spmd.x, devices[0])
        rec["residual"] = check(a0, x_s, b0)
        rec["local"] = {**rec0, "residual": check(a0, local.x, b0)}
        rec["agreement"] = agree(a0, x_s, local.x, b0)
        x_l = np.asarray(local.x)
        rec["rel_diff_vs_local"] = float(
            np.abs(np.asarray(x_s) - x_l).max() / np.abs(x_l).max())
        return rec, spmd, local

    def lu():
        rec, spmd, local = compare(
            "lu", hpl_system, k_lu, hpl_ratio, hpl_agreement,
            block_size=NB)
        rec["check"] = (f"both hpl_ratio < {HPL_BOUND:g}, agreement < "
                        f"{2 * HPL_BOUND:g}, shards on 4 devices, memory "
                        "grew on each")
        rec["ok"] = (rec["residual"] < HPL_BOUND
                     and rec["local"]["residual"] < HPL_BOUND
                     and rec["agreement"] < 2 * HPL_BOUND
                     and rec["placed_ok"])
        return rec

    def cg():
        rec, spmd, local = compare(
            "cg", spd_system, k_cg, rel_residual, rel_agreement, tol=TOL,
            maxiter=1000, precond="jacobi")
        bound = KRYLOV_SLACK * TOL
        rec["check"] = (f"both converged with rel_residual <= {bound:g}, "
                        f"agreement <= {2 * bound:g}, shards on 4 devices, "
                        "memory grew on each")
        rec["ok"] = (bool(spmd.converged) and bool(local.converged)
                     and rec["residual"] <= bound
                     and rec["local"]["residual"] <= bound
                     and rec["agreement"] <= 2 * bound
                     and rec["placed_ok"])
        return rec

    run("lu_spmd", lu, n=N, backend="ref", engine="spmd", mesh=[2, 2])
    run("cg_spmd", cg, n=N, backend="ref", engine="spmd", mesh=[2, 2])


def one_chip_phases(run, key):
    keys = iter(jax.random.split(key, 16))
    for method in ("lu", "cholesky"):
        for backend in ("ref", "pallas"):
            k = next(keys)
            run(f"{method}_{backend}",
                lambda m=method, be=backend, k=k: direct_phase(m, be, k),
                n=N, backend=backend, block_size=NB)
    for method, precond in (("cg", "jacobi"), ("bicgstab", None)):
        for backend in ("ref", "pallas"):
            k = next(keys)
            run(f"{method}_{backend}",
                lambda m=method, be=backend, k=k, pc=precond:
                krylov_phase(m, be, k, precond=pc),
                n=N, backend=backend, precond=precond)
    k = next(keys)
    run("sparse_cg_pallas", lambda: sparse_phase(k), backend="pallas")
    k = next(keys)
    run("serve", lambda: serve_phase(k), backend="ref")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache    # fails outside a checkout

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    compile_cache.enable()

    failed = []

    def run(name, fn, **meta):
        t0 = time.perf_counter()
        try:
            rec = fn()
        except Exception as e:           # report, keep going, fail at end
            traceback.print_exc()
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[:1000]}
        rec = {"phase": name, **meta, **rec,
               "wall_s": time.perf_counter() - t0}
        print(json.dumps(rec), flush=True)
        if not rec["ok"]:
            failed.append(name)

    key = jax.random.key(args.seed)
    if args.chips == 4:
        spmd_phases(run, key)
    else:
        one_chip_phases(run, key)

    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
