"""CUPLSS driver — the paper's end-to-end use case.

    PYTHONPATH=src python -m repro.launch.solve --n 1024 --method bicgstab

Generates a synthetic dense system A x = b (diagonally-dominant general or
SPD depending on the method), solves it with the chosen CUPLSS method on
the available device mesh, and reports residual + timing — the single-node
analogue of the paper's §4 runs (benchmarks/ has the scaling versions).

Resilience drills (docs/resilience.md):

    # inject a NaN into every matvec, recover via the escalation policy
    ... --method cg --inject matvec --policy resilient

    # checkpoint every 25 iterations, kill chunk 1, restore + resume
    ... --method cg --checkpoint-dir /tmp/ck --checkpoint-every 25 \\
        --fail-at-chunk 1 --watchdog 300
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import api
from repro.launch.mesh import solver_mesh
from repro.resilience import inject


def make_system(n: int, *, spd: bool, m: int | None = None,
                dtype=np.float32, seed: int = 0):
    rng = np.random.default_rng(seed)
    if m is not None and m != n:                # rectangular: least squares
        a = rng.standard_normal((m, n)).astype(dtype)
        return a, rng.standard_normal(m).astype(dtype)
    a = rng.standard_normal((n, n)).astype(dtype)
    if spd:
        a = a @ a.T / n + np.eye(n, dtype=dtype) * 4.0
    else:
        a += n * np.eye(n, dtype=dtype)         # diagonally dominant
    b = rng.standard_normal(n).astype(dtype)
    return a, b


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--m", type=int, default=None,
                    help="rows; m > n makes the system rectangular least "
                         "squares (methods qr/lsqr/cgls)")
    ap.add_argument("--method", default="lu",
                    choices=["lu", "cholesky", "qr", "cg", "pipelined_cg",
                             "ca_cg", "ca_gmres", "bicg", "bicgstab",
                             "gmres", "lsqr", "cgls"])
    ap.add_argument("--s", type=int, default=2,
                    help="s-step basis size for ca_cg/ca_gmres (the "
                         "monomial basis conditions like kappa^s: keep "
                         "s small in float32, raise under --dtype "
                         "float64 — see docs/resilience.md)")
    ap.add_argument("--engine", default="gspmd", choices=["gspmd", "spmd"])
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--precond", default=None,
                    choices=[None, "jacobi", "block_jacobi"])
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "float64"])
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--maxiter", type=int, default=1000)
    ap.add_argument("--distributed", action="store_true")
    # -- resilience drills -------------------------------------------------
    ap.add_argument("--policy", default=None, choices=["resilient"],
                    help="failure classification + retry/fallback "
                         "escalation (api.solve policy)")
    ap.add_argument("--inject", default=None, choices=list(inject.SITES),
                    help="arm a deterministic fault at this site for the "
                         "solve (drill; combine with --policy resilient)")
    ap.add_argument("--inject-mode", default="nan",
                    choices=list(inject.MODES))
    ap.add_argument("--checkpoint-dir", default=None,
                    help="run the solve in checkpointed chunks persisted "
                         "here (iterative methods; enables kill/resume)")
    ap.add_argument("--checkpoint-every", type=int, default=100,
                    help="iterations per checkpointed chunk")
    ap.add_argument("--fail-at-chunk", type=int, action="append",
                    default=None,
                    help="inject a NodeFailure before this chunk index "
                         "(repeatable; exercises restore + resume)")
    ap.add_argument("--watchdog", type=float, default=None,
                    help="heartbeat watchdog budget in seconds (with "
                         "--checkpoint-dir)")
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    spd = args.method in ("cholesky", "cg", "pipelined_cg", "ca_cg")
    a, b = make_system(args.n, spd=spd, m=args.m,
                       dtype=np.dtype(args.dtype))
    mesh = solver_mesh() if args.distributed else None

    t0 = time.time()
    extra = {"s": args.s} if args.method.startswith("ca_") else {}
    kw = dict(method=args.method, mesh=mesh, engine=args.engine,
              backend=args.backend, tol=args.tol,
              block_size=args.block_size, precond=args.precond, **extra)
    drill = (inject.inject(site=args.inject, mode=args.inject_mode)
             if args.inject else contextlib.nullcontext())
    with drill as session:
        if args.checkpoint_dir:
            from repro.distributed import fault_tolerance as ft
            from repro.resilience import runner
            hb = (ft.HeartbeatMonitor(args.watchdog).start()
                  if args.watchdog else None)
            inj = (ft.FailureInjector(set(args.fail_at_chunk))
                   if args.fail_at_chunk else None)
            try:
                res = runner.checkpointed_solve(
                    jnp.asarray(a), jnp.asarray(b),
                    directory=args.checkpoint_dir,
                    every=args.checkpoint_every, maxiter=args.maxiter,
                    heartbeat=hb, injector=inj, policy=args.policy, **kw)
            finally:
                if hb is not None:
                    hb.stop()
            print(f"checkpointed: iters={int(res.iterations)} "
                  f"recoveries={res.info['recoveries']} "
                  f"steps={res.info['checkpoint_steps']}")
        else:
            res = api.solve(jnp.asarray(a), jnp.asarray(b),
                            maxiter=args.maxiter, policy=args.policy,
                            return_info=True, **kw)
    if session is not None:
        print(f"fault drill: site={args.inject} mode={args.inject_mode} "
              f"fired={session.fired}")
    info = res.info or {}
    for att in info.get("attempts", []):
        print(f"  attempt: method={att['method']} backend={att['backend']} "
              f"-> {att['reason']}")
    x = jax.block_until_ready(res.x)
    dt = time.time() - t0

    rvec = np.asarray(b) - a @ np.asarray(x)
    if a.shape[0] != a.shape[1]:
        # least squares: ||b - Ax|| stays O(1) at the solution — what
        # vanishes is the normal-equations residual
        res = float(np.linalg.norm(a.T @ rvec) / np.linalg.norm(a.T @ b))
        label = "||Aᵀ(b - Ax)||/||Aᵀb||"
    else:
        res = float(np.linalg.norm(rvec) / np.linalg.norm(b))
        label = "||b - Ax||/||b||"
    print(f"method={args.method} engine={args.engine} shape={a.shape} "
          f"dtype={args.dtype} mesh={mesh.shape if mesh else None}")
    print(f"relative residual {label} = {res:.3e}   "
          f"wall = {dt:.3f}s")
    if res > max(args.tol * 100, 1e-4):
        raise SystemExit(f"residual too large: {res}")
    return res


if __name__ == "__main__":
    main()
