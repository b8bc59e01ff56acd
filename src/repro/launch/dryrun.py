import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Production-mesh dry-run of the distributed solver: a guard against
compile regressions.

Builds the production mesh (16×16 single-pod or 2×16×16 multi-pod) out
of 512 virtual CPU devices, jits the solve with its in/out shardings,
lowers it against ShapeDtypeStruct inputs (no device allocation),
compiles, and records:

* ``compiled.memory_analysis()``  — per-device argument/temp bytes;
* ``compiled.cost_analysis()``    — XLA's own FLOPs (cross-check);
* the while-aware HLO cost model  — FLOPs, HBM traffic, per-kind collective
  payload bytes;
* the derived three-term roofline (v5e constants).

These are compile records from virtual CPU devices, not chip numbers.
With ``--out DIR`` each record lands in
``DIR/solver-<method>__n<n>__<mesh>.json``; without it nothing is written.

Usage:
    python -m repro.launch.dryrun --solver --solver-method cg --mesh pod \
        --out /tmp/dryrun
"""
import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp

import repro.analysis.hlo as hlo_mod
import repro.analysis.roofline as rl
from repro.launch.mesh import make_production_mesh


def _cost_analysis(compiled) -> dict:
    """Normalize compiled.cost_analysis() across JAX versions (older
    releases return a one-element list of dicts, newer a dict)."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def run_solver_cell(mesh_kind: str = "pod", n: int = 61_440, *,
                    method: str = "lu", out_dir: str | None = None) -> dict:
    from repro.core import api, dist, krylov, operator

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh.devices.size
    a = jax.ShapeDtypeStruct((n, n), jnp.float32)
    b = jax.ShapeDtypeStruct((n,), jnp.float32)
    mspec, vspec = dist.matrix_sharding(mesh), dist.vector_sharding(mesh)

    if method in ("lu", "cholesky"):
        # mesh=None: GSPMD propagates layouts from in_shardings freely.
        # Threading the mesh (per-panel constraints) was measured WORSE
        # (LU tx 383→856 s — constraints fight the propagated layout).
        fn = jax.jit(functools.partial(api.solve, method=method, mesh=None,
                                       block_size=1920),
                     in_shardings=(mspec, vspec), out_shardings=vspec)
    elif method in ("cg", "pipelined_cg"):
        driver = krylov.cg if method == "cg" else krylov.pipelined_cg
        fn = jax.jit(lambda a_, b_: operator.spmd_solve(
            driver, a_, b_, mesh, maxiter=100).x,
            in_shardings=(mspec, vspec), out_shardings=vspec)
    else:
        raise ValueError(method)

    t0 = time.time()
    lowered = fn.lower(a, b)
    compiled = lowered.compile()
    t_all = time.time() - t0
    ma = compiled.memory_analysis()
    ca = _cost_analysis(compiled)
    cost = hlo_mod.analyze_hlo(compiled.as_text())
    model_fl = (2 / 3 * n**3 if method in ("lu",) else
                1 / 3 * n**3 if method == "cholesky" else
                100 * 2 * n * n)
    report = rl.roofline(f"solver-{method}/{mesh_kind}", cost, chips=chips,
                         model_flops_global=model_fl,
                         xla_flops=ca.get("flops", 0.0))
    record = {
        "method": method, "n": n, "mesh": mesh_kind, "chips": chips,
        "compile_s": round(t_all, 2),
        "memory": {"argument_bytes": ma.argument_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes},
        "xla_cost": {"flops": ca.get("flops")},
        "hlo_cost": {"flops": cost.flops,
                     "traffic_bytes": cost.traffic_bytes,
                     "collective_bytes": dict(cost.collective_bytes)},
        "roofline": {"t_compute_s": report.t_compute,
                     "t_memory_s": report.t_memory,
                     "t_collective_s": report.t_collective,
                     "bottleneck": report.bottleneck,
                     "useful_ratio": report.useful_ratio},
    }
    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir, f"solver-{method}__n{n}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--solver", action="store_true",
                    help="the solver cell (the only kind there is)")
    ap.add_argument("--solver-method", default="lu",
                    choices=["lu", "cholesky", "cg", "pipelined_cg"])
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--out", default=None,
                    help="directory for the JSON records (default: none)")
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        r = run_solver_cell(mk, method=args.solver_method, out_dir=args.out)
        rr = r["roofline"]
        t = max(rr["t_compute_s"], rr["t_memory_s"], rr["t_collective_s"])
        print(f"[solver-{args.solver_method} {mk}] "
              f"bottleneck={rr['bottleneck']} t={t:.4f}s")


if __name__ == "__main__":
    main()
