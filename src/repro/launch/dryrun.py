import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the production mesh (16×16 single-pod or
2×16×16 multi-pod), constructs the jitted train/prefill/decode step with
its in/out shardings, lowers it against ShapeDtypeStruct inputs (no device
allocation), compiles, and records:

* ``compiled.memory_analysis()``  — per-device argument/output/temp bytes
  (proves the cell fits — or doesn't — in 16 GB v5e HBM);
* ``compiled.cost_analysis()``    — XLA's own FLOPs/bytes (cross-check);
* the while-aware HLO cost model  — FLOPs, HBM traffic, per-kind collective
  payload bytes (feeds EXPERIMENTS.md §Roofline);
* the derived three-term roofline.

Artifacts land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    python -m repro.launch.dryrun --all              # every applicable cell
    python -m repro.launch.dryrun --all --mesh multipod
    python -m repro.launch.dryrun --solver           # the paper's LU/CG cell
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp

import repro.analysis.hlo as hlo_mod
import repro.analysis.roofline as rl
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train import sharding as sh
from repro.train import specs as sp
from repro.train import steps as S

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _cost_analysis(compiled) -> dict:
    """Normalize compiled.cost_analysis() across JAX versions (older
    releases return a one-element list of dicts, newer a dict)."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def pick_optimizer(cfg) -> str:
    """Adafactor for ≥50B-param configs (HBM capacity; see optim/adafactor)."""
    return "adafactor" if cfg.param_count() > 50e9 else "adamw"


def build_and_lower(arch: str, shape_name: str, mesh, *, opt_override=None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    # ambient mesh: bare-PartitionSpec constraints inside model code
    # (runtime.mixer_cp) resolve against it during tracing
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_name = opt_override or pick_optimizer(cfg)
            step_fn, sspecs, bspecs, opt = S.make_train_step(
                cfg, mesh, shape, optimizer_name=opt_name, donate=False)
            astate = jax.eval_shape(
                functools.partial(S.init_train_state, cfg, opt),
                jax.random.key(0))
            abatch = sp.train_inputs(cfg, shape)
            return step_fn.lower(astate, abatch), cfg, shape
        if shape.kind == "prefill":
            step_fn, pspecs, bspecs = S.make_prefill_step(cfg, mesh, shape)
            aparams = sp.abstract_params(cfg)
            abatch = sp.prefill_inputs(cfg, shape)
            return step_fn.lower(aparams, abatch), cfg, shape
        # decode
        step_fn, pspecs, ispecs = S.make_decode_step(cfg, mesh, shape,
                                                     donate=False)
        aparams = sp.abstract_params(cfg)
        ain = sp.decode_inputs(cfg, shape)
        return step_fn.lower(aparams, ain["state"], ain["token"],
                             ain["index"]), cfg, shape


def run_cell(arch: str, shape_name: str, mesh_kind: str = "pod", *,
             save: bool = True, opt_override=None, tag: str = "") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh.devices.size
    lowered, cfg, shape = build_and_lower(arch, shape_name, mesh,
                                          opt_override=opt_override)
    t_lower = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    ma = compiled.memory_analysis()
    ca = _cost_analysis(compiled)
    cost = hlo_mod.analyze_hlo(compiled.as_text())
    report = rl.roofline(
        f"{arch}/{shape_name}/{mesh_kind}", cost, chips=chips,
        model_flops_global=rl.model_flops(cfg, shape),
        xla_flops=ca.get("flops", 0.0),
        xla_bytes=ca.get("bytes accessed", 0.0))

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "chips": chips,
        "kind": shape.kind, "tag": tag,
        "optimizer": (opt_override or pick_optimizer(cfg)
                      if shape.kind == "train" else None),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_total_gib": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
                / 2**30, 3),
        },
        "xla_cost": {"flops": ca.get("flops"),
                     "bytes_accessed": ca.get("bytes accessed")},
        "hlo_cost": {
            "flops": cost.flops,
            "traffic_bytes": cost.traffic_bytes,
            "collective_bytes": dict(cost.collective_bytes),
            "collective_counts": dict(cost.collective_counts),
            "group_sizes": dict(cost.group_sizes),
        },
        "roofline": {
            "t_compute_s": report.t_compute,
            "t_memory_s": report.t_memory,
            "t_collective_s": report.t_collective,
            "bottleneck": report.bottleneck,
            "model_flops_global": report.model_flops_global,
            "useful_ratio": report.useful_ratio,
            "mfu_bound": report.mfu_bound,
            "collective_breakdown": report.collective_breakdown,
        },
    }
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = os.path.join(
            OUT_DIR, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


# ----------------------------------------------------------------------------
# the paper's own cell: distributed solver dry-run at n ≈ 60 000
# ----------------------------------------------------------------------------

def run_solver_cell(mesh_kind: str = "pod", n: int = 61_440, *,
                    method: str = "lu", save: bool = True) -> dict:
    from repro.core import api, dist, krylov, operator

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh.devices.size
    a = jax.ShapeDtypeStruct((n, n), jnp.float32)
    b = jax.ShapeDtypeStruct((n,), jnp.float32)
    mspec, vspec = dist.matrix_sharding(mesh), dist.vector_sharding(mesh)

    if method in ("lu", "cholesky"):
        # mesh=None: GSPMD propagates layouts from in_shardings freely.
        # Threading the mesh (per-panel constraints) was measured WORSE
        # (LU tx 383→856 s — constraints fight the propagated layout);
        # see EXPERIMENTS.md §Perf solver iterations.
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
        "arch": f"solver-{method}", "shape": f"n{n}", "mesh": mesh_kind,
        "chips": chips, "kind": "solver", "compile_s": round(t_all, 2),
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
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        path = os.path.join(
            OUT_DIR, f"solver-{method}__n{n}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--solver", action="store_true")
    ap.add_argument("--solver-method", default="lu",
                    choices=["lu", "cholesky", "cg", "pipelined_cg"])
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    if args.solver:
        for mk in meshes:
            r = run_solver_cell(mk, method=args.solver_method)
            print(f"[solver-{args.solver_method} {mk}] "
                  f"bottleneck={r['roofline']['bottleneck']} "
                  f"t={max(r['roofline']['t_compute_s'], r['roofline']['t_memory_s'], r['roofline']['t_collective_s']):.4f}s")
        return

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                if shape_applicable(arch, shape_name):
                    cells.append((arch, shape_name))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape_name in cells:
        for mk in meshes:
            try:
                r = run_cell(arch, shape_name, mk,
                             opt_override=args.optimizer, tag=args.tag)
                rr = r["roofline"]
                print(f"[{arch} {shape_name} {mk}] ok "
                      f"compile={r['compile_s']}s "
                      f"mem/dev={r['memory']['per_device_total_gib']}GiB "
                      f"bottleneck={rr['bottleneck']} "
                      f"tc={rr['t_compute_s']:.2e} tm={rr['t_memory_s']:.2e} "
                      f"tx={rr['t_collective_s']:.2e}", flush=True)
            except Exception as e:
                failures.append((arch, shape_name, mk, repr(e)))
                print(f"[{arch} {shape_name} {mk}] FAILED: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")
    print("all dry-run cells passed")


if __name__ == "__main__":
    main()
