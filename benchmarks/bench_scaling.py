"""Paper Figs. 3 & 4 analogue: solver speedup vs number of computing nodes.

The paper ran n = 60 000 on 1/2/4/8/16 workstations.  This container has
one physical CPU, so *measured* wall time across virtual devices is
emulation (all "devices" share the same silicon) — reported for curve
shape only.  The headline number is the MODELED speedup on the target
v5e mesh from the roofline terms of the per-device compiled program
(compute+memory+collective max), which is how the dry-run methodology
extends the paper's experiment to hardware we don't have.

Each device count runs in a subprocess (XLA fixes the device count at
first init).
"""
from __future__ import annotations

from benchmarks.common import SRC, emit, run_child

_CHILD = r"""
import sys, json, time
sys.path.insert(0, %(src)r)
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import krylov, api, dist, operator
from repro.analysis import hlo as H
import repro.analysis.roofline as R

n = %(n)d
p = int(%(ndev)d ** 0.5)
while %(ndev)d %% p: p -= 1
mesh = make_mesh((p, %(ndev)d // p), ("data", "model"))
rng = np.random.default_rng(0)
a = (rng.standard_normal((n, n)) / n + 4 * np.eye(n)).astype(np.float32)
b = rng.standard_normal(n).astype(np.float32)
out = {}

# --- iterative (CG, explicit SPMD — the paper's MPI pattern) ---------------
aj = dist.shard_matrix(jnp.asarray(a), mesh)
bj = dist.shard_vector(jnp.asarray(b), mesh)
fn = jax.jit(lambda A, B: operator.spmd_solve(
    krylov.cg, A, B, mesh, tol=1e-6, maxiter=50).x)
lowered = fn.lower(aj, bj); compiled = lowered.compile()
t0 = time.perf_counter(); jax.block_until_ready(fn(aj, bj))
t1 = time.perf_counter(); jax.block_until_ready(fn(aj, bj))
cost = H.analyze_hlo(compiled.as_text())
wire, _ = R.wire_bytes(cost)
out["cg"] = {
  "wall_s": time.perf_counter() - t1,
  "t_compute": cost.flops / R.PEAK_FLOPS_BF16,
  "t_memory": cost.traffic_bytes / R.HBM_BW,
  "t_collective": wire / R.ICI_BW,
}

# --- direct (blocked LU, GSPMD) --------------------------------------------
fn2 = jax.jit(lambda A, B: api.solve(A, B, method="lu",
                                     block_size=max(n // 8, 32), mesh=None))
lowered2 = fn2.lower(aj, bj); compiled2 = lowered2.compile()
t0 = time.perf_counter(); jax.block_until_ready(fn2(aj, bj))
t1 = time.perf_counter(); jax.block_until_ready(fn2(aj, bj))
cost2 = H.analyze_hlo(compiled2.as_text())
wire2, _ = R.wire_bytes(cost2)
out["lu"] = {
  "wall_s": time.perf_counter() - t1,
  "t_compute": cost2.flops / R.PEAK_FLOPS_BF16,
  "t_memory": cost2.traffic_bytes / R.HBM_BW,
  "t_collective": wire2 / R.ICI_BW,
}
print("RESULT " + json.dumps(out))
"""


def run(n: int = 2048, device_counts=(1, 2, 4, 8, 16)):
    results = {}
    for ndev in device_counts:
        code = _CHILD % {"ndev": ndev, "n": n, "src": SRC}
        results[ndev] = run_child(code, ndev)

    for method in ("cg", "lu"):
        if 1 not in results:
            continue
        base = results[1][method]
        t1_model = max(base["t_compute"], base["t_memory"],
                       base["t_collective"])
        for ndev, r in sorted(results.items()):
            m = r[method]
            t_model = max(m["t_compute"], m["t_memory"], m["t_collective"])
            emit("scaling", f"{method}_n{n}_ndev{ndev}_modeled",
                 round(t1_model / t_model, 2), "x speedup (v5e roofline)",
                 f"t_model={t_model:.2e}s bottleneck="
                 f"{max(('compute', m['t_compute']), ('memory', m['t_memory']), ('collective', m['t_collective']), key=lambda kv: kv[1])[0]}")
            emit("scaling", f"{method}_n{n}_ndev{ndev}_wall",
                 round(base["wall_s"] / m["wall_s"], 2),
                 "x speedup (CPU emulation)", f"wall={m['wall_s']:.3f}s")
