"""Paper §4 analogue: direct vs iterative solver comparison (single node).

Paper finding to reproduce: direct (factorization) methods have the higher
*arithmetic intensity* (Level-3 BLAS) and iterative methods are
matvec-bound — measured here as wall time vs n and flops/byte, fp32 + fp64
(the paper tested both precisions).

``run_spmd`` (the ``solvers_spmd`` section / ``--spmd`` flag) adds the
communication-avoiding sweep: ``cg`` vs ``ca_cg(s=4)`` vs
``ca_gmres(s=8)`` wall time per host device count, with the trace-time
reduction tally in each note — the number that motivates s-step methods
(one Gram psum per s iterations vs two psums per iteration).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SRC, emit, make_system, run_child, timeit
from repro import telemetry
from repro.core import api


def run(sizes=(512, 1024), dtypes=("float32",)):
    for dtype in dtypes:
        if dtype == "float64":
            jax.config.update("jax_enable_x64", True)
        for n in sizes:
            a, b = make_system(n, spd=False, dtype=np.dtype(dtype))
            spd, _ = make_system(n, spd=True, dtype=np.dtype(dtype))
            aj, bj, sj = jnp.asarray(a), jnp.asarray(b), jnp.asarray(spd)
            x_ref = np.linalg.solve(a, b)
            xs_ref = np.linalg.solve(spd, b)

            for method, mat, ref in (
                    ("lu", aj, x_ref), ("cholesky", sj, xs_ref),
                    ("cg", sj, xs_ref), ("pipelined_cg", sj, xs_ref),
                    ("ca_cg", sj, xs_ref), ("bicgstab", aj, x_ref),
                    ("gmres", aj, x_ref), ("ca_gmres", aj, x_ref),
                    ("bicg", aj, x_ref)):
                extra = {"s": 4} if method.startswith("ca_") else {}
                fn = jax.jit(lambda A, B, m=method, kw=extra: api.solve(
                    A, B, method=m, tol=1e-8, block_size=min(128, n // 4),
                    **kw))
                t = timeit(fn, mat, bj)
                x = np.asarray(fn(mat, bj))
                res = float(np.linalg.norm(b - np.asarray(mat) @ x)
                            / np.linalg.norm(b))
                kind = "direct" if method in ("lu", "cholesky") else "iter"
                emit("solvers", f"{method}_n{n}_{dtype}", round(t * 1e3, 2),
                     "ms", f"kind={kind} rel_res={res:.1e}")

            if dtype != "float32":
                continue        # fused kernels are float32-only

            # fused-Pallas vs ref hot loop, and pipelined vs classic CG:
            # iteration counts via return_info (pipelined should match CG
            # ±rounding while issuing ONE reduction per iteration).
            for method in ("cg", "pipelined_cg", "bicgstab"):
                mat = sj if method.endswith("cg") else aj
                for backend in ("ref", "pallas"):
                    fn = jax.jit(lambda A, B, m=method, be=backend:
                                 api.solve(A, B, method=m, tol=1e-8,
                                           backend=be, return_info=True))
                    t = timeit(fn, mat, bj)
                    r = fn(mat, bj)
                    emit("solvers",
                         f"backend_{backend}_{method}_n{n}_{dtype}",
                         round(t * 1e3, 2), "ms",
                         f"iters={int(r.iterations)} "
                         f"converged={bool(r.converged)}")

            # -- telemetry: convergence records + armed-overhead probe ----
            # Eager instrumented solves so concrete iteration counts land
            # in the section's TELEM_solvers.json solve records (per-method
            # f32-reachable tolerances; the timed rows above use 1e-8 and
            # run to maxiter in f32).  The overhead rows then time the SAME
            # jitted solve disarmed vs armed — the armed graph carries the
            # residual ring buffer; contract is <= 5% slowdown.
            for method, mat, tol_i, kw in (
                    ("cg", sj, 1e-6, {}),
                    # s=2: the f32-stable s-step depth (s=4 diverges on
                    # this system in single precision)
                    ("ca_cg", sj, 1e-5, {"s": 2}),
                    ("lu", aj, 1e-6, {})):
                api.solve(mat, bj, method=method, tol=tol_i,
                          return_info=True, **kw)
            fn_off = jax.jit(lambda A, B: api.solve(A, B, method="cg",
                                                    tol=1e-8))
            fn_on = jax.jit(lambda A, B: api.solve(A, B, method="cg",
                                                   tol=1e-8))
            # alternating rounds + median-of-ratios: sub-ms wall times
            # swing with CPU warm-up state, a single off/on pair lies
            ratios = []
            for _ in range(3):
                with telemetry.disabled():
                    t_off = timeit(fn_off, sj, bj, warmup=2, iters=10)
                with telemetry.session("overhead-probe"):
                    t_on = timeit(fn_on, sj, bj, warmup=2, iters=10)
                ratios.append(t_on / t_off)
            emit("solvers", f"telemetry_overhead_cg_n{n}_{dtype}",
                 round(float(np.median(ratios)), 3), "ratio",
                 f"armed {t_on * 1e3:.2f} ms vs disarmed "
                 f"{t_off * 1e3:.2f} ms, 3 rounds (contract: <= 1.05)")

            # -- perf-observatory overhead: session(perf=True) routes
            # eager solves through an AOT executable and attributes a
            # roofline per solve — all analysis happens once per
            # compile, so warm perf-armed solves must cost the same as
            # span-armed ones.  One nested perf session for the whole
            # probe (one observatory, one compile), a plain session
            # nested inside it for the baseline halves.
            eager_cg = lambda A, B: api.solve(A, B, method="cg", tol=1e-6)
            pratios = []
            with telemetry.session("perf-probe", perf=True) as psess:
                eager_cg(sj, bj)                # compile + analyze once
                for _ in range(3):
                    t_perf = timeit(eager_cg, sj, bj, warmup=2, iters=10)
                    with telemetry.session("plain-probe"):
                        t_plain = timeit(eager_cg, sj, bj, warmup=2,
                                         iters=10)
                    pratios.append(t_perf / t_plain)
                n_analyses = psess.perf.analyses
            emit("solvers", f"perf_overhead_cg_n{n}_{dtype}",
                 round(float(np.median(pratios)), 3), "ratio",
                 f"perf-armed {t_perf * 1e3:.2f} ms vs span-armed "
                 f"{t_plain * 1e3:.2f} ms, {n_analyses} HLO analyses for "
                 f"31 solves, 3 rounds (contract: <= 1.05)")
        if dtype == "float64":
            jax.config.update("jax_enable_x64", False)


# --------------------------------------------------------------------------
# --spmd: communication-avoiding Krylov vs device count
# --------------------------------------------------------------------------

_SPMD_CHILD = r"""
import sys, json, time
sys.path.insert(0, %(src)r)
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import api, pblas

n, ndev = %(n)d, %(ndev)d
p = int(ndev ** 0.5)
while ndev %% p: p -= 1
mesh = make_mesh((p, ndev // p), ("data", "model"))
rng = np.random.default_rng(0)
a = rng.standard_normal((n, n)).astype(np.float32)
spd = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
nonsym = (a + n * np.eye(n)).astype(np.float32)
b = rng.standard_normal(n).astype(np.float32)
bj = jnp.asarray(b)

out = {}
for method, mat, kw in (("cg", spd, {}), ("ca_cg", spd, {"s": 4}),
                        ("ca_gmres", nonsym, {"s": 8})):
    mj = jnp.asarray(mat)
    with pblas.collective_counts() as c:
        fn = jax.jit(lambda A, B, m=method, k=kw: api.solve(
            A, B, method=m, tol=1e-6, maxiter=400, mesh=mesh,
            engine="spmd", **k))
        jax.block_until_ready(fn(mj, bj))          # trace+compile+warmup
    dots = c["dots"]
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(mj, bj))
        ts.append(time.perf_counter() - t0)
    x = np.asarray(fn(mj, bj))
    res = float(np.linalg.norm(b - mat @ x) / np.linalg.norm(b))
    out[method] = {"t": float(np.median(ts)), "dots": dots, "res": res}
print("RESULT " + json.dumps(out))
"""


def run_spmd(device_counts=(1, 2, 4, 8), n=1024):
    """cg vs ca_cg/ca_gmres wall time per host device count.

    Each row's note carries the trace-time reduction ("dots") tally —
    the communication-avoiding claim as a counted number — and a
    ``scaling_efficiency`` field (t at 1 dev / (ndev * t at ndev)).
    """
    t1 = {}                               # method -> wall at 1 device
    for ndev in device_counts:
        code = _SPMD_CHILD % {"ndev": ndev, "n": n,
                              "src": SRC}
        for method, r in run_child(code, ndev).items():
            if ndev == device_counts[0]:
                t1[method] = r["t"]
            eff = (f" scaling_efficiency={t1[method] / (ndev * r['t']):.2f}"
                   if method in t1 else "")
            emit("solvers_spmd", f"{method}_spmd_n{n}_ndev{ndev}",
                 round(r["t"] * 1e3, 2), "ms",
                 f"dots_trace={r['dots']} rel_res={r['res']:.1e}{eff}"
                 " (CPU emulation)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--spmd", action="store_true",
                    help="CA-Krylov wall time vs device count (1->8)")
    args = ap.parse_args(argv)
    if args.spmd:
        run_spmd(device_counts=(1, 8) if args.smoke else (1, 2, 4, 8),
                 n=512 if args.smoke else 1024)
    elif args.smoke:
        run(sizes=(256,), dtypes=("float32",))
    else:
        run()


if __name__ == "__main__":
    main()
