"""Direct-path benchmark (paper §4, factorization half).

Rows emitted:

* ``lu_factor`` / ``cholesky_factor`` GFLOP/s vs the
  ``jax.scipy.linalg.lu_factor`` / ``cholesky`` baselines,
* factor + solve wall time per method,
* an unrolled-vs-fori **trace+lower time** comparison — the point of the
  PR 2 rewrite: the Python-unrolled block loop's trace grows O(n / nb)
  while the ``lax.fori_loop`` version is O(1) in ``n``,
* ``--spmd``: block-cyclic distributed LU GFLOP/s vs host device count
  (1 → 8 virtual devices, one subprocess each — XLA fixes the device
  count at first init), each row carrying a ``scaling_efficiency``
  field plus a ``lu_spmd_mono`` summary row (worst successive-ratio of
  the curve) that ``check_regression`` gates against collapse.  On this
  one-CPU container the device scaling is *emulation* (all "devices"
  share the silicon, so the curve shows collective overhead, not
  speedup) — the same caveat as bench_scaling.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_direct
[--smoke|--spmd] (also the ``direct`` / ``direct_spmd`` sections of
``benchmarks.run``).
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import solve_triangular

from benchmarks.common import SRC, emit, make_system, run_child, timeit
from repro import telemetry
from repro.core import api, cholesky, lu


# --------------------------------------------------------------------------
# pre-PR-2 reference: Python-unrolled outer block loop (the seed's
# structure) — kept ONLY for the compile-time comparison row
# --------------------------------------------------------------------------

def _panel_factor_unrolled(pan):
    m, nb = pan.shape
    rows = jnp.arange(m)

    def col_step(j, carry):
        pan, perm = carry
        col = pan[:, j]
        cand = jnp.where(rows >= j, jnp.abs(col), -jnp.inf)
        p = jnp.argmax(cand)
        row_j, row_p = pan[j, :], pan[p, :]
        pan = pan.at[j, :].set(row_p).at[p, :].set(row_j)
        pj, pp = perm[j], perm[p]
        perm = perm.at[j].set(pp).at[p].set(pj)
        pivot = pan[j, j]
        safe = jnp.where(pivot == 0, jnp.asarray(1, pan.dtype), pivot)
        col = pan[:, j]
        mcol = jnp.where(rows > j, col / safe, col)
        pan = pan.at[:, j].set(mcol)
        urow = pan[j, :]
        mmask = jnp.where(rows > j, mcol, 0)
        umask = jnp.where(jnp.arange(nb) > j, urow, 0)
        pan = pan - jnp.outer(mmask, umask)
        return pan, perm

    return jax.lax.fori_loop(0, nb, col_step, (pan, jnp.arange(m)))


def _lu_factor_unrolled(a, nb):
    """Trace-time-unrolled blocked LU: O(n / nb) trace size."""
    n = a.shape[0]
    perm_total = jnp.arange(n)
    for k in range(0, n, nb):
        pan, perm = _panel_factor_unrolled(a[k:, k:k + nb])
        rows_blk = jnp.take(a[k:, :], perm, axis=0)
        rows_blk = rows_blk.at[:, k:k + nb].set(pan)
        a = a.at[k:, :].set(rows_blk)
        perm_total = perm_total.at[k:].set(jnp.take(perm_total[k:], perm))
        if k + nb < n:
            l11 = a[k:k + nb, k:k + nb]
            u12 = solve_triangular(l11, a[k:k + nb, k + nb:], lower=True,
                                   unit_diagonal=True)
            a = a.at[k:k + nb, k + nb:].set(u12)
            upd = a[k + nb:, k + nb:] - a[k + nb:, k:k + nb] @ u12
            a = a.at[k + nb:, k + nb:].set(upd)
    return a, perm_total


def _trace_lower_ms(fn, n):
    spec = jax.ShapeDtypeStruct((n, n), jnp.float32)
    t0 = time.perf_counter()
    jax.jit(fn).lower(spec)
    return (time.perf_counter() - t0) * 1e3


def run(sizes=(512, 1024), compile_sizes=(256, 512, 1024), nb=128):
    for n in sizes:
        bs = min(nb, n // 2)
        a, b = make_system(n, spd=False)
        spd, _ = make_system(n, spd=True)
        aj, bj, sj = jnp.asarray(a), jnp.asarray(b), jnp.asarray(spd)

        # -- factor GFLOP/s vs jax.scipy baselines -------------------------
        for name, fn, base, mat, flops in (
                ("lu", functools.partial(lu.lu_factor, block_size=bs),
                 jax.scipy.linalg.lu_factor, aj, 2 / 3 * n ** 3),
                ("cholesky",
                 functools.partial(cholesky.cholesky_factor, block_size=bs),
                 jax.scipy.linalg.cholesky, sj, 1 / 3 * n ** 3)):
            t = timeit(jax.jit(fn), mat)
            tb = timeit(jax.jit(base), mat)
            emit("direct", f"{name}_factor_n{n}", round(flops / t / 1e9, 2),
                 "gflops", f"baseline_jsp={flops / tb / 1e9:.2f}")

        # -- factor + solve wall time per method/backend -------------------
        for method, mat, ref_mat in (("lu", aj, a), ("cholesky", sj, spd)):
            for backend in ("ref", "pallas"):
                fn = jax.jit(lambda A, B, m=method, be=backend: api.solve(
                    A, B, method=m, block_size=bs, backend=be))
                t = timeit(fn, mat, bj)
                x = np.asarray(fn(mat, bj))
                res = float(np.linalg.norm(b - ref_mat @ x)
                            / np.linalg.norm(b))
                emit("direct", f"{method}_solve_{backend}_n{n}",
                     round(t * 1e3, 2), "ms", f"rel_res={res:.1e}")

        # -- telemetry armed-overhead probe (direct path) ------------------
        # Instrumented eager solves for the TELEM solve records (under
        # perf=True these route through the observatory's AOT
        # executables and gain roofline/memory perf records), then the
        # same jitted LU solve timed disarmed vs armed (direct solves
        # add a fixed-shape info dict, no loop-carried state; <= 5%).
        api.solve(aj, bj, method="lu", block_size=bs, return_info=True)
        api.solve(sj, bj, method="cholesky", block_size=bs,
                  return_info=True)
        fn_off = jax.jit(lambda A, B: api.solve(A, B, method="lu",
                                                block_size=bs))
        fn_on = jax.jit(lambda A, B: api.solve(A, B, method="lu",
                                               block_size=bs))
        ratios = []
        for _ in range(3):       # alternate + median: warm-up-state noise
            with telemetry.disabled():
                t_off = timeit(fn_off, aj, bj, warmup=2, iters=10)
            with telemetry.session("overhead-probe"):
                t_on = timeit(fn_on, aj, bj, warmup=2, iters=10)
            ratios.append(t_on / t_off)
        emit("direct", f"telemetry_overhead_lu_n{n}",
             round(float(np.median(ratios)), 3), "ratio",
             f"armed {t_on * 1e3:.2f} ms vs disarmed {t_off * 1e3:.2f} ms, "
             f"3 rounds (contract: <= 1.05)")

        # -- batched throughput --------------------------------------------
        B = 8
        ab = jnp.asarray(np.stack([a] * B))
        bb = jnp.asarray(np.stack([b] * B))
        fn = jax.jit(lambda A, Bv: api.solve(A, Bv, method="lu",
                                             block_size=bs))
        t = timeit(fn, ab, bb)
        emit("direct", f"lu_batched_B{B}_n{n}", round(t * 1e3 / B, 2),
             "ms/system", "vmapped fori_loop factorization")

    # -- unrolled-vs-fori trace+lower time (the compile-time win) ----------
    for n in compile_sizes:
        t_unrolled = _trace_lower_ms(
            functools.partial(_lu_factor_unrolled, nb=nb), n)
        t_fori = _trace_lower_ms(
            functools.partial(lu.lu_factor, block_size=nb), n)
        emit("direct", f"lu_trace_lower_n{n}", round(t_fori, 1), "ms",
             f"unrolled={t_unrolled:.1f}ms steps={n // nb}")


# --------------------------------------------------------------------------
# --spmd: distributed (block-cyclic shard_map) LU vs device count
# --------------------------------------------------------------------------

_SPMD_CHILD = r"""
import sys, json, time
sys.path.insert(0, %(src)r)
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core import lu

n, nb, ndev = %(n)d, %(nb)d, %(ndev)d
p = int(ndev ** 0.5)
while ndev %% p: p -= 1
mesh = make_mesh((p, ndev // p), ("data", "model"))
rng = np.random.default_rng(0)
a = (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)
b = rng.standard_normal(n).astype(np.float32)
aj, bj = jnp.asarray(a), jnp.asarray(b)

def timed(fn, *args):
    jax.block_until_ready(fn(*args))              # warmup / compile
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))

factor = jax.jit(lambda A: lu.lu_factor_spmd(
    A, block_size=nb, mesh=mesh).lu)
t_factor = timed(factor, aj)
state = lu.lu_factor_spmd(aj, block_size=nb, mesh=mesh)
apply = jax.jit(lambda B: lu.lu_apply_spmd(state, B))
t_solve = timed(apply, bj)
x = np.asarray(apply(bj))
res = float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))
print("RESULT " + json.dumps(
    {"t_factor": t_factor, "t_solve": t_solve, "res": res}))
"""


def run_resilience(n=1024, nb=64):
    """ABFT checksum overhead (docs/resilience.md).

    Times the carried-checksum factorization (``abft=True``) against the
    unchecked one — same mesh, same schedule; the checksum update is
    O(n·nb) per step against the O(n²·nb) trailing GEMM, plus a constant
    number of exit reductions.  Acceptance budget: <= 10% (ratio <= 1.10)
    at n=1024.  Both jitted functions return the checksum error alongside
    the factor so XLA cannot dead-code-eliminate the checksum column.
    """
    from repro.core import dist
    mesh = dist.single_device_mesh()
    a, _ = make_system(n, spd=False)
    spd, _ = make_system(n, spd=True)
    for name, factor, mat, field in (
            ("lu", lu.lu_factor_spmd, a, "lu"),
            ("cholesky", cholesky.cholesky_factor_spmd, spd, "l")):
        mj = jnp.asarray(mat)

        def plain(A, f=factor, fl=field):
            return getattr(f(A, block_size=nb, mesh=mesh), fl)

        def checked(A, f=factor, fl=field):
            st = f(A, block_size=nb, mesh=mesh, abft=True)
            return getattr(st, fl), st.abft_err

        t0 = timeit(jax.jit(plain), mj)
        t1 = timeit(jax.jit(checked), mj)
        emit("direct_spmd", f"resilience_overhead_{name}_n{n}",
             round(t1 / t0, 3), "ratio",
             f"abft={t1 * 1e3:.1f}ms plain={t0 * 1e3:.1f}ms budget<=1.10 "
             f"(CPU emulation)")


def run_spmd(device_counts=(1, 2, 4, 8), n=1024, nb=64):
    """GFLOP/s of the distributed LU factorization vs host device count.

    Emits one gflops row per device count with a ``scaling_efficiency``
    field (GFLOP/s at ndev / (ndev * GFLOP/s at 1), the strong-scaling
    parallel efficiency) plus a ``lu_spmd_mono_n{n}`` summary row: the
    worst GFLOP/s ratio between successive device counts.  The default
    n is 1024 — large enough that the per-step panel broadcast is
    amortized against the O(n^2 nb) trailing update, which is what a
    strong-scaling measurement needs (at n=512 the curve measures
    collective latency, not the factorization).
    """
    flops = 2 / 3 * n ** 3
    curve = []                      # (ndev, gflops) for the summary row
    for ndev in device_counts:
        code = _SPMD_CHILD % {"ndev": ndev, "n": n, "nb": nb,
                              "src": SRC}
        r = run_child(code, ndev)
        gflops = flops / r["t_factor"] / 1e9
        curve.append((ndev, gflops))
        g1 = curve[0][1] if curve[0][0] == 1 else None
        eff = (f" scaling_efficiency={gflops / (ndev * g1):.2f}"
               if g1 else "")
        emit("direct_spmd", f"lu_spmd_factor_n{n}_ndev{ndev}",
             round(gflops, 2), "gflops",
             f"wall={r['t_factor'] * 1e3:.1f}ms{eff} (CPU emulation)")
        emit("direct_spmd", f"lu_spmd_solve_n{n}_ndev{ndev}",
             round(r["t_solve"] * 1e3, 2), "ms",
             f"rel_res={r['res']:.1e} (CPU emulation)")
    if len(curve) >= 2:
        ratios = [curve[i + 1][1] / curve[i][1]
                  for i in range(len(curve) - 1)]
        shape = " -> ".join(f"{g:.2f}@{d}" for d, g in curve)
        emit("direct_spmd", f"lu_spmd_mono_n{n}", round(min(ratios), 3),
             "ratio", f"worst successive-device-count GFLOP/s ratio; "
             f"curve {shape} (CPU emulation)")
    run_resilience(n=n, nb=nb)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (fast, CPU-friendly)")
    ap.add_argument("--spmd", action="store_true",
                    help="distributed LU GFLOP/s vs device count (1->8)")
    args = ap.parse_args(argv)
    if args.spmd:
        run_spmd(device_counts=(1, 2, 8) if args.smoke else (1, 2, 4, 8),
                 n=1024, nb=64)
    elif args.smoke:
        run(sizes=(256,), compile_sizes=(256, 512), nb=64)
    else:
        run()


if __name__ == "__main__":
    main()
