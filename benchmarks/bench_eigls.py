"""Least-squares & eigenvalue benchmark (PR 5 subsystem).

Rows emitted:

* ``qr_factor`` GFLOP/s vs the ``jnp.linalg.qr`` baseline (tall-skinny
  and square shapes, ref + pallas backends),
* LSQR / CGLS wall time + iterations on a rectangular dense system,
* ``--spmd``: TSQR wall time vs host device count (1 → 8 virtual
  devices, one subprocess each).  On this one-CPU container the device
  scaling is *emulation* — the curve shows collective overhead, not
  speedup (same caveat as bench_scaling / bench_direct --spmd),
* Lanczos iterations/second on the poisson_2d stencil (matrix-free BSR
  SpMV hot loop).

Standalone:  PYTHONPATH=src python -m benchmarks.bench_eigls
[--smoke|--spmd] (also the ``eigls`` / ``eigls_spmd`` sections of
``benchmarks.run``).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import SRC, emit, run_child, timeit
from repro.core import api, qr


def run(shapes=((2048, 256), (1024, 1024)), nb=128, ls_shape=(4096, 512),
        grid=48, ncv=150):
    # -- blocked QR GFLOP/s vs jnp.linalg.qr -------------------------------
    rng = np.random.default_rng(0)
    for m, n in shapes:
        a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
        flops = 2 * m * n * n - 2 / 3 * n ** 3      # Householder QR count
        for backend in ("ref", "pallas"):
            fn = jax.jit(lambda A, be=backend: qr.qr_factor(
                A, block_size=min(nb, n // 2 or n), backend=be).qr)
            t = timeit(fn, a)
            tb = timeit(jax.jit(jnp.linalg.qr), a)
            emit("eigls", f"qr_factor_{backend}_m{m}_n{n}",
                 round(flops / t / 1e9, 2), "gflops",
                 f"baseline_jnp={flops / tb / 1e9:.2f}")

    # -- iterative least squares (the acceptance shape) --------------------
    m, n = ls_shape
    a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    for method in ("lsqr", "cgls"):
        fn = jax.jit(lambda A, B, me=method: tuple(api.solve(
            A, B, method=me, tol=1e-5, maxiter=200, return_info=True)))
        t = timeit(fn, a, b)
        r = fn(a, b)
        emit("eigls", f"{method}_m{m}_n{n}", round(t * 1e3, 2), "ms",
             f"iters={int(r[1])} arnorm={float(r[2]):.1e}")

    # -- Lanczos iterations/s on the stencil (matrix-free SpMV loop) -------
    from repro.sparse import BSR, problems
    pa = problems.poisson_2d(grid)
    bsr = BSR.from_dense(pa, block_size=16)
    for backend in ("ref", "pallas"):
        fn = jax.jit(lambda d, be=backend: api.eigsolve(
            BSR(d, bsr.indices, bsr.indptr, bsr.shape, bsr.nb),
            k=5, which="LA", ncv=ncv, backend=be).eigenvalues)
        t = timeit(fn, bsr.data)
        emit("eigls", f"lanczos_{backend}_n{pa.shape[0]}_ncv{ncv}",
             round(ncv / t, 1), "iters/s",
             f"wall={t * 1e3:.1f}ms k=5")


# --------------------------------------------------------------------------
# --spmd: TSQR wall time vs device count (subprocess per count)
# --------------------------------------------------------------------------

_SPMD_CHILD = r"""
import sys, json, time
sys.path.insert(0, %(src)r)
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.eigls import tsqr

m, n, ndev = %(m)d, %(n)d, %(ndev)d
p = int(ndev ** 0.5)
while ndev %% p: p -= 1
mesh = make_mesh((p, ndev // p), ("data", "model"))
rng = np.random.default_rng(0)
a = rng.standard_normal((m, n)).astype(np.float32)
aj = jnp.asarray(a)

def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))

factor = jax.jit(lambda A: tsqr.tsqr_factor_spmd(A, mesh=mesh).q)
t = timed(factor, aj)
st = tsqr.tsqr_factor_spmd(aj, mesh=mesh)
res = float(np.abs(np.asarray(st.q) @ np.asarray(st.r) - a).max())
print("RESULT " + json.dumps({"t_factor": t, "err": res}))
"""


def run_spmd(device_counts=(1, 2, 4, 8), m=8192, n=256):
    flops = 2 * m * n * n - 2 / 3 * n ** 3
    for ndev in device_counts:
        code = _SPMD_CHILD % {"ndev": ndev, "m": m, "n": n,
                              "src": SRC}
        r = run_child(code, ndev)
        emit("eigls_spmd", f"tsqr_factor_m{m}_n{n}_ndev{ndev}",
             round(flops / r["t_factor"] / 1e9, 2), "gflops",
             f"wall={r['t_factor'] * 1e3:.1f}ms QR=A err={r['err']:.1e} "
             "(CPU emulation)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (fast, CPU-friendly)")
    ap.add_argument("--spmd", action="store_true",
                    help="TSQR GFLOP/s vs device count (1->8)")
    args = ap.parse_args(argv)
    if args.spmd:
        run_spmd(device_counts=(1, 2, 4, 8),
                 m=2048 if args.smoke else 8192,
                 n=128 if args.smoke else 256)
    elif args.smoke:
        run(shapes=((512, 128),), nb=64, ls_shape=(1024, 128), grid=32,
            ncv=60)
    else:
        run()


if __name__ == "__main__":
    main()
