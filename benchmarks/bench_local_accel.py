"""Paper §4 ablation analogue: CUDA-accelerated vs plain local BLAS.

The paper swapped CUBLAS for ATLAS and measured the drop.  Here the two
"local engines" are the Pallas kernels (TPU target; validated in interpret
mode) vs the plain-jnp reference path.  On this CPU container kernel wall
time is Python interpretation — meaningless — so the reported quantities
are: (a) oracle-vs-kernel max error (correctness of the swap), (b) the
modeled MXU-utilization of the kernel's BlockSpec tiling, (c) measured
wall of the jnp path (the "ATLAS" side, which XLA:CPU compiles natively).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.kernels import ops, ref
import repro.analysis.roofline as R


def _mxu_util(m, n, k, bm, bn, bk):
    """Fraction of MXU-aligned work for a given tiling (128-lane MXU)."""
    pad = lambda x, b: -(-x // b) * b
    useful = m * n * k
    padded = pad(m, bm) * pad(n, bn) * pad(k, bk)
    return useful / padded


def run():
    key = jax.random.key(0)
    k1, k2, k3 = jax.random.split(key, 3)

    # GEMM (the delayed rank-k update hot spot)
    m = n = k = 512
    a = jax.random.normal(k1, (m, k), jnp.float32)
    b = jax.random.normal(k2, (k, n), jnp.float32)
    c_kernel = ops.matmul(a, b, bm=256, bn=256, bk=256)
    c_ref = ref.matmul(a, b)
    err = float(jnp.max(jnp.abs(c_kernel - c_ref)))
    t_ref = timeit(jax.jit(ref.matmul), a, b)
    emit("local_accel", "gemm_kernel_vs_ref_err", f"{err:.2e}", "abs",
         "pallas interpret vs jnp oracle")
    emit("local_accel", "gemm_ref_wall", round(t_ref * 1e3, 3), "ms",
         "jnp path (the ATLAS analogue)")
    emit("local_accel", "gemm_mxu_alignment",
         round(_mxu_util(m, n, k, 256, 256, 256), 3), "frac",
         "BlockSpec (256,256,256) on 512^3")
    flops = 2 * m * n * k
    emit("local_accel", "gemm_v5e_model_time",
         f"{flops / R.PEAK_FLOPS_BF16:.2e}", "s",
         "512^3 GEMM at bf16 peak")

    # TRSM
    l = jnp.tril(jax.random.normal(k1, (256, 256))) + 4 * jnp.eye(256)
    bb = jax.random.normal(k2, (256, 256), jnp.float32)
    x_kernel = ops.trsm_lower(l, bb, sb=64, bc=128)
    x_ref = ref.trsm_lower(l, bb)
    emit("local_accel", "trsm_kernel_vs_ref_err",
         f"{float(jnp.max(jnp.abs(x_kernel - x_ref))):.2e}", "abs", "")

    # fused Krylov update: traffic saving is the point (6n → 4n read+2n write)
    nvec = 1 << 16
    x0 = jax.random.normal(k1, (nvec,), jnp.float32)
    r0 = jax.random.normal(k2, (nvec,), jnp.float32)
    p0 = jax.random.normal(k3, (nvec,), jnp.float32)
    ap = jax.random.normal(k1, (nvec,), jnp.float32)
    xk, rk, rrk = ops.fused_cg_update(x0, r0, p0, ap, 0.37)
    xr, rr_, rrr = ref.fused_cg_update(x0, r0, p0, ap, 0.37)
    emit("local_accel", "fused_cg_err",
         f"{float(jnp.max(jnp.abs(xk - xr))):.2e}", "abs", "")
    naive_bytes = 10 * nvec * 4     # x,r,p,ap reads ×(separate kernels) + writes
    fused_bytes = 6 * nvec * 4      # one pass: 4 reads + 2 writes
    emit("local_accel", "fused_cg_traffic_saving",
         round(naive_bytes / fused_bytes, 2), "x",
         "one-pass vs unfused Level-1 chain")
