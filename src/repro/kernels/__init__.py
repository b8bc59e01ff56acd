"""Pallas TPU kernels for the compute hot spots (paper's CUDA level):

* ``gemm``         — MXU-tiled matmul (the delayed rank-k update / CUBLAS role)
* ``trsm``         — inverse-based block triangular solve (lower/upper, auto-pad)
* ``factor_fused`` — fused LU/Cholesky panel update (TRSM + rank-nb GEMM in
  one launch, masked for fori_loop block stepping)
* ``qr_fused``     — fused QR compact-WY trailing update (Vᵀ A projection +
  rank-nb product in one launch, same masked fori_loop contract)
* ``krylov_fused`` — fused CG/BiCGSTAB vector update + reduction
* ``spmv``         — BSR SpMV/SpMM (scalar-prefetch brick gather +
  block-GEMM accumulate in one launch)

``ops`` is the jit'd dispatch layer (TPU native / CPU interpret / jnp
fallback); ``ref`` holds the pure-jnp oracles the tests sweep against.
"""
from repro.kernels import ops, ref  # noqa: F401
