"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU the compiled kernels run natively; on CPU they
execute in ``interpret=True`` mode, which runs the kernel body in Python
for correctness validation.  ``use_pallas=False`` falls back to the
pure-jnp oracle (``ref.py``).

This module is the "architecture independence" shim of the paper's level 2:
callers never know which backend executed the math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import gemm as _gemm
from repro.kernels import krylov_fused as _krylov_fused
from repro.kernels import ref as _ref
from repro.kernels import trsm as _trsm


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def matmul(a, b, *, use_pallas: bool = True, **kw):
    if not use_pallas:
        return _ref.matmul(a, b)
    return _gemm.matmul(a, b, interpret=not _on_tpu(), **kw)


def trsm_lower(l, b, *, unit_diagonal: bool = False, use_pallas: bool = True,
               **kw):
    if not use_pallas:
        return _ref.trsm_lower(l, b, unit_diagonal=unit_diagonal)
    return _trsm.trsm_lower(l, b, unit_diagonal=unit_diagonal,
                            interpret=not _on_tpu(), **kw)


def fused_cg_update(x, r, p, ap, alpha, *, use_pallas: bool = True, **kw):
    if not use_pallas:
        return _ref.fused_cg_update(x, r, p, ap, alpha)
    return _krylov_fused.fused_cg_update(x, r, p, ap, alpha,
                                         interpret=not _on_tpu(), **kw)
