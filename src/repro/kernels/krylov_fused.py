"""Fused Krylov vector-update kernel (memory-bound hot spot of the paper's
iterative methods).

A CG/BiCGSTAB step performs x += αp; r -= αAp; ρ = <r, r> — four O(n)
streams read + two written + a reduction if done naively (6n traffic plus a
separate reduction pass).  This kernel fuses all three into a single pass
(4n read + 2n write, reduction for free), the TPU analogue of the paper's
"replace several CUBLAS Level-1 calls with one fused kernel" local
optimization.  Vectors are viewed as (rows, 128) so the lane dimension is
hardware-aligned; the partial <r,r> is accumulated across the sequential
grid in SMEM-like (1,1) scratch and written once at the end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _fused_kernel(alpha_ref, x_ref, r_ref, p_ref, ap_ref,
                  xo_ref, ro_ref, rr_ref, acc_ref, *, n_steps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    alpha = alpha_ref[0]
    x = x_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    ap = ap_ref[...].astype(jnp.float32)
    xn = x + alpha * p
    rn = r - alpha * ap
    xo_ref[...] = xn.astype(xo_ref.dtype)
    ro_ref[...] = rn.astype(ro_ref.dtype)
    acc_ref[...] += jnp.sum(rn * rn)[None, None]

    @pl.when(i == n_steps - 1)
    def _done():
        rr_ref[...] = acc_ref[...]


def fused_cg_update(x: jax.Array, r: jax.Array, p: jax.Array, ap: jax.Array,
                    alpha, *, block_rows: int = 256,
                    interpret: bool = False):
    """Returns (x + αp, r − αAp, <r', r'>) in one memory pass."""
    (n,) = x.shape
    if n % _LANE:
        raise ValueError(f"n={n} must be a multiple of {_LANE}")
    rows = n // _LANE
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows={rows} not tiled by {br}")
    n_steps = rows // br

    def as2d(v):
        return v.reshape(rows, _LANE)

    alpha_arr = jnp.asarray([alpha], jnp.float32)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))

    vec_spec = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    xo, ro, rr = pl.pallas_call(
        functools.partial(_fused_kernel, n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # alpha scalar
            vec_spec, vec_spec, vec_spec, vec_spec,
        ],
        out_specs=[
            vec_spec, vec_spec,
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANE), x.dtype),
            jax.ShapeDtypeStruct((rows, _LANE), r.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
        **params,
    )(alpha_arr, as2d(x), as2d(r), as2d(p), as2d(ap))
    return xo.reshape(n), ro.reshape(n), rr[0, 0]


# --------------------------------------------------------------------------
# Hot-path dispatch helpers: arbitrary n (auto zero-pad to the 128-lane
# constraint — padding contributes 0 to every reduction) and automatic
# interpret-mode fallback off-TPU.  This is what the LinearOperator dense
# engine calls from inside the solver loops.
# --------------------------------------------------------------------------

def _auto_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _pad_lanes(vs):
    # pad to a multiple of 8 rows (f32 min sublane tile), not just _LANE,
    # so _pick_block_rows always finds an aligned block — zero-pads are
    # exact for all these reductions.
    n = vs[0].shape[0]
    pad = (-n) % (8 * _LANE)
    if pad:
        vs = [jnp.pad(v, (0, pad)) for v in vs]
    return vs, n


def _pick_block_rows(rows: int, block_rows: int) -> int:
    """Largest multiple of 8 (the f32 sublane tile) that divides ``rows``
    and is at most ``block_rows``; the whole of ``rows`` when none does
    (a full-extent block needs no alignment)."""
    for br in range(min(block_rows, rows) // 8 * 8, 0, -8):
        if rows % br == 0:
            return br
    return rows


def fused_cg_update_auto(x, r, p, ap, alpha, *, block_rows: int = 256,
                         interpret: bool | None = None):
    """``fused_cg_update`` for arbitrary n: zero-pads to a lane multiple
    (exact — pads add 0 to ⟨r', r'⟩), slices the outputs back."""
    (x, r, p, ap), n = _pad_lanes([x, r, p, ap])
    br = _pick_block_rows(x.shape[0] // _LANE, block_rows)
    xo, ro, rr = fused_cg_update(x, r, p, ap, alpha, block_rows=br,
                                 interpret=_auto_interpret(interpret))
    return xo[:n], ro[:n], rr


def _dots_kernel(r_ref, u_ref, w_ref, out_ref, acc_ref, *, n_steps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = r_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.stack(
        [jnp.sum(r * u), jnp.sum(w * u), jnp.sum(r * r)])[None, :]

    @pl.when(i == n_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...]


def fused_pipelined_dots(r: jax.Array, u: jax.Array, w: jax.Array, *,
                         block_rows: int = 256, interpret: bool = False):
    """Pipelined-CG reduction: (⟨r,u⟩, ⟨w,u⟩, ⟨r,r⟩) in ONE memory pass
    (3n read, no vector writes) — the single-synchronization step of
    Chronopoulos–Gear CG (Rupp et al. 1410.4054 kernel fusion)."""
    (n,) = r.shape
    if n % _LANE:
        raise ValueError(f"n={n} must be a multiple of {_LANE}")
    rows = n // _LANE
    br = _pick_block_rows(rows, block_rows)
    n_steps = rows // br

    def as2d(v):
        return v.reshape(rows, _LANE)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))

    vec_spec = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_dots_kernel, n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[vec_spec, vec_spec, vec_spec],
        out_specs=pl.BlockSpec((1, 3), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 3), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 3), jnp.float32)],
        interpret=interpret,
        **params,
    )(as2d(r), as2d(u), as2d(w))
    return out[0, 0], out[0, 1], out[0, 2]


def fused_pipelined_dots_auto(r, u, w, *, block_rows: int = 256,
                              interpret: bool | None = None):
    """``fused_pipelined_dots`` for arbitrary n (zero-pad is exact)."""
    (r, u, w), _ = _pad_lanes([r, u, w])
    return fused_pipelined_dots(r, u, w, block_rows=block_rows,
                                interpret=_auto_interpret(interpret))


# --------------------------------------------------------------------------
# Fused Gram reduction (s-step / communication-avoiding Krylov): all k²
# basis inner products G = V Vᵀ in ONE pass over the (k, n) row-stack —
# the block analogue of ``fused_pipelined_dots`` (k(k+1)/2 distinct dots
# for the price of one read of V), accumulated across the sequential
# column-chunk grid in a VMEM scratch tile and written once at the end.
# --------------------------------------------------------------------------

def _gram_kernel(m_ref, out_ref, acc_ref, *, n_steps: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mb = m_ref[...].astype(jnp.float32)            # (k_pad, bc) chunk
    acc_ref[...] += jnp.dot(mb, mb.T, preferred_element_type=jnp.float32)

    @pl.when(i == n_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...]


def fused_gram(m: jax.Array, *, block_cols: int = 2048,
               interpret: bool = False) -> jax.Array:
    """G = m @ m.T for a (k, n) row-stack in one memory pass; returns the
    (k, k) float32 Gram matrix.  ``k`` must be a multiple of 8 (sublane
    tile) and ``n`` a multiple of 128 (lane tile)."""
    k, n = m.shape
    if k % 8:
        raise ValueError(f"k={k} must be a multiple of 8")
    if n % _LANE:
        raise ValueError(f"n={n} must be a multiple of {_LANE}")
    bc = _LANE * _pick_block_rows(n // _LANE, block_cols // _LANE)
    n_steps = n // bc

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))

    out = pl.pallas_call(
        functools.partial(_gram_kernel, n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((k, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, k), jnp.float32)],
        interpret=interpret,
        **params,
    )(m)
    return out


def fused_gram_auto(m: jax.Array, *, block_cols: int = 2048,
                    interpret: bool | None = None) -> jax.Array:
    """``fused_gram`` for arbitrary (k, n): zero-pads rows to the sublane
    tile and columns to 8 lane tiles, so the column chunk stays a few
    lane tiles wide (pads contribute exact 0 to every Gram entry), slices
    the (k, k) result back, restores the dtype."""
    k, n = m.shape
    pad_k, pad_n = (-k) % 8, (-n) % (8 * _LANE)
    if pad_k or pad_n:
        m = jnp.pad(m, ((0, pad_k), (0, pad_n)))
    g = fused_gram(m, block_cols=block_cols,
                   interpret=_auto_interpret(interpret))
    return g[:k, :k].astype(m.dtype)
