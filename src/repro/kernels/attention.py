"""Flash-attention forward Pallas kernel (online softmax, GQA, causal,
optional sliding window).

This is the LM stack's perf-critical hot spot (prefill_32k / train_4k
shapes).  TPU adaptation of the FlashAttention tiling: the kv dimension is
the *sequential* innermost grid axis with the running (m, l, acc) carried in
VMEM scratch across iterations — HBM traffic is O(T·d) per head instead of
O(T²).  The sliding-window mask makes the same kernel serve hymba-1.5b's
window attention (long_500k shapes).

Layout: q (B, Hq, Tq, D), k/v (B, Hkv, Tk, D); grid (B, Hq, Tq/bq, Tk/bk);
kv-head index map folds the GQA group (Hq // Hkv) so no repeat is
materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  bq: int, bk: int, n_kv: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions of this tile (q_offset aligns q/k ends: Tk - Tq)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: entire tile masked out → no compute
    first_q = iq * bq + q_offset
    last_q = first_q + bq - 1
    first_k = ik * bk
    live = True
    if causal:
        live = first_k <= last_q
    if window is not None:
        live = jnp.logical_and(live, ik * bk + bk - 1 > first_q - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1)[:, None]
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _done():
        denom = jnp.where(l_ref[...] == 0, 1.0, l_ref[...])
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 128,
                    bk: int = 128, interpret: bool = False) -> jax.Array:
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bq = min(bq, tq)
    bk = min(bk, tk)
    if tq % bq or tk % bk:
        raise ValueError(f"seq lens {(tq, tk)} not tiled by {(bq, bk)}")
    scale = (d ** -0.5) if scale is None else scale
    grid = (b, hq, tq // bq, tk // bk)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv=grid[3], q_offset=tk - tq)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, iq, ik: (b_, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        **params,
    )(q, k, v)
