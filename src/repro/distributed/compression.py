"""Int8 gradient compression with error feedback for cross-pod reduction.

At multi-pod scale the ``"pod"`` axis crosses DCN (data-center network),
which is ~10× slower than ICI — the cross-pod gradient all-reduce is the
scaling bottleneck.  This module implements the standard mitigation:

* **Block-wise int8 quantization** — per-block (128 values) max-abs scale,
  symmetric int8 payload: 4× fewer wire bytes than fp32 (2× vs bf16).
* **Error feedback (EF)** — the quantization residual is carried into the
  next step's gradient, making the compression *unbiased over time* (Seide
  et al.; 1-bit SGD lineage).  Without EF, int8 rounding bias stalls
  convergence; with it, training curves track the uncompressed baseline
  (tests/test_compression.py).
* **Ring all-reduce with an int8 wire format** — reduce-scatter +
  all-gather via ``lax.ppermute`` where every hop transmits int8+scales;
  accumulation happens in fp32 after dequantize.  This is the explicit
  (shard_map) schedule — wire bytes really are int8-sized, unlike a psum
  wrapped in quant/dequant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BLOCK = 128


def _pad_to(x, m):
    n = x.size
    pad = (-n) % m
    return jnp.pad(x.reshape(-1), (0, pad)), n


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array, int]:
    """x (any shape) → (int8 payload (Nb, BLOCK), scales (Nb,), orig_size)."""
    flat, n = _pad_to(x.astype(jnp.float32), BLOCK)
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(blocks / safe[:, None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32), n


def dequantize_int8(q: jax.Array, scale: jax.Array, n: int, shape) -> jax.Array:
    out = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n]
    return out.reshape(shape)


def _roundtrip_with_ef(g, ef):
    """Quantize (g + ef); return (dequantized value, new error feedback)."""
    target = g.astype(jnp.float32) + ef.astype(jnp.float32)
    q, s, n = quantize_int8(target)
    deq = dequantize_int8(q, s, n, g.shape)
    return deq, (target - deq)


def ring_allreduce_int8(x: jax.Array, axis: str) -> jax.Array:
    """All-reduce along a shard_map axis with int8 wire format.

    Ring reduce-scatter then ring all-gather; every hop sends int8 chunks +
    fp32 block scales.  Must be called inside ``shard_map`` with ``axis``
    mapped.  x is this device's (identical-shape) contribution.
    """
    n = jax.lax.psum(1, axis)    # static axis size (lax.axis_size drifted)
    if n == 1:
        return x
    i = jax.lax.axis_index(axis)
    flat, orig = _pad_to(x.astype(jnp.float32), n * BLOCK)
    chunks = flat.reshape(n, -1)                    # (n, chunk)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # ---- reduce-scatter: after n-1 hops, device i owns the full sum of
    # chunk (i+1) % n ------------------------------------------------------
    def rs_body(t, carry):
        acc, send_idx = carry
        # quantize the chunk we forward (wire format: int8 + scales)
        chunk = acc[send_idx]
        q, s, nn = quantize_int8(chunk)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv = dequantize_int8(q, s, nn, chunk.shape)
        recv_idx = (send_idx - 1) % n
        acc = acc.at[recv_idx].add(recv)
        return acc, recv_idx

    acc, owned = jax.lax.fori_loop(0, n - 1, rs_body, (chunks, i))

    # ---- all-gather: circulate the owned (fully-reduced) chunk ------------
    def ag_body(t, carry):
        acc, send_idx = carry
        chunk = acc[send_idx]
        q, s, nn = quantize_int8(chunk)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv = dequantize_int8(q, s, nn, chunk.shape)
        recv_idx = (send_idx - 1) % n
        acc = acc.at[recv_idx].set(recv)
        return acc, recv_idx

    acc, _ = jax.lax.fori_loop(0, n - 1, ag_body, (acc, owned))
    return acc.reshape(-1)[:orig].reshape(x.shape).astype(x.dtype)


def compressed_pod_allreduce(grads, ef, mesh: Mesh, pspecs):
    """Mean-reduce grads across the ``"pod"`` axis with int8 + EF.

    grads arrive already summed over ``"data"`` (GSPMD did that inside the
    backward pass); this performs the remaining cross-pod mean with the
    compressed wire format.  Returns (reduced grads, new error feedback).
    """
    if "pod" not in mesh.axis_names:
        return grads, ef
    npods = mesh.shape["pod"]

    def body(g_and_ef):
        g, e = g_and_ef

        def one(gl, el):
            val, new_e = _roundtrip_with_ef(gl / npods, el)
            red = ring_allreduce_int8(val, "pod")
            return red.astype(jnp.float32), new_e

        flat_g, tdef = jax.tree.flatten(g)
        flat_e = tdef.flatten_up_to(e)
        out = [one(gl, el) for gl, el in zip(flat_g, flat_e)]
        return (tdef.unflatten([o[0] for o in out]),
                tdef.unflatten([o[1] for o in out]))

    # params/grads replicated over "pod"; sharded per pspecs inside a pod.
    specs = jax.tree.map(lambda s: s, pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    f = shard_map(body, mesh=mesh, in_specs=((specs, specs),),
                  out_specs=(specs, specs), check_vma=False,
                  axis_names=frozenset({"pod"}))
    return f((grads, ef))
