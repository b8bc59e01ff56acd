"""Global runtime switches (kept tiny on purpose).

``use_pallas``: whether models route hot spots through the Pallas kernels.
Defaults to True only on a real TPU backend; the CPU container and the
512-device dry-run take the pure-jnp paths (same math — see
repro.kernels.ops docstring).

``mixer_cp``: context-parallel resharding helper for sequence-mixer blocks
whose head counts do not divide the TP axis (hymba's 25 heads, mamba2's
uneven in_proj split points).  Without it GSPMD replicates the whole mixer
across ``"model"`` — 16× redundant HBM traffic (EXPERIMENTS.md §Perf,
hymba hc1 iteration 3).  The constraint shards the *batch* over every mesh
axis inside the mixer; the tiny mixer weights are all-gathered instead.
No-ops when there is no ambient mesh or the batch does not divide.
"""
from __future__ import annotations

import contextlib
import math

import jax
from jax.sharding import PartitionSpec as P

_FORCED: bool | None = None


def _ambient_mesh():
    """The ambient abstract mesh, or None when there is none (or it has
    no axes) — every helper below is a no-op then."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or not mesh.axis_names:
        return None
    return mesh


def _data_axes(mesh) -> tuple[tuple[str, ...], int]:
    """The data-parallel axes (every axis but "model") and their size."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    return dp, math.prod(mesh.shape[a] for a in dp)


def _constrain(x, *lead):
    return jax.lax.with_sharding_constraint(
        x, P(*lead, *([None] * (x.ndim - len(lead)))))


def mixer_cp(x):
    """Reshard (B, S, d) activations to batch-over-ALL-axes, if possible."""
    mesh = _ambient_mesh()
    if mesh is None or x.shape[0] % mesh.size:
        return x
    return _constrain(x, tuple(mesh.axis_names))


def tokens_shard(x):
    """(T, d) flattened-token tensors: shard T over the DP axes.  The MoE
    dispatch's sort/gather otherwise pushes GSPMD into replicating tokens
    everywhere (measured: kimi-k2 attention ran at global batch per
    device)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    dp, total = _data_axes(mesh)
    if not dp or x.shape[0] % total:
        return x
    return _constrain(x, dp)


def expert_shard(x):
    """(E, C, ...) expert-dispatch tensors: experts over "model" (EP),
    capacity rows over "data" — the expert einsums then run fully
    sharded instead of replicated."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    parts = [None] * x.ndim
    if "model" in mesh.axis_names and x.shape[0] % mesh.shape["model"] == 0:
        parts[0] = "model"
    if "data" in mesh.axis_names and x.ndim > 1 \
            and x.shape[1] % mesh.shape["data"] == 0:
        parts[1] = "data"
    if not any(parts):
        return x
    return jax.lax.with_sharding_constraint(x, P(*parts))


def replicate_heads(x):
    """(B, H, T, D) k/v: batch on DP, everything else replicated — one
    gather per layer instead of one per chunk-scan step."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    dp, total = _data_axes(mesh)
    return _constrain(x, dp if (dp and x.shape[0] % total == 0) else None)


def seq_shard(x):
    """Sequence parallelism: shard (B, S, ...) activations' sequence dim
    over "model" at layer boundaries.  Norms/residual adds then compute
    1/TP per device and GSPMD turns the row-parallel all-reduce into
    reduce-scatter (+ all-gather at the next column-parallel matmul) —
    halving wire bytes per Megatron-SP.  No-op without an ambient mesh."""
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    if x.ndim < 2 or x.shape[1] % mesh.shape["model"]:
        return x
    dp, total = _data_axes(mesh)
    bspec = dp if (dp and x.shape[0] % total == 0) else None
    return _constrain(x, bspec, "model")


# reshard mixer output back to batch-over-DP (TP axes free again)
mixer_cp_out = tokens_shard


def use_pallas() -> bool:
    if _FORCED is not None:
        return _FORCED
    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def force_pallas(value: bool | None):
    global _FORCED
    prev = _FORCED
    _FORCED = value
    try:
        yield
    finally:
        _FORCED = prev
