"""Warm compiled-executable cache for the solve server.

Throughput on accelerators comes from amortizing trace + XLA-compile
cost across many requests: a mixed stream of small solves spends more
wall time compiling than solving unless executables persist.  This
module keeps one process-wide LRU of jit-compiled solve executables
keyed on everything that changes the compiled program —
``(method, engine, backend, padded shape, dtype, precond spec, solver
options)`` — built through the cache-aware dispatch hook
:func:`repro.core.api.make_executable`.

* :func:`make_key` / :class:`CacheKey` — the canonical key.  Shapes are
  *padded* shapes (bucket rungs, see :mod:`repro.serve.bucket`), so
  heterogeneous request sizes collapse onto O(log n) keys.
* :meth:`ExecutableCache.get_or_build` — LRU lookup; hit/miss/eviction
  counters land in the :mod:`repro.telemetry.metrics` registry
  (``serve_cache_hits`` / ``serve_cache_misses`` /
  ``serve_cache_evictions``, gauge ``serve_cache_size``).
* :meth:`ExecutableCache.warm` — explicit prefill: builds each key's
  executable and drives one dummy solve through it, so the first real
  request hits jit's populated dispatch cache instead of a compile.
* warmth across process restarts is JAX's persistent compilation
  cache, which :func:`repro.compile_cache.enable` places.

Also home to :func:`fingerprint`, the content hash the server's
repeated-A fast path keys cached factorizations on.
"""
from __future__ import annotations

import functools
import hashlib
import time
from collections import OrderedDict
from typing import Callable, Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.telemetry import metrics
from repro.telemetry import perf as perf_mod


class CacheKey(NamedTuple):
    """Everything that changes the compiled program — nothing more.

    ``shape`` is the padded operand shape: ``(n, n)`` for single
    systems, ``(B, n, n)`` for a coalesced micro-batch.  ``opts`` is a
    sorted tuple of ``(name, value)`` pairs covering ``tol`` /
    ``maxiter`` / ``restart`` plus any registry-declared method extras
    (``s=`` for the CA methods), so two configurations that trace
    different programs never share an executable."""
    method: str
    engine: str
    backend: str
    shape: tuple
    dtype: str
    precond: str | None = None
    mode: str = "solve"           # "solve" | "factor" | "apply"
    opts: tuple = ()


def make_key(method: str, n: int, dtype, *, batch: int | None = None,
             engine: str = "gspmd", backend: str = "ref",
             precond: str | None = None, mode: str = "solve",
             **opts) -> CacheKey:
    """Build a :class:`CacheKey` from request-level parameters.  ``n``
    must already be the padded (bucket) size."""
    if precond is not None and not isinstance(precond, str):
        raise TypeError(
            f"cache keys need a *named* preconditioner spec (e.g. "
            f"'jacobi'), not {type(precond).__name__} — callables are "
            "not stable cache identities")
    shape = (n, n) if batch is None else (int(batch), n, n)
    return CacheKey(method, engine, backend, shape,
                    str(np.dtype(dtype)), precond, mode,
                    tuple(sorted(opts.items())))


def fingerprint(a) -> str:
    """Content hash of a matrix — the repeated-A factor-reuse key.

    Hashing is O(n²) over the raw bytes (blake2b), vs the O(n³)
    refactorization it saves; shape and dtype are mixed in so a
    truncated view never aliases."""
    arr = np.asarray(a)
    h = hashlib.blake2b(digest_size=16)
    h.update(str((arr.shape, arr.dtype.str)).encode())
    h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _dummy_system(key: CacheKey):
    """A well-conditioned stand-in matching ``key``'s shape/dtype:
    identity plus a tiny off-diagonal ramp (SPD, symmetric — valid for
    every registered method) and a ones rhs."""
    n = key.shape[-1]
    dtype = np.dtype(key.dtype)
    i = np.arange(n)
    a = np.eye(n, dtype=dtype) + 0.01 * np.exp(
        -np.abs(i[:, None] - i[None, :]).astype(dtype))
    b = np.ones((n,), dtype=dtype)
    if len(key.shape) == 3:
        a = np.broadcast_to(a, key.shape).copy()
        b = np.broadcast_to(b, key.shape[:1] + (n,)).copy()
    return jnp.asarray(a), jnp.asarray(b)


class _LazyAOT:
    """Wrap a jit solve fn so the first call compiles ahead of time.

    ``fn.lower(*args).compile()`` on first sight — timed, so the cache
    can attribute compile-seconds per :class:`CacheKey`, and handed to
    the observatory's HLO/memory analysis exactly once.  Later calls
    with the same arg signature dispatch straight to the compiled
    executable; a signature change (shouldn't happen — the key pins
    shape and dtype) goes through the plain jit fn.  A compile error
    propagates to the caller."""

    __slots__ = ("_fn", "_compiled", "_sig", "_record")

    def __init__(self, fn: Callable, record: Callable):
        self._fn = fn
        self._compiled = None
        self._sig = None
        self._record = record           # callback(compile_s, compiled)

    @staticmethod
    def _signature(args):
        return jax.tree.map(
            lambda x: (tuple(getattr(x, "shape", ())),
                       str(getattr(x, "dtype", ""))), args)

    def __call__(self, *args):
        sig = self._signature(args)
        if self._compiled is not None:
            if sig == self._sig:
                return self._compiled(*args)
            return self._fn(*args)
        t0 = time.perf_counter()
        compiled = self._fn.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        self._compiled, self._sig = compiled, sig
        try:
            self._record(compile_s, compiled)
        except Exception:               # bookkeeping never sinks a solve
            pass
        return compiled(*args)


class ExecutableCache:
    """Process-wide LRU of compiled solve executables.

    ``maxsize`` bounds the number of live executables (every one pins
    device buffers for its constants); eviction is least-recently-used.

    Entries are :class:`_LazyAOT` wrappers: the first call through a key
    compiles ahead of time, records per-key compile-seconds (visible in
    :meth:`stats` under ``"keys"``), and runs the while-aware HLO +
    memory analysis once — so a serving process knows the modeled FLOPs
    and peak bytes of everything it keeps warm."""

    def __init__(self, maxsize: int = 128):
        if maxsize < 1:
            raise ValueError(f"maxsize={maxsize} must be >= 1")
        self.maxsize = maxsize
        self._entries: OrderedDict[CacheKey, Callable] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.key_info: dict[CacheKey, dict] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: CacheKey) -> bool:
        return key in self._entries

    def get(self, key: CacheKey) -> Callable | None:
        """Peek without building (no miss counter on absence)."""
        fn = self._entries.get(key)
        if fn is not None:
            self._entries.move_to_end(key)
        return fn

    def get_or_build(self, key: CacheKey) -> Callable:
        fn = self._entries.get(key)
        if fn is not None:
            self.hits += 1
            metrics.counter_inc("serve_cache_hits")
            self._entries.move_to_end(key)
            return fn
        self.misses += 1
        metrics.counter_inc("serve_cache_misses")
        fn = self._build(key)
        self._entries[key] = fn
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1
            metrics.counter_inc("serve_cache_evictions")
        metrics.gauge_set("serve_cache_size", len(self._entries))
        return fn

    def warm(self, keys: Iterable[CacheKey]) -> "ExecutableCache":
        """Prefill: build each key's executable and run one dummy solve
        through it (block_until_ready), so the jit dispatch cache holds
        a compiled program before the first real request arrives.
        Returns self for chaining."""
        for key in keys:
            fn = self.get_or_build(key)
            a, b = _dummy_system(key)
            if key.mode == "factor":
                jax.block_until_ready(fn(a))
            elif key.mode == "apply":
                fkey = key._replace(mode="factor")
                state = self.get_or_build(fkey)(a)
                jax.block_until_ready(fn(state, b))
            else:
                jax.block_until_ready(fn(a, b))
        return self

    def stats(self) -> dict:
        return {"size": len(self._entries), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "compile_s_total": round(sum(
                    i.get("compile_s", 0.0)
                    for i in self.key_info.values()), 4),
                "keys": {self._label(k): dict(i)
                         for k, i in self.key_info.items()}}

    # -- construction ------------------------------------------------------
    @staticmethod
    def _label(key: CacheKey) -> str:
        lbl = f"{key.method}/{key.mode}/n{key.shape[-1]}/{key.dtype}"
        if len(key.shape) == 3:
            lbl += f"/b{key.shape[0]}"
        return lbl

    def _on_compile(self, key: CacheKey, compile_s: float,
                    compiled) -> None:
        """First execution of a key: record compile-seconds and the
        one-time HLO/memory analysis (never again for this key)."""
        info = {"compile_s": round(compile_s, 4)}
        try:
            a = perf_mod.analyze_compiled(compiled)
            info["flops"] = a["cost"].flops
            info["traffic_bytes"] = a["cost"].traffic_bytes
            if a["memory"]:
                info["peak_bytes"] = a["memory"].get("peak_bytes", 0)
                info["temp_bytes"] = a["memory"].get("temp_bytes", 0)
        except Exception:               # analysis is best-effort
            pass
        self.key_info[key] = info
        metrics.counter_inc("serve_compiles")
        metrics.counter_inc("serve_compile_seconds", compile_s)

    def _build(self, key: CacheKey) -> Callable:
        batch = key.shape[0] if len(key.shape) == 3 else None
        opts = dict(key.opts)
        fn = api.make_executable(
            method=key.method, mode=key.mode, batch=batch,
            engine=key.engine, backend=key.backend, precond=key.precond,
            **opts)
        return _LazyAOT(fn, functools.partial(self._on_compile, key))


__all__ = ["CacheKey", "ExecutableCache", "make_key", "fingerprint"]
