"""Async batched solve server: the requests/sec front-end over the
method registry.

Requests (`submit`) enter an asyncio queue and are coalesced by a
single batcher task into micro-batches: requests sharing a
:class:`repro.serve.bucket.GroupKey` (method / engine / backend /
bucket rung / dtype / precond spec / solver options) are flushed
together when either ``max_batch`` requests have accumulated or the
oldest has waited ``max_delay_ms`` — the classic throughput/latency
dial.  Execution goes through the warm
:class:`repro.serve.cache.ExecutableCache`, so a steady-state stream
never traces or compiles.

Fast paths and pressure valves:

* **repeated-A factor reuse** — direct-method requests fingerprint
  their matrix (:func:`repro.serve.cache.fingerprint`); a fingerprint
  already in the factor LRU skips refactorization entirely and runs the
  cached factor state through the apply executable (O(n²) instead of
  O(n³)).  Refactorization and reuse counts land in the telemetry
  metrics registry (``serve_factorizations`` / ``serve_factor_reuse``).
* **backpressure** — the queue is bounded (``max_pending``);
  :meth:`SolveServer.submit` awaits (graceful: producers slow down),
  :meth:`SolveServer.submit_nowait` raises :class:`ServerOverloaded`
  for callers that prefer load-shedding.
* **per-request resilience** — ``policy="resilient"`` opts a request
  out of batching and into the full
  :mod:`repro.resilience.policy` escalation ladder.

Execution runs inline on the event loop (deterministic, single
consumer); while a batch executes, arrivals accumulate in the queue —
which is exactly what the next micro-batch wants.  Under an armed
``telemetry.session()`` every flush opens a ``serve_batch`` span.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import api
from repro.core.krylov import SolveResult
from repro.serve import bucket
from repro.serve import cache as cache_mod
from repro.telemetry import metrics, trace


class ServerOverloaded(RuntimeError):
    """Raised by :meth:`SolveServer.submit_nowait` when the request
    queue is full — shed load or fall back to :meth:`submit`."""


_STOP = object()


@dataclasses.dataclass
class _Request:
    a: Any
    b: Any
    n: int                      # logical size (pre-pad)
    group: bucket.GroupKey
    future: asyncio.Future
    t_submit: float
    fingerprint: str | None = None


class SolveServer:
    """Asyncio micro-batching front-end over ``api.solve``.

    Parameters
    ----------
    max_batch:     flush a group as soon as it holds this many requests.
    max_delay_ms:  flush a group when its oldest request has waited this
                   long (latency bound; the batching deadline).
    max_pending:   bounded queue depth — backpressure threshold.
    cache:         a shared :class:`ExecutableCache` (one is created if
                   omitted).
    factor_cache_size: LRU capacity of the repeated-A factor store.
    ladder:        shape-bucket rungs (default
                   ``core/blocking.bucket_ladder()``).
    metrics_port:  when set, serve the live metrics registry over HTTP
                   for the server's lifetime — ``/metrics`` (Prometheus
                   text 0.0.4), ``/stats`` (this server's
                   :meth:`stats` as JSON), ``/healthz``.  ``0`` binds
                   an ephemeral port; read :attr:`metrics_server`.port.
    request_log:   per-request structured logging — a callable invoked
                   with one JSON-serializable dict per finished request
                   (ts, method, n, latency_ms, converged, …), or a
                   writable file-like that gets one JSON line each.
    """

    def __init__(self, *, max_batch: int = 8, max_delay_ms: float = 2.0,
                 max_pending: int = 1024,
                 cache: cache_mod.ExecutableCache | None = None,
                 factor_cache_size: int = 32, block_size: int = 128,
                 ladder=None, metrics_port: int | None = None,
                 request_log=None):
        if max_batch < 1:
            raise ValueError(f"max_batch={max_batch} must be >= 1")
        if max_delay_ms < 0:
            raise ValueError(f"max_delay_ms={max_delay_ms} must be >= 0")
        compile_cache.enable()
        self.max_batch = max_batch
        self.max_delay_ms = max_delay_ms
        self.cache = cache if cache is not None \
            else cache_mod.ExecutableCache()
        self.block_size = block_size
        self.ladder = tuple(ladder) if ladder is not None else None
        self._queue: asyncio.Queue = asyncio.Queue(maxsize=max_pending)
        self._factors: OrderedDict[tuple, Any] = OrderedDict()
        self._factor_cap = factor_cache_size
        self._task: asyncio.Task | None = None
        self._metrics_port = metrics_port
        self.metrics_server = None        # live MetricsServer when bound
        self._request_log = request_log
        # instance tallies (the metrics registry keeps process-wide ones)
        self.requests_served = 0
        self.factorizations = 0
        self.factor_reuses = 0
        self.batches: list[dict] = []

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> "SolveServer":
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())
        if self._metrics_port is not None and self.metrics_server is None:
            from repro.serve import metrics_http
            self.metrics_server = metrics_http.MetricsServer(
                port=self._metrics_port, stats_fn=self.stats).start()
        return self

    async def stop(self) -> None:
        """Drain the queue, flush every pending group, stop the batcher."""
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None
        if self._task is None:
            return
        await self._queue.put(_STOP)
        await self._task
        self._task = None

    async def __aenter__(self) -> "SolveServer":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # -- request entry -----------------------------------------------------
    def _make_request(self, a, b, method, backend, precond, policy,
                      tol, maxiter, restart, method_kwargs) -> _Request:
        api.get_method(method)          # raises on unknown method
        a = np.asarray(a)
        b = np.asarray(b)
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError(f"serve requests are single square systems; "
                             f"got a {a.shape} — batched inputs are what "
                             "the server coalesces for you")
        if policy not in (None, "resilient"):
            raise ValueError(f"unknown policy {policy!r}; expected "
                             "'resilient' (or None)")
        n = a.shape[-1]
        group = bucket.group_key(
            method=method, engine="gspmd", backend=backend, n=n,
            dtype=a.dtype, precond=precond, policy=policy,
            ladder=self.ladder, tol=tol, maxiter=maxiter, restart=restart,
            block_size=self.block_size, **method_kwargs)
        fut = asyncio.get_running_loop().create_future()
        return _Request(a, b, n, group, fut, time.perf_counter())

    async def submit(self, a, b, *, method: str = "lu",
                     backend: str = "ref", precond: str | None = None,
                     policy: str | None = None, tol: float = 1e-6,
                     maxiter: int = 1000, restart: int = 32,
                     **method_kwargs) -> SolveResult:
        """Enqueue one solve and await its :class:`SolveResult`.  When
        the queue is full this *awaits* — backpressure propagates to the
        producer instead of dropping work."""
        req = self._make_request(a, b, method, backend, precond, policy,
                                 tol, maxiter, restart, method_kwargs)
        await self._queue.put(req)
        metrics.gauge_set("serve_queue_depth", self._queue.qsize())
        return await req.future

    async def submit_nowait(self, a, b, **kw) -> SolveResult:
        """Like :meth:`submit` but load-shedding: raises
        :class:`ServerOverloaded` instead of waiting when the queue is
        full."""
        req = self._make_request(
            a, b, kw.pop("method", "lu"), kw.pop("backend", "ref"),
            kw.pop("precond", None), kw.pop("policy", None),
            kw.pop("tol", 1e-6), kw.pop("maxiter", 1000),
            kw.pop("restart", 32), kw)
        try:
            self._queue.put_nowait(req)
        except asyncio.QueueFull:
            metrics.counter_inc("serve_rejected")
            raise ServerOverloaded(
                f"request queue is full ({self._queue.maxsize} pending); "
                "retry, back off, or raise max_pending") from None
        return await req.future

    def stats(self) -> dict:
        lat = metrics.get_histogram("serve_latency_ms")
        return {"requests_served": self.requests_served,
                "batches": len(self.batches),
                "factorizations": self.factorizations,
                "factor_reuses": self.factor_reuses,
                "factor_cache_size": len(self._factors),
                "queue_depth": self._queue.qsize(),
                "latency_p50_ms": lat.quantile(0.5) if lat else None,
                "latency_p99_ms": lat.quantile(0.99) if lat else None,
                "cache": self.cache.stats()}

    # -- batcher -----------------------------------------------------------
    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        pending: dict[bucket.GroupKey, list[_Request]] = {}
        deadlines: dict[bucket.GroupKey, float] = {}
        stopping = False
        while True:
            req = None
            if not stopping:
                timeout = None
                if deadlines:
                    timeout = max(0.0,
                                  min(deadlines.values()) - loop.time())
                try:
                    req = await asyncio.wait_for(self._queue.get(), timeout)
                except asyncio.TimeoutError:
                    pass
            else:
                try:
                    req = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    req = None
            if req is _STOP:
                stopping = True
                continue
            if req is not None:
                grp = pending.setdefault(req.group, [])
                grp.append(req)
                if len(grp) == 1:
                    deadlines[req.group] = loop.time() \
                        + self.max_delay_ms / 1e3
                if len(grp) >= self.max_batch:
                    deadlines.pop(req.group, None)
                    self._flush(req.group, pending.pop(req.group))
                if stopping or not self._queue.empty():
                    continue        # keep draining before deadline checks
            now = loop.time()
            for g in [g for g, d in deadlines.items()
                      if d <= now or stopping]:
                deadlines.pop(g)
                self._flush(g, pending.pop(g))
            if stopping and not pending and self._queue.empty():
                return

    def _flush(self, group: bucket.GroupKey, reqs: list[_Request]) -> None:
        t0 = time.perf_counter()
        try:
            with trace.span("serve_batch", method=group.method,
                            backend=group.backend, n=group.n,
                            batch=len(reqs)):
                self._execute(group, reqs)
        except Exception as e:          # noqa: BLE001 — fail the futures
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
        self.batches.append({"group": group, "size": len(reqs),
                             "ms": (time.perf_counter() - t0) * 1e3})
        metrics.counter_inc("serve_batches")
        metrics.histogram_observe("serve_batch_size", len(reqs),
                                  buckets=(1, 2, 4, 8, 16, 32, 64))
        metrics.histogram_observe("serve_batch_ms",
                                  (time.perf_counter() - t0) * 1e3)

    # -- execution ---------------------------------------------------------
    def _execute(self, group: bucket.GroupKey, reqs: list[_Request]) -> None:
        entry = api.get_method(group.method)
        if group.policy == "resilient":
            self._execute_resilient(group, reqs)
        elif entry.kind == "direct":
            self._execute_direct(group, reqs)
        else:
            self._execute_iterative(group, reqs, entry)

    def _execute_resilient(self, group, reqs) -> None:
        """The opt-out lane: no batching, full escalation ladder."""
        opts = dict(group.opts)
        opts.pop("block_size", None)
        for r in reqs:
            res = api.solve(jnp.asarray(r.a), jnp.asarray(r.b),
                            method=group.method, backend=group.backend,
                            precond=group.precond, policy="resilient",
                            block_size=self.block_size,
                            return_info=True, **opts)
            self._finish(r, jax.block_until_ready(res))

    def _solve_key(self, group, batch, mode="solve") -> cache_mod.CacheKey:
        return cache_mod.make_key(
            group.method, group.n, group.dtype, batch=batch,
            engine=group.engine, backend=group.backend,
            precond=group.precond if mode == "solve" else None,
            mode=mode, **dict(group.opts))

    def _execute_direct(self, group, reqs) -> None:
        fgroup = group._replace(policy=None)
        warm, cold = [], []
        for r in reqs:
            r.fingerprint = cache_mod.fingerprint(r.a)
            target = warm if (r.fingerprint, fgroup) in self._factors \
                else cold
            target.append(r)
        if cold:
            nb = bucket.batch_rung(len(cold), self.max_batch)
            mats, rhss = bucket.coalesce([(r.a, r.b) for r in cold],
                                         group.n, batch=nb)
            state = self.cache.get_or_build(
                self._solve_key(group, nb, "factor"))(mats)
            x = self.cache.get_or_build(
                self._solve_key(group, nb, "apply"))(state, rhss)
            x = np.asarray(jax.block_until_ready(x))
            state = jax.tree.map(np.asarray, state)   # host: slice w/o compiles
            self.factorizations += len(cold)
            metrics.counter_inc("serve_factorizations", len(cold))
            for i, r in enumerate(cold):
                self._store_factor(
                    (r.fingerprint, fgroup),
                    jax.tree.map(lambda t: t[i], state))
                self._finish(r, self._direct_result(r, x[i], group))
        for r in warm:
            st = self._factors[(r.fingerprint, fgroup)]
            self._factors.move_to_end((r.fingerprint, fgroup))
            self.factor_reuses += 1
            metrics.counter_inc("serve_factor_reuse")
            apply1 = self.cache.get_or_build(
                self._solve_key(group, None, "apply"))
            _, b_pad = bucket.pad_request(r.a, r.b, group.n)
            x = jax.block_until_ready(apply1(st, b_pad))
            self._finish(r, self._direct_result(r, x, group))

    def _execute_iterative(self, group, reqs, entry) -> None:
        batchable = "gram" not in entry.requires
        if batchable and len(reqs) > 1:
            nb = bucket.batch_rung(len(reqs), self.max_batch)
            mats, rhss = bucket.coalesce([(r.a, r.b) for r in reqs],
                                         group.n, batch=nb)
            res = self.cache.get_or_build(self._solve_key(group, nb))(
                mats, rhss)
            res = jax.tree.map(
                lambda t: np.asarray(t) if isinstance(t, jax.Array) else t,
                jax.block_until_ready(res))
            for i, r in enumerate(reqs):
                # slice per-system leaves (leading batch axis) on the host
                # — no per-shape eager-op compiles; scalar leaves (the
                # shared iteration counter) pass through
                ri = jax.tree.map(
                    lambda t, j=i: t[j] if getattr(t, "ndim", 0) >= 1
                    and t.shape[0] == nb else t, res)
                self._finish(r, ri._replace(
                    x=bucket.unpad_solution(ri.x, r.n)))
        else:
            # GMRES-family (basis Gram products) has no batched operator;
            # shape bucketing still coalesces its compiles
            exe = self.cache.get_or_build(self._solve_key(group, None))
            for r in reqs:
                a_pad, b_pad = bucket.pad_request(r.a, r.b, group.n)
                res = jax.block_until_ready(exe(a_pad, b_pad))
                self._finish(r, res._replace(
                    x=bucket.unpad_solution(res.x, r.n)))

    # -- helpers -----------------------------------------------------------
    def _store_factor(self, key, state) -> None:
        self._factors[key] = state
        self._factors.move_to_end(key)
        while len(self._factors) > self._factor_cap:
            self._factors.popitem(last=False)

    def _direct_result(self, r: _Request, x_padded, group) -> SolveResult:
        x = np.asarray(x_padded)[: r.n]
        tol = dict(group.opts).get("tol", 1e-6)
        rnorm = np.linalg.norm(r.b - r.a @ x)
        bnorm = np.linalg.norm(r.b)
        atol = tol * (bnorm if bnorm > 0 else 1.0)
        # host-side numpy result: zero eager-op compiles on the hot path
        return SolveResult(x, np.int32(0), rnorm, np.bool_(rnorm <= atol),
                           info={"fail_code": np.int32(0),
                                 "fail_iter": np.int32(0),
                                 "fail_reason": "ok"})

    def _finish(self, r: _Request, result: SolveResult) -> None:
        self.requests_served += 1
        latency_ms = (time.perf_counter() - r.t_submit) * 1e3
        metrics.counter_inc("serve_requests")
        metrics.histogram_observe("serve_latency_ms", latency_ms)
        if self._request_log is not None:
            self._log_request(r, result, latency_ms)
        if not r.future.done():
            r.future.set_result(result)

    def _log_request(self, r: _Request, result: SolveResult,
                     latency_ms: float) -> None:
        """One structured JSON record per finished request — to a
        callable (gets the dict) or a writable (gets a JSON line).
        Logging failures never fail the request."""
        try:
            rec = {"ts": round(time.time(), 6), "method": r.group.method,
                   "backend": r.group.backend, "n": r.n,
                   "bucket_n": r.group.n, "dtype": str(r.group.dtype),
                   "latency_ms": round(latency_ms, 3)}
            try:
                rec["iterations"] = int(np.max(result.iterations))
                rec["residual"] = float(np.max(result.residual))
                rec["converged"] = bool(np.all(result.converged))
            except Exception:
                pass
            if callable(self._request_log):
                self._request_log(rec)
            else:
                self._request_log.write(json.dumps(rec) + "\n")
        except Exception:
            pass


__all__ = ["SolveServer", "ServerOverloaded"]
