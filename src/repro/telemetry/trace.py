"""Host-side span tree: ``telemetry.session()`` + ``span(name)``.

``api.solve``/``factorize``/``eigsolve`` open a span per call with two
phase children — ``dispatch`` (Python tracing + XLA compile + enqueue;
JAX compile events land here via ``jax.monitoring``, so a compile-cache
hit shows as a dispatch span with no ``compile_ms``) and ``execute``
(the ``block_until_ready`` wait — actual device time).  The
``policy="resilient"`` ladder opens one ``attempt`` span per rung, so a
recovered solve reads as a tree, not a mystery latency.

Export: :meth:`Session.save` (JSON, the ``TELEM_*.json`` schema), and
``profiler_dir=``, which records a ``jax.profiler`` trace of the session
(``.xplane.pb`` plus ``perfetto_trace.json.gz`` for ui.perfetto.dev).
Every span of an armed session is also a
``jax.profiler.TraceAnnotation`` named ``repro.<span>``, so that trace
holds ``repro.solve`` → ``repro.dispatch``/``repro.execute`` on the
profiler's own clock, beside the device ops they launched.

Zero overhead when disarmed: ``span()`` yields ``None`` after ONE module
global check, and the solve path never calls ``block_until_ready`` it
would not otherwise call — disarmed jaxprs are untouched (the span layer
is pure host code and emits no ops either way).
"""
from __future__ import annotations

import contextlib
import json
import time

import jax

from repro.telemetry import comm as comm_mod
from repro.telemetry import convergence as conv_mod
from repro.telemetry import metrics as metrics_mod

_SESSION: "Session | None" = None
_LISTENING = False


def active() -> "Session | None":
    return _SESSION


class Span:
    __slots__ = ("name", "attrs", "t0", "dur", "children", "events")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = time.perf_counter()
        self.dur = 0.0
        self.children: list[Span] = []
        self.events: list[dict] = []   # compile/lower events (jax.monitoring)

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def compile_ms(self) -> float:
        return sum(e["ms"] for e in self.events) \
            + sum(c.compile_ms for c in self.children)

    def to_dict(self, t_base: float) -> dict:
        d = {"name": self.name, "t_ms": (self.t0 - t_base) * 1e3,
             "dur_ms": self.dur * 1e3}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.events:
            d["events"] = list(self.events)
        if self.children:
            d["children"] = [c.to_dict(t_base) for c in self.children]
        return d


class Session:
    """One recording: a span tree + per-solve records + the comm profile
    + a metrics snapshot.  Obtained from :func:`session`."""

    def __init__(self, name: str):
        self.name = name
        self.root = Span(name, {})
        self._stack: list[Span] = [self.root]
        self.solves: list[dict] = []
        self.comm: comm_mod.CommProfile | None = None
        self.perf = None     # PerfObservatory when session(perf=True)

    # -- span plumbing -----------------------------------------------------
    def _open(self, name: str, attrs: dict) -> Span:
        sp = Span(name, attrs)
        self._stack[-1].children.append(sp)
        self._stack.append(sp)
        return sp

    def _close(self, sp: Span) -> None:
        sp.dur = time.perf_counter() - sp.t0
        # close everything down to sp (robust to a span leaked by an
        # exception in user code between enter and exit)
        while self._stack and self._stack[-1] is not sp:
            self._stack.pop()
        if self._stack:
            self._stack.pop()
        metrics_mod.histogram_observe(f"span_{sp.name}_ms", sp.dur * 1e3)

    def current(self) -> Span:
        return self._stack[-1]

    def record_solve(self, **rec) -> None:
        self.solves.append(rec)

    # -- export ------------------------------------------------------------
    def span_table(self) -> list[dict]:
        """Aggregate spans by (name, method/engine/backend attrs)."""
        rows: dict[tuple, dict] = {}

        def walk(sp: Span, path: str):
            label = path + sp.name
            for k in ("method", "engine", "backend"):
                if k in sp.attrs:
                    label += f" {k}={sp.attrs[k]}"
            r = rows.setdefault(label, {"span": label, "count": 0,
                                        "total_ms": 0.0, "compile_ms": 0.0})
            r["count"] += 1
            r["total_ms"] += sp.dur * 1e3
            r["compile_ms"] += sum(e["ms"] for e in sp.events)
            for c in sp.children:
                walk(c, path + sp.name + "/")

        for c in self.root.children:
            walk(c, "")
        return sorted(rows.values(), key=lambda r: -r["total_ms"])

    def to_dict(self) -> dict:
        d = {"section": self.name,
             "t_total_ms": self.root.dur * 1e3,
             "spans": self.span_table(),
             "span_tree": [c.to_dict(self.root.t0)
                           for c in self.root.children],
             "comm": self.comm.table() if self.comm is not None else [],
             "solves": list(self.solves),
             "metrics": metrics_mod.export_json()}
        if self.perf is not None:
            d["machine"] = self.perf.machine.to_dict()
            d["perf"] = self.perf.summary()
        return d

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, default=str)


def _on_jax_event(event: str, duration_secs: float, **kw) -> None:
    """jax.monitoring listener: attach compile/lower durations to the
    current span.  Registered once, forever — it early-outs on the
    module global, so it costs one attribute read when no session is
    live (listeners cannot be unregistered portably)."""
    s = _SESSION
    if s is None:
        return
    if "compile" not in event and "lower" not in event:
        return
    s.current().events.append({"name": event, "ms": duration_secs * 1e3})


def _ensure_listener() -> None:
    global _LISTENING
    if _LISTENING:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jax_event)
        _LISTENING = True
    except Exception:       # monitoring API moved/absent: spans still work
        _LISTENING = True


@contextlib.contextmanager
def session(name: str = "telemetry", *, histlen: int = 64,
            convergence: bool = True, comm: bool = True,
            perf: bool = False, profiler_dir: str | None = None):
    """Arm the full telemetry stack for the block: span recording,
    in-graph convergence histories (``histlen`` ring slots), per-site
    communication bytes, optionally the performance observatory
    (``perf=True`` — roofline-attributed solve records, see
    :mod:`repro.telemetry.perf`), and optionally a
    ``jax.profiler.trace`` under ``profiler_dir``: the device timeline
    with the session's ``repro.*`` spans on the same clock, and a
    ``perfetto_trace.json.gz`` of it.
    Yields the :class:`Session`; sessions nest (the inner one records
    until it closes)."""
    global _SESSION
    _ensure_listener()
    prev = _SESSION
    s = Session(name)
    if perf:
        from repro.telemetry import perf as perf_mod
        s.perf = perf_mod.PerfObservatory()
    with contextlib.ExitStack() as stack:
        if convergence:
            stack.enter_context(conv_mod.capture(histlen))
        if comm:
            s.comm = stack.enter_context(comm_mod.capture())
        if profiler_dir is not None:
            stack.enter_context(jax.profiler.trace(
                profiler_dir, create_perfetto_trace=True))
        _SESSION = s
        try:
            yield s
        finally:
            s.root.dur = time.perf_counter() - s.root.t0
            _SESSION = prev


@contextlib.contextmanager
def disabled():
    """Temporarily disarm everything (used by the overhead benchmarks to
    measure the plain baseline from inside an armed section)."""
    global _SESSION
    prev = _SESSION
    _SESSION = None
    with contextlib.ExitStack() as stack:
        if conv_mod.armed():
            # re-enter with the disarmed sentinel by saving/restoring
            stack.enter_context(_disarm_convergence())
        if comm_mod.active() is not None:
            stack.enter_context(_disarm_comm())
        try:
            yield
        finally:
            _SESSION = prev


@contextlib.contextmanager
def _disarm_convergence():
    prev = conv_mod._CFG
    conv_mod._CFG = None
    try:
        yield
    finally:
        conv_mod._CFG = prev


@contextlib.contextmanager
def _disarm_comm():
    prev = comm_mod._PROFILE
    comm_mod._PROFILE = None
    try:
        yield
    finally:
        comm_mod._PROFILE = prev


@contextlib.contextmanager
def span(name: str, **attrs):
    """Open a named span under the live session (``None`` yielded — and
    nothing recorded — when no session is armed).  The span is also a
    ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, recorded
    when a profiler trace is running."""
    s = _SESSION
    if s is None:
        yield None
        return
    sp = s._open(name, attrs)
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield sp
    finally:
        s._close(sp)


def annotate(**attrs) -> None:
    """Attach attributes to the innermost open span (no-op disarmed)."""
    s = _SESSION
    if s is not None:
        s.current().set(**attrs)


def block(x):
    """``jax.block_until_ready`` that passes through non-array pytrees
    (factorize returns a callable; tracers have no block method)."""
    try:
        return jax.block_until_ready(x)
    except Exception:
        return x


__all__ = ["Session", "Span", "session", "span", "annotate", "active",
           "disabled", "block"]
