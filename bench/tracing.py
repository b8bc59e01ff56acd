"""The profiler's trace of a window, reduced to device intervals.

A run with ``--trace 1`` records the window with ``jax.profiler`` and
reduces the ``.xplane.pb`` here, with nothing but JAX's own reader:

* every device plane (``/device:TPU:<i>``) gives its ``XLA Ops`` events
  (what ran on the device, when) and its ``XLA Modules`` events (which
  compiled program they belong to);
* every host line gives the harness's own spans, the
  ``jax.profiler.TraceAnnotation`` blocks named ``bench.<what>``.

The window is the host span ``bench.window``; device time outside it is
cut off.  Busy time is the union of the op intervals of a device, idle
time the rest of the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all|send|recv)\b")


def span(name: str):
    """A host span of the harness, visible in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def profiler_options():
    """Device and host tracing, without the Python function tracer (which
    would slow the host path that a host-bound cell measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


@dataclasses.dataclass
class Device:
    name: str
    op_start: np.ndarray          # ns, sorted by start
    op_end: np.ndarray
    op_name: list                 # the HLO instruction's name ("fusion.53")
    op_self: np.ndarray           # ns not covered by the op's nested ops
    op_leaf: np.ndarray           # no op nested inside (not a while/call)
    mod_start: np.ndarray
    mod_end: np.ndarray
    mod_name: list


@dataclasses.dataclass
class Trace:
    devices: list                 # [Device], in plane order
    spans: list                   # [(name, start_ns, end_ns)] of the harness
    t0: float                     # the window, ns
    t1: float
    host: list = dataclasses.field(default_factory=list)  # other host events

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """``%fusion.53 = f32[...] fusion(...)`` → ``fusion.53``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _sorted(events):
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    start = np.array([e[0] for e in events], np.float64)
    end = np.array([e[1] for e in events], np.float64)
    return start, end, [e[2] for e in events]


def _nesting(start, end):
    """Self time and leaf flag of each interval of one timeline, where an
    interval that lies inside another is that one's child."""
    self_ns = end - start
    leaf = np.ones(start.size, bool)
    stack = []
    for i in range(start.size):
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_ns[parent] -= end[i] - start[i]
            leaf[parent] = False
        stack.append(i)
    return self_ns, leaf


def _device(name, ops, mods) -> Device:
    start, end, names = _sorted(ops)
    self_ns, leaf = _nesting(start, end)
    return Device(name, start, end, [op_name(n) for n in names], self_ns,
                  leaf, *_sorted(mods))


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans, host = [], [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.start_ns, e.end_ns, e.name)
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    mods += [(e.start_ns, e.end_ns, e.name)
                             for e in line.events]
            devices.append(_device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns, e.end_ns)
                    (spans if e.name.startswith(SPAN_PREFIX)
                     else host).append(ev)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no host span {WINDOW_SPAN!r}")
    _, t0, t1 = max(windows, key=lambda s: s[2] - s[1])
    host = [h for h in host if h[2] > t0 and h[1] < t1]
    return Trace(devices, spans, t0, t1, host)


# --------------------------------------------------------------------------
# interval arithmetic (ns)
# --------------------------------------------------------------------------

def union(start, end, t0=-np.inf, t1=np.inf):
    """Merge intervals, clipped to [t0, t1]: sorted disjoint (s, e) arrays."""
    s = np.clip(np.asarray(start, np.float64), t0, t1)
    e = np.clip(np.asarray(end, np.float64), t0, t1)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(e[idx[1:] - 1], e[-1])


def measure(start, end) -> float:
    return float(np.sum(np.asarray(end) - np.asarray(start)))


def subtract(a, b) -> float:
    """Length of the union ``a`` less the union ``b`` (both merged)."""
    total = measure(*a)
    if total == 0 or b[0].size == 0:
        return total
    overlap = 0.0
    bs, be = b
    for s, e in zip(*a):
        lo = np.searchsorted(be, s, side="right")
        hi = np.searchsorted(bs, e, side="left")
        if hi > lo:
            overlap += float(np.sum(np.minimum(be[lo:hi], e)
                                    - np.maximum(bs[lo:hi], s)))
    return total - overlap


# --------------------------------------------------------------------------
# what the readers and the breakdown ask of a trace
# --------------------------------------------------------------------------

def busy_s(trace: Trace, device: Device) -> float:
    return measure(*union(device.op_start, device.op_end,
                          trace.t0, trace.t1)) / 1e9


def mean_busy_s(trace: Trace) -> float | None:
    if not trace.devices:
        return None
    return float(np.mean([busy_s(trace, d) for d in trace.devices]))


def idle_share_pct(trace: Trace) -> float | None:
    """Percent of the window in which no op ran, averaged over devices."""
    busy = mean_busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)


def _runs(trace: Trace, device: Device, pattern: str):
    """Indices of the runs of programs named like ``pattern`` that lie
    wholly inside the window."""
    return [i for i, n in enumerate(device.mod_name)
            if pattern in n and device.mod_start[i] >= trace.t0
            and device.mod_end[i] <= trace.t1]


def module_s(trace: Trace, device: Device, pattern: str) -> float:
    """Seconds in which a run of a program named like ``pattern`` ran on
    ``device``, of the runs wholly inside the window."""
    pick = _runs(trace, device, pattern)
    return measure(*union(device.mod_start[pick], device.mod_end[pick],
                          trace.t0, trace.t1)) / 1e9


def module_count(trace: Trace, device: Device, pattern: str) -> int:
    return len(_runs(trace, device, pattern))


def _collective(device: Device) -> np.ndarray:
    return np.array([bool(_COLLECTIVE.match(n)) for n in device.op_name],
                    bool)


def exposed_collective_s(trace: Trace, device: Device) -> float:
    """Seconds in which a collective op runs on ``device`` and no other
    op does (ops that only enclose others, such as a while loop, do not
    count as running)."""
    coll = _collective(device)
    if not coll.any():
        return 0.0
    other = device.op_leaf & ~coll
    c = union(device.op_start[coll], device.op_end[coll], trace.t0,
              trace.t1)
    o = union(device.op_start[other], device.op_end[other], trace.t0,
              trace.t1)
    return subtract(c, o) / 1e9


def has_collectives(device: Device) -> bool:
    return bool(_collective(device).any())


def top_ops(trace: Trace, k: int = 10):
    """The device ops that took most time by their own (self) time, not
    counting the ops nested in them, summed over devices and divided by
    their number: [[name, seconds], ...]."""
    total: dict[str, float] = {}
    for d in trace.devices:
        inside = (d.op_start >= trace.t0) & (d.op_end <= trace.t1)
        for i in np.flatnonzero(inside):
            total[d.op_name[i]] = total.get(d.op_name[i], 0.0) + float(
                d.op_self[i])
    ndev = max(1, len(trace.devices))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / ndev] for name, ns in ranked]


def idle_gaps(trace: Trace, k: int = 10):
    """The longest idle gaps of the first device, each labelled by what
    the host was doing: the harness span that covers most of it, where
    one covers at least half, and otherwise the host event (JAX's own,
    on any thread) that does: [[label, seconds], ...]."""
    if not trace.devices:
        return []
    s, e = union(trace.devices[0].op_start, trace.devices[0].op_end,
                 trace.t0, trace.t1)
    gs = np.concatenate([[trace.t0], e])
    ge = np.concatenate([s, [trace.t1]])
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    order = np.argsort(-(ge - gs), kind="stable")[:k]
    spans = [sp for sp in trace.spans if sp[0] != WINDOW_SPAN]
    out = []
    for i in order:
        gap = ge[i] - gs[i]
        name, cover = _most_overlap(spans, gs[i], ge[i])
        if cover >= gap / 2:
            label = name[len(SPAN_PREFIX):]
        else:
            name, cover = _most_overlap(trace.host, gs[i], ge[i])
            label = f"host:{name[:60]}" if cover > 0 else "none"
        out.append([label, float(gap) / 1e9])
    return out


def _most_overlap(events, s, e):
    best, label = 0.0, "none"
    for name, es, ee in events:
        ov = min(ee, e) - max(es, s)
        if ov > best:
            best, label = ov, name
    return label, best
