"""The direct engine's device scopes, read from a traced window.

The library names the work of each LU block step with ``jax.named_scope``
(``lu.panel``, ``lu.pivot``, ``lu.update``, ``lu.bcast``; the
substitutions ``lu.fsub`` and ``lu.bsub``).  A scope adds no op: it lands
in the ``op_name`` metadata of the compiled program's instructions, and a
device trace names its ops by those instructions (``fusion.53``).  So the
map from instruction to scope is read from the compiled program's text,
after the window, and the trace's ops are sorted by it.

Each op counts with its self time (its interval less the ops nested in
it, as ``bench.tracing`` computes it), so the scopes and the unscoped ops
(XLA's own copies, the outer loop's own time) partition the op-covered
device time of the program's runs exactly.  A ``while`` op's own time
goes to the scope that encloses it.
"""
from __future__ import annotations

import contextlib
import re

import numpy as np

from bench import run, tracing

SCOPE_PREFIX = "lu."
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r",? metadata=\{[^}]*\}")


def op_scopes(hlo_text: str) -> dict:
    """``{instruction: scope}`` for every instruction of a compiled
    program's text: the innermost ``lu.*`` component of its ``op_name``,
    or ``None`` where it has none (or no ``op_name`` at all)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        scopes = [c for c in (meta.group(1).split("/") if meta else [])
                  if c.startswith(SCOPE_PREFIX)]
        out[m.group(1)] = scopes[-1] if scopes else None
    return out


def scope_s(trace: tracing.Trace, scopes: dict | None, program: str,
            scope: str | None) -> float | None:
    """Seconds per run of ``program`` in ops whose scope is ``scope``
    (``None``: ops with no scope, or missing from the map), by self time,
    over the runs that lie wholly in the window, averaged over devices.

    ``None`` where the map is missing, the program names no scope at all
    (it predates the scopes), or no run of it lies in the window."""
    if not scopes or not any(scopes.values()):
        return None
    total_ns, runs = 0.0, 0
    for d in trace.devices:
        pick = tracing._runs(trace, d, program)
        runs += len(pick)
        inside = np.zeros(len(d.op_name), bool)
        for i in pick:
            inside |= (d.op_start >= d.mod_start[i]) & (
                d.op_end <= d.mod_end[i])
        for i in np.flatnonzero(inside):
            if scopes.get(d.op_name[i]) == scope:
                total_ns += float(d.op_self[i])
    if runs == 0:
        return None
    return total_ns / runs / 1e9


def instructions(hlo_text: str) -> list:
    """The instruction lines of a compiled program's text without their
    metadata: equal for two compiles that differ only in metadata."""
    return [_METADATA.sub("", line).strip()
            for line in hlo_text.splitlines() if _INSTRUCTION.match(line)]


@contextlib.contextmanager
def _no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _program_text(cell, driver) -> str:
    driver.prepare(cell)
    try:
        return cell.state["solve"].as_text()
    finally:
        driver.release(cell)


def program_scopes(cell) -> dict | None:
    """The op-scope map of the cell's timed program, taken once per run
    and kept in ``cell.readings["op_scopes"]``.

    The driver has freed its programs when the readers run, so the
    program is prepared again, twice, after the window and the check.
    Loaded from the persistent compilation cache the set-up filled, it is
    the executable that ran; but JAX leaves metadata out of the cache's
    key, so the ``op_name`` metadata of that executable is that of
    whichever version of the source compiled it first, which may predate
    the scopes.  So the map is taken from a compile made with the cache off,
    from this source, and only where its instructions are exactly those
    that ran (``None`` otherwise)."""
    if "op_scopes" not in cell.readings:
        if not cell.readings.get("program"):
            return None
        driver = run.load_driver(cell.workload["driver"])
        ran = _program_text(cell, driver)
        with _no_persistent_cache():
            fresh = _program_text(cell, driver)
        cell.readings["op_scopes"] = (
            op_scopes(fresh) if instructions(fresh) == instructions(ran)
            else None)
    return cell.readings["op_scopes"]


def read(cell, trace, *names) -> float | None:
    """Seconds per solve in the scopes ``names`` of the cell's timed
    program (``None`` among them: the unscoped ops), summed."""
    program = cell.readings.get("program")
    if not program or not any(
            tracing.module_count(trace, d, program) for d in trace.devices):
        return None
    scopes = program_scopes(cell)
    parts = [scope_s(trace, scopes, program, name) for name in names]
    return None if None in parts else sum(parts)
