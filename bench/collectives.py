"""The share of a program's device time in which its collectives run
exposed, with no other op running beside them.

``bench.tracing.exposed_collective_s`` knows a collective by its op's
name, and a device trace names an op by its compiled instruction: most
of the collectives of a ``shard_map`` program are named for the JAX
operation that made them (the panel broadcast's all-reduce is
``psum.63``).  So each instruction's opcode is read from the compiled
program's text, as ``bench.scopes`` reads its scope, and the trace's ops
are renamed by it before the reducer sees them.  Only the ops inside the
program's runs that lie wholly in the window count, on both sides of the
share.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from bench import run, scopes, tracing

_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def opcodes(hlo_text: str) -> dict:
    """``{instruction: opcode}`` for every instruction of a compiled
    program's text (``psum.63`` → ``all-reduce``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OPCODE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def program_opcodes(cell) -> dict | None:
    """The opcode map of the cell's timed program, taken once per run
    from the executable that ran (opcodes are no metadata, so the
    persistent cache's copy is exact) and kept in
    ``cell.readings["op_codes"]``."""
    if "op_codes" not in cell.readings:
        if not cell.readings.get("program"):
            return None
        driver = run.load_driver(cell.workload["driver"])
        cell.readings["op_codes"] = opcodes(scopes._program_text(cell,
                                                                 driver))
    return cell.readings["op_codes"]


def _program_ops(trace, device, program, codes) -> tracing.Device:
    """``device`` cut to the ops inside ``program``'s runs, each named by
    its opcode where the map has it."""
    inside = np.zeros(len(device.op_name), bool)
    for i in tracing._runs(trace, device, program):
        inside |= (device.op_start >= device.mod_start[i]) & (
            device.op_end <= device.mod_end[i])
    keep = np.flatnonzero(inside)
    return dataclasses.replace(
        device, op_start=device.op_start[keep], op_end=device.op_end[keep],
        op_self=device.op_self[keep], op_leaf=device.op_leaf[keep],
        op_name=[codes.get(device.op_name[i], device.op_name[i])
                 for i in keep])


def exposed_share(trace, program: str, codes: dict) -> float | None:
    """Percent of ``program``'s device time, summed over the devices, in
    which a collective of it runs and no other op does; ``None`` where no
    run of the program lies in the window."""
    device_s = sum(tracing.module_s(trace, d, program)
                   for d in trace.devices)
    if device_s <= 0:
        return None
    exposed = sum(tracing.exposed_collective_s(
        trace, _program_ops(trace, d, program, codes))
        for d in trace.devices)
    return 100.0 * exposed / device_s
