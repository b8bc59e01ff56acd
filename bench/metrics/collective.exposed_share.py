"""collective.exposed_share: the share of the solves' device time, summed
over the chips, in which a collective runs with no other op beside it
(``bench.collectives``)."""
from bench import collectives, tracing


def read(cell, trace):
    program = cell.readings.get("program")
    if not program or not any(tracing.module_count(trace, d, program)
                              for d in trace.devices):
        return None
    return collectives.exposed_share(trace, program,
                                     collectives.program_opcodes(cell))
