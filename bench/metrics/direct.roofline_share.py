"""direct.roofline_share: the direct engine's share of the chip's roofline.

HPL's operation count for the solves that ran wholly inside the traced
window (2/3·n³ + 2·n² each, fixed by n whatever implements it), at the
least time the chips could take for it (operations over the bf16 peak,
or bytes over HBM bandwidth, whichever is larger), divided by the device
time of the solves' compiled program, summed over the chips.

The bf16 MXU peak is the chip's top rate, so no precision or algorithm
can read over 100% against it.  The solves run at Precision.HIGHEST,
six bf16 passes for each float32 product: against the rate of float32 at
HIGHEST the same number would read about six times higher.
"""
from bench import peaks, systems, tracing


def read(cell, trace):
    n, program = cell.readings.get("n"), cell.readings.get("program")
    if not n or not program or not trace.devices:
        return None
    count = tracing.module_count(trace, trace.devices[0], program)
    device_s = sum(tracing.module_s(trace, d, program)
                   for d in trace.devices)
    if count == 0 or device_s <= 0:
        return None
    p = peaks.peaks(cell.devices[0].device_kind)
    least = max(systems.hpl_flops(n) / p["bf16_flops"],
                systems.hpl_bytes(n) / p["hbm_bytes_per_s"])
    return 100.0 * count * least / device_s
