"""device.idle_share.solve: the share of the traced window in which no
operation ran on the device, averaged over the cell's chips."""
from bench import tracing


def read(cell, trace):
    return tracing.idle_share_pct(trace)
