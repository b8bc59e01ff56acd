"""direct.distribute_s: device seconds per solve in the ``lu.distribute``
scope, the distributed engine's change from the 2-D block layout into
its column-cyclic one (the column gather and the collectives folded into
it)."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, "lu.distribute")
