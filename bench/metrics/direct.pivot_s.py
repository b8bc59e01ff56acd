"""direct.pivot_s: device seconds per solve in the ``lu.pivot`` scope, the
row gather that applies each panel's swaps and the panel's store."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, "lu.pivot")
