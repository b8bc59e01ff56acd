"""direct.panel_s: device seconds per solve in the ``lu.panel`` scope, the
pivoted panel factorizations of the block steps."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, "lu.panel")
