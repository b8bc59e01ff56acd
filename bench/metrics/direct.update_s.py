"""direct.update_s: device seconds per solve in the ``lu.update`` scope,
the row-block TRSM and the masked rank-nb trailing update."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, "lu.update")
