"""direct.bcast_s: device seconds per solve in the ``lu.bcast`` scope, the
distributed engine's panel broadcasts: the masked psum of each factored
panel and the ranks' wait for the panel's owner."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, "lu.bcast")
