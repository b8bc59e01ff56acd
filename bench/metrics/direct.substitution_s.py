"""direct.substitution_s: device seconds per solve in the ``lu.fsub`` and
``lu.bsub`` scopes, the forward and backward substitutions."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, "lu.fsub", "lu.bsub")
