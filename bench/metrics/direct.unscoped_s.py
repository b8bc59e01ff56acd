"""direct.unscoped_s: device seconds per solve in ops of the solve's
program with no ``lu.*`` scope: copies XLA inserted itself, the outer
loop's own time."""
from bench import scopes


def read(cell, trace):
    return scopes.read(cell, trace, None)
