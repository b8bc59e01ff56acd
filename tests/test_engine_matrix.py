"""The README's engine contract as one table: every registered method on
every engine (``mesh=None``; ``mesh=`` gspmd; ``mesh=``, ``engine="spmd"``)
with a vector ``(n,)`` and a block ``(n, 2)`` right-hand side.

Each cell either solves — checked against numpy in float64 — or is
refused with the error ``api`` raises for that combination."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api

N, M, K = 64, 96, 2          # square n, rows of the rectangular systems
SPD = {"cg", "pipelined_cg", "ca_cg", "cholesky"}
ENGINES = ("local", "gspmd", "spmd")     # local: mesh=None

# Relative error of x against the float64 answer.  Direct methods are
# float32 factorizations; the Krylov methods stop at a relative residual
# of 1e-6 on systems whose condition number is at most ~10.
RTOL = {"direct": 1e-5, "iterative": 1e-4}

# Combinations api refuses, with the message it raises.
REFUSED = {
    ("qr", "gspmd"): "qr_factor is single-device; the distributed "
                     "factorization is TSQR",
    ("lsqr", "spmd"): "rectangular engine='spmd' is the TSQR factorization",
    ("cgls", "spmd"): "rectangular engine='spmd' is the TSQR factorization",
}
# The Gram-based drivers take one right-hand side: a block one fails
# with a JAX shape error while the solve is traced (api names no error).
VECTOR_ONLY = {"gmres", "ca_gmres", "ca_cg"}


def _system(method, k):
    rng = np.random.default_rng(17)
    if api.get_method(method).rectangular:
        a = rng.standard_normal((M, N))
    elif method in SPD:
        m = rng.standard_normal((N, N))
        a = m @ m.T / N + 4 * np.eye(N)
    else:
        a = rng.standard_normal((N, N)) + N * np.eye(N)
    b = rng.standard_normal((a.shape[0],) if k is None else (a.shape[0], k))
    return a.astype(np.float32), b.astype(np.float32)


@pytest.mark.parametrize("rhs", ["vector", "block"])
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("method", api.available_methods())
def test_engine_matrix(method, engine, rhs, mesh1):
    entry = api.get_method(method)
    a, b = _system(method, None if rhs == "vector" else K)
    kw = {} if engine == "local" else {"mesh": mesh1, "engine": engine}

    def solve():
        return api.solve(jnp.asarray(a), jnp.asarray(b), method=method,
                         block_size=16, tol=1e-6, **kw)

    if (method, engine) in REFUSED:
        with pytest.raises(ValueError, match=REFUSED[method, engine]):
            solve()
        return
    if rhs == "block" and method in VECTOR_ONLY:
        with pytest.raises(ValueError, match="shape"):
            solve()
        return
    x = np.asarray(solve())
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    want = (np.linalg.lstsq(a64, b64, rcond=None)[0] if entry.rectangular
            else np.linalg.solve(a64, b64))
    assert x.shape == want.shape
    err = np.linalg.norm(x - want) / np.linalg.norm(want)
    assert err <= RTOL[entry.kind], err
