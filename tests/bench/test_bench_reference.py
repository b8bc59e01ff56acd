"""The plain LU reference (``bench/reference.py``), and the distributed
engine of the four-chip HPL cell held to it on four CPU devices."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cells import ROOT
from bench import reference, systems


@pytest.fixture()
def f64():
    old = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


def _hpl(n, seed, dtype=jnp.float32):
    a, b = systems.hpl_system(jax.random.key(systems.key32(seed)), n)
    return a.astype(dtype), b.astype(dtype)


def test_reference_pivots_as_lapack_getrf(f64):
    """On the CPU ``jax.lax.linalg.lu`` is LAPACK's ``getrf``: the same
    pivot sequence, factors to float64 rounding, and x solves the system."""
    a, b = _hpl(256, 3, jnp.float64)
    piv, lu, x = reference.solve(a, b)
    lu_lapack, piv_lapack, perm = jax.lax.linalg.lu(a)
    assert (np.asarray(piv) == np.asarray(piv_lapack)).all()
    assert (np.asarray(reference.permutation(piv)) == np.asarray(perm)).all()
    assert np.abs(np.asarray(lu - lu_lapack)).max() <= 1e-12
    assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-9


def test_reference_without_pivoting_factors_the_rows_as_given(f64):
    a, _ = _hpl(128, 4, jnp.float64)
    piv, lu = reference.lu_factor(a)
    kept, lu_kept = reference.lu_factor(a[reference.permutation(piv)],
                                        pivoting=False)
    assert (np.asarray(kept) == np.arange(128)).all()
    assert np.abs(np.asarray(lu_kept - lu)).max() <= 1e-12


# ---------------------------------------------------------------------------
# the spmd engine against the reference, on a (2, 2) mesh of CPU devices
# ---------------------------------------------------------------------------

N, NB, SEED = 1024, 64, 11     # 16 blocks, four per rank

SPMD = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from bench import reference, systems
from repro.core import dist, lu
from repro.launch.mesh import solver_mesh

n, nb, seed = {n}, {nb}, {seed}
mesh = solver_mesh(jax.devices()[:4])
a_sh = NamedSharding(mesh, dist.matrix_spec(mesh))
b_sh = NamedSharding(mesh, dist.vector_spec(mesh))
eps32 = float(np.finfo(np.float32).eps)
out = {{}}
for dtype in ("float64", "float32"):
    jax.config.update("jax_enable_x64", dtype == "float64")
    # the cell's system, in the generator's 2-D blocks
    a, b = jax.jit(lambda k: systems.hpl_system(jax.random.key(k), n),
                   out_shardings=(a_sh, b_sh))(
        np.uint32(systems.key32(seed)))
    a, b = a.astype(dtype), b.astype(dtype)
    # the reference runs on one device, from a copy
    a1, b1 = jnp.asarray(np.asarray(a)), jnp.asarray(np.asarray(b))
    piv, lu_ref, x_ref = reference.solve(a1, b1)
    perm_ref = np.asarray(reference.permutation(piv))
    for lookahead in (True, False):
        with jax.default_matmul_precision("highest"):
            st = lu.lu_factor_spmd(a, block_size=nb, mesh=mesh,
                                   lookahead=lookahead)
            x = lu.lu_apply_spmd(st, b)
        key = f"{{dtype}}-lookahead-{{'on' if lookahead else 'off'}}"
        perm = np.asarray(st.perm)
        r = {{"pivots_equal": bool((perm == perm_ref).all()),
              "pivots_differing": int((perm != perm_ref).sum())}}
        if dtype == "float32":
            # the engine's factor in natural column order, against the
            # reference's factors of the same rows
            l, u = lu.unpack(st.lu[:, st.layout.inv_colperm])
            _, lu_same = reference.lu_factor(a1[perm], pivoting=False)
            l_ref, u_ref = lu.unpack(lu_same)
            a64 = np.asarray(a1, np.float64)
            kappa = (np.abs(np.linalg.inv(a64)).sum(1).max()
                     * np.abs(a64).sum(1).max())
            r.update(
                l_err=float(jnp.max(jnp.abs(l - l_ref))),
                u_err=float(jnp.max(jnp.abs(u - u_ref))),
                u_max=float(jnp.max(jnp.abs(u_ref))),
                x_err=float(jnp.max(jnp.abs(x - x_ref))
                            / jnp.max(jnp.abs(x_ref))),
                kappa=float(kappa), eps=eps32, n=n,
                hpl=float(systems.hpl_ratio(a1, jnp.asarray(np.asarray(x)),
                                            b1)),
                hpl_ref=float(systems.hpl_ratio(a1, x_ref, b1)))
        out[key] = r
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def spmd():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SPMD.format(root=str(ROOT), src=str(ROOT / "src"), n=N, nb=NB,
                       seed=SEED)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


LOOKAHEAD = ["lookahead-on", "lookahead-off"]


@pytest.mark.parametrize("lookahead", LOOKAHEAD)
def test_spmd_pivots_equal_the_references_in_float64(spmd, lookahead):
    """In float64 no near-tie can flip a pivot under the engine's other
    summation order, so the sequences must be equal entry for entry."""
    r = spmd[f"float64-{lookahead}"]
    assert r["pivots_equal"], r


@pytest.mark.parametrize("lookahead", LOOKAHEAD)
def test_spmd_factors_within_tolerance_in_float32(spmd, lookahead):
    """Both factor the same rows in float32, in different summation
    orders (the engine adds each entry's updates in nb-term dot products,
    the reference one rank-1 step at a time); an entry of the trailing
    matrix takes up to n roundings, so the factors may differ by a few
    n·ε of their size (measured: 1.08 n·ε in L, 0.59 n·ε of max|U| in U).
    8·n·ε leaves room for growth; a wrong pivot or a lost update differs
    by O(1).  At this seed the float32 pivot sequences themselves differ
    (a near-tie the other order flips), so the reference factors the
    engine's row order: the order is held to the reference's in
    float64, above."""
    r = spmd[f"float32-{lookahead}"]
    tol = 8 * r["n"] * r["eps"]
    assert r["l_err"] <= tol, r                       # |L| <= 1
    assert r["u_err"] <= tol * r["u_max"], r


@pytest.mark.parametrize("lookahead", LOOKAHEAD)
def test_spmd_solution_within_tolerance_in_float32(spmd, lookahead):
    """Each solve is backward stable with a backward error of a few ε
    (its HPL ratio times n·ε), so each lies within about κ∞(A)·ε of the
    exact solution, relative to ‖x‖∞ (measured: 0.012 κ∞·ε; the pivots
    that differ move x by no more than rounding does)."""
    r = spmd[f"float32-{lookahead}"]
    assert r["x_err"] <= r["kappa"] * r["eps"], r


@pytest.mark.parametrize("lookahead", LOOKAHEAD)
def test_hpl_ratios_pass_at_this_size(spmd, lookahead):
    """0.05, the one-chip cell's limit.  The four-chip cell's own limit is
    set from chip readings at n = 49152; the ratio divides by n while the
    rounding error grows more slowly, so at n = 1024 it reads higher
    (measured here: 0.0015 for the engine, 0.0042 for the reference)."""
    r = spmd[f"float32-{lookahead}"]
    assert r["hpl"] <= 0.05 and r["hpl_ref"] <= 0.05, r
