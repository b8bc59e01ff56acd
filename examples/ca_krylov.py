"""Strong-scaling quickstart: s-step CA-Krylov + lookahead direct path.

The two mechanisms of the strong-scaling PR, end to end:

* ``method="ca_cg"`` / ``"ca_gmres"`` take ONE Gram-matrix reduction per
  ``s`` iterations (vs two per iteration for classic CG) — shown here by
  counting the reduction sites with ``pblas.collective_counts``;
* ``lu_factor_spmd(..., lookahead=True)`` overlaps the next panel's
  factor+broadcast with the trailing update, bitwise-identically to the
  sequential schedule.

    PYTHONPATH=src python examples/ca_krylov.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import api, lu, pblas
from repro.launch import mesh as mesh_lib

n, s = 512, 4
rng = np.random.default_rng(0)
a = rng.standard_normal((n, n))
spd = a @ a.T / n + 4 * np.eye(n)
b = rng.standard_normal(n)
sj, bj = jnp.asarray(spd), jnp.asarray(b)
x_ref = np.linalg.solve(spd, b)
mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))

# -- one reduction per s iterations, counted ------------------------------
# counts tally at TRACE time (the loop body traces once), so they are the
# number of reduction *sites* per iteration, not totals
for method, kw in (("cg", {}), ("pipelined_cg", {}), ("ca_cg", {"s": s})):
    with pblas.collective_counts() as c:
        r = api.solve(sj, bj, method=method, tol=1e-10, maxiter=2000,
                      mesh=mesh, engine="spmd", return_info=True, **kw)
    err = np.linalg.norm(np.asarray(r.x) - x_ref) / np.linalg.norm(x_ref)
    per = {"cg": "2 / iteration", "pipelined_cg": "1 / iteration",
           "ca_cg": f"1 / {s} iterations"}[method]
    print(f"{method:13s} reductions: {per:16s} (trace sites: "
          f"{c['dots']})  iters={int(r.iterations)}  err={err:.1e}")

# ca_gmres: matrix-powers sweep + ONE block orthogonalization per cycle
g = jnp.asarray(a + n * np.eye(n))
r = api.solve(g, bj, method="ca_gmres", s=8, tol=1e-10, maxiter=400,
              mesh=mesh, engine="spmd", return_info=True)
err = np.linalg.norm(np.asarray(r.x)
                     - np.linalg.solve(np.asarray(g), b))
print(f"ca_gmres      s=8 one Gram psum per cycle           err={err:.1e}")

# -- lookahead direct path: overlap, not elision --------------------------
aj = jnp.asarray(np.asarray(g))
st = lu.lu_factor_spmd(aj, block_size=64, mesh=mesh)            # default on
st_seq = lu.lu_factor_spmd(aj, block_size=64, mesh=mesh, lookahead=False)
with pblas.collective_counts() as c_la:
    lu.lu_factor_spmd(aj, block_size=64, mesh=mesh)
with pblas.collective_counts() as c_no:
    lu.lu_factor_spmd(aj, block_size=64, mesh=mesh, lookahead=False)
print(f"lookahead LU  bitwise == sequential: "
      f"{np.array_equal(np.asarray(st.lu), np.asarray(st_seq.lu))}  "
      f"broadcasts {c_la['bcast']} vs {c_no['bcast']} "
      f"(+1 pipeline fill, same count per step)")
