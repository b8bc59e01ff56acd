"""Resilience walkthrough: inject faults, detect them, recover.

The four mechanisms of the robustness PR, end to end
(docs/resilience.md):

* ``inject.inject(...)`` arms a deterministic fault at a named site
  inside the solver body — here a NaN in every matvec and a silent
  scale corruption in the distributed LU trailing update;
* the Krylov health monitor classifies the broken run (``NON_FINITE``)
  instead of returning garbage;
* ``policy="resilient"`` retries/falls back — the transient fault's
  re-trace is clean, so the retry converges; every attempt is audited
  with an independent residual check;
* ``abft=True`` carries a Huang–Abraham checksum column through the
  distributed factorization (embedded as one extra local column — the
  factor stays bitwise identical) and ``abft.verify`` catches a
  corruption the unchecked path silently absorbs.

    PYTHONPATH=src python examples/resilient_solve.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import api, lu
from repro.resilience import abft, inject
from repro.launch import mesh as mesh_lib

n, nb = 256, 32
rng = np.random.default_rng(0)
g = rng.standard_normal((n, n))
spd = jnp.asarray(g @ g.T / n + 4 * np.eye(n))
gen = jnp.asarray(g + n * np.eye(n))
b = jnp.asarray(rng.standard_normal(n))
x_ref = np.linalg.solve(np.asarray(spd), np.asarray(b))
mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))

# -- 1. an injected matvec NaN, classified and recovered ------------------
with inject.inject(site="matvec", mode="nan") as ses:
    r = api.solve(spd, b, method="cg", tol=1e-10, policy="resilient",
                  return_info=True)
for att in r.info["attempts"]:
    print(f"attempt {att['method']}/{att['backend']}: {att['reason']}")
err = np.linalg.norm(np.asarray(r.x) - x_ref) / np.linalg.norm(x_ref)
print(f"matvec NaN drill: fired={ses.fired}  recovered err={err:.2e}\n")
assert r.info["attempts"][0]["reason"] == "non_finite" and err <= 1e-8

# -- 2. silent data corruption vs the ABFT checksum -----------------------
# a scaled element in the trailing update: finite, plausible — the
# unchecked factorization absorbs it and quietly solves the wrong system
drill = dict(site="trailing", mode="scale", seed=7, at_step=1, at_rank=0)
with inject.inject(**drill):
    silent = lu.lu_factor_spmd(gen, block_size=nb, mesh=mesh)
x_bad = lu.lu_apply_spmd(silent, b)
res_bad = float(np.linalg.norm(np.asarray(gen) @ np.asarray(x_bad)
                               - np.asarray(b)) / np.linalg.norm(b))
print(f"unchecked LU under corruption: finite="
      f"{bool(np.isfinite(np.asarray(x_bad)).all())} resid={res_bad:.2e}")

with inject.inject(**drill):
    checked = lu.lu_factor_spmd(gen, block_size=nb, mesh=mesh, abft=True)
try:
    abft.verify(checked)
    raise SystemExit("corruption went undetected")
except abft.FactorCorruption as e:
    print(f"checked LU: {e}\n")

# -- 3. the same drill under the policy: detect -> retry -> clean ---------
with inject.inject(**drill):
    r = api.solve(gen, b, method="lu", mesh=mesh, engine="spmd",
                  block_size=nb, policy="resilient", return_info=True)
res = float(np.linalg.norm(np.asarray(gen) @ np.asarray(r.x)
                           - np.asarray(b)) / np.linalg.norm(b))
print(f"policy over ABFT: {[a['reason'] for a in r.info['attempts']]} "
      f"resid={res:.2e}")
assert res <= 1e-8

# -- 4. clean runs pay (almost) nothing -----------------------------------
st0 = lu.lu_factor_spmd(gen, block_size=nb, mesh=mesh)
st1 = lu.lu_factor_spmd(gen, block_size=nb, mesh=mesh, abft=True)
print(f"clean abft_err={float(st1.abft_err):.1e} "
      f"(threshold {abft.checksum_threshold(st1.layout.n, st1.lu.dtype):.1e})"
      f"  factor bitwise-equal={np.array_equal(np.asarray(st0.lu), np.asarray(st1.lu))}")
