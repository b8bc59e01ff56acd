"""Observability quickstart: profile solves across engines and export
the traces (docs/observability.md).

One armed ``telemetry.session()`` around a handful of solves — cg,
ca_cg, and a distributed LU on 8 virtual devices — then every export
path the telemetry subsystem has:

* a span-timing table (solve → dispatch/execute, compile attribution),
* the per-rank communication-volume table (the distributed LU's panel
  broadcast should be the top row: O(P · n · nb) bytes),
* per-solve convergence records (iters_to_tol, residual histories),
* ``profile_trace/`` — the ``jax.profiler`` trace of the session: the
  device timeline with the ``repro.*`` spans on the same clock
  (``.xplane.pb``) and ``perfetto_trace.json.gz``, which
  https://ui.perfetto.dev loads,
* ``TELEM_profile.json`` — the session JSON that
  ``python -m repro.telemetry.report`` renders.

    PYTHONPATH=src python examples/profile_solve.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import api
from repro.telemetry import report
from repro.launch import mesh as mesh_lib

n, nb = 1024, 64
rng = np.random.default_rng(0)
a = rng.standard_normal((n, n)).astype(np.float32)
spd = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
nonsym = (a + n * np.eye(n)).astype(np.float32)
b = rng.standard_normal(n).astype(np.float32)
sj, aj, bj = jnp.asarray(spd), jnp.asarray(nonsym), jnp.asarray(b)
mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))

out_dir = os.path.dirname(os.path.abspath(__file__))
trace_dir = os.path.join(out_dir, "profile_trace")
telem_path = os.path.join(out_dir, "TELEM_profile.json")

with telemetry.session("profile", profiler_dir=trace_dir) as sess:
    # local (ref) engine: classic vs communication-avoiding CG + direct
    api.solve(sj, bj, method="cg", tol=1e-6, return_info=True)
    api.solve(sj, bj, method="ca_cg", s=4, tol=1e-6, return_info=True)
    # f32 direct/block-cyclic solves plateau near 1e-4 relative
    # residual at n=1024 — tol only sets the "converged" verdict here
    api.solve(aj, bj, method="lu", block_size=nb, tol=1e-4,
              return_info=True)
    # spmd engine: MPI-faithful collectives on the (4, 2) device mesh —
    # the comm table attributes every broadcast/psum to its site
    api.solve(sj, bj, method="cg", engine="spmd", mesh=mesh, tol=1e-6,
              return_info=True)
    api.solve(sj, bj, method="ca_cg", s=4, engine="spmd", mesh=mesh,
              tol=1e-4, return_info=True)
    api.solve(aj, bj, method="lu", engine="spmd", mesh=mesh,
              block_size=nb, tol=1e-3, return_info=True)

sess.save(telem_path)

print(report.render(sess.to_dict()))
print(f"trace dir    : {trace_dir}  (perfetto_trace.json.gz loads at "
      "https://ui.perfetto.dev)")
print(f"session json : {telem_path}  "
      "(render: python -m repro.telemetry.report)")

# the distributed-LU panel broadcast must dominate the comm profile
top = sess.comm.table()[0]
assert top["site"] == "lu_panel_bcast", top
print(f"top comm site: {top['site']} "
      f"({telemetry.comm.format_bytes(top['total_bytes'])} per rank)")
