"""Unified observability: spans (:mod:`.trace`), in-graph convergence
histories (:mod:`.convergence`), per-site communication bytes
(:mod:`.comm`), a metrics registry with JSON/Prometheus export
(:mod:`.metrics`), and the performance observatory (:mod:`.perf` —
roofline-attributed solves, arm with ``session(..., perf=True)``).
One entry point::

    from repro import telemetry
    with telemetry.session("profile", profiler_dir="trace") as sess:
        x = api.solve(a, b, method="cg", mesh=mesh, engine="spmd")
    sess.save("TELEM_profile.json")            # repro.telemetry.report
    # trace/: jax.profiler trace, repro.* spans beside the device ops

Everything follows the zero-overhead-when-disarmed contract of
``resilience/inject.py``: with no session armed, no jaxpr changes by a
single op and the host-side cost is one module-global check per tap.
"""
from repro.telemetry import comm, convergence, metrics, perf, trace
from repro.telemetry.trace import (Session, active, annotate, block,
                                   disabled, session, span)

__all__ = ["comm", "convergence", "metrics", "perf", "trace", "Session",
           "session", "span", "annotate", "active", "disabled", "block"]
