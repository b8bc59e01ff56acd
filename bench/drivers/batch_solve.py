"""Back-to-back dense solves of fresh systems, timed as HPL times them.

Each solve gets a new system, generated on the device from
``seed·1000 + i``.  Its time runs from the call to the answer's
``block_until_ready``; generating the system and copying the answer to
the host fall outside it.  Solves run back to back while the next one,
at the length of the last, still ends inside ``--seconds`` (at least
one solve runs), so a run holds a whole number of solves and ends within
its window.  After the window every system is
generated again from its seed and the answer is held to HPL's scaled
residual, as HPL checks its own.

Config keys: ``n``, ``nb``, ``method``, ``system`` (a generator of
``bench.systems``); with ``chips`` > 1 the solve runs the distributed
engine (``engine="spmd"``) on ``solver_mesh`` over those chips.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding

from bench import systems
from bench.tracing import span

# the compiled program of every timed solve; the trace reader finds the
# solves' device time by this name
PROGRAM = "hpl_solve"


def _shardings(cell):
    if cell.chips == 1:
        one = SingleDeviceSharding(cell.devices[0])
        return None, one, one
    from repro.core import dist
    from repro.launch.mesh import solver_mesh
    mesh = solver_mesh(cell.devices)
    return (mesh, NamedSharding(mesh, dist.matrix_spec(mesh)),
            NamedSharding(mesh, dist.vector_spec(mesh)))


def _solver(config, mesh):
    from repro.core import api
    kw = dict(method=config["method"], block_size=config["nb"])
    if mesh is not None:
        kw.update(mesh=mesh, engine="spmd")

    def hpl_solve(a, b):
        return api.solve(a, b, **kw)
    return jax.jit(hpl_solve)


def _generator(config, a_sh, b_sh):
    make = systems.KINDS[config["system"]]
    n = config["n"]
    return jax.jit(lambda s: make(jax.random.key(s), n),
                   out_shardings=(a_sh, b_sh))


def system_seed(cell, i: int) -> np.uint32:
    return np.uint32(systems.key32(cell.seed * 1000 + i))


def prepare(cell) -> None:
    """Compile the generator and the solve (from the persistent cache
    after a checkout's first run); nothing else is built."""
    n = cell.config["n"]
    mesh, a_sh, b_sh = _shardings(cell)
    seed = jax.ShapeDtypeStruct((), jnp.uint32)
    gen = _generator(cell.config, a_sh, b_sh).lower(seed).compile()
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=a_sh)
    b = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=b_sh)
    solve = _solver(cell.config, mesh).lower(a, b).compile()
    cell.state.update(gen=gen, solve=solve, b_sharding=b_sh)


def measure(cell, seconds: float) -> None:
    gen, solve = cell.state["gen"], cell.state["solve"]
    times, answers = [], []
    t_end = time.perf_counter() + seconds
    while not times or time.perf_counter() + times[-1] <= t_end:
        with span("generate"):
            a, b = jax.block_until_ready(
                gen(system_seed(cell, len(times))))
        with span("solve"):
            t0 = time.perf_counter()
            x = jax.block_until_ready(solve(a, b))
            times.append(time.perf_counter() - t0)
        with span("fetch"):
            answers.append(np.asarray(x))
        del a, b, x
    cell.attempted = len(times)
    cell.metrics["solve_s"] = float(np.mean(times))
    cell.readings.update(n=cell.config["n"], solve_times=times,
                         program=PROGRAM)
    cell.state["answers"] = answers
    print("solve seconds: " + " ".join(f"{t:.6f}" for t in times),
          file=sys.stderr, flush=True)


def release(cell) -> None:
    """Free the solve's program before the check runs."""
    for k in ("gen", "solve"):
        cell.state.pop(k, None)


def verify(cell) -> dict:
    """Each answer against its system, generated again from its seed."""
    n = cell.config["n"]
    _, a_sh, b_sh = _shardings(cell)
    gen = _generator(cell.config, a_sh, b_sh)
    ratio = jax.jit(systems.hpl_ratio)
    ratios = []
    for i, x in enumerate(cell.state.pop("answers")):
        with span("check"):
            a, b = gen(system_seed(cell, i))
            ratios.append(float(ratio(a, jax.device_put(x, b_sh), b)))
            del a, b
    cell.readings["hpl_ratios"] = ratios
    worst = max(ratios) if ratios else float("nan")
    if np.isnan(ratios).any():
        worst = float("nan")
    return {"hpl_ratio": {"value": worst,
                          "limit": cell.workload["limits"]["hpl_ratio"]}}
