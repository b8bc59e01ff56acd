"""Performance observatory: close the model-vs-measurement loop per solve.

PR 8 telemetry measures wall clock and trace-time comm bytes; the
``analysis`` package *models* FLOPs / HBM traffic / collective payloads
— but nothing ever compared the two.  This module does, for every
eligible ``api.solve`` under a ``telemetry.session(..., perf=True)``:

* the solve routes through an AOT-compiled executable
  (``jit(...).lower(a, b).compile()``) owned by the observatory, so
  there IS a compiled artifact to analyze — the while-aware HLO parser
  (:mod:`repro.analysis.hlo`) and ``compiled.memory_analysis()`` run
  exactly **once per compile**, cached per solve configuration, never on
  the per-solve path;
* each per-solve record gains a ``perf`` sub-record: achieved GFLOP/s
  and HBM GB/s (modeled work over *measured* execute-span time),
  roofline-efficiency % against the **detected** machine peaks
  (:class:`MachineProfile` — measured micro-calibration on CPU/GPU, the
  datasheet table on TPU, replacing roofline.py's hard-coded v5e
  constants), peak/argument/output/temp memory, compile-seconds, a
  modeled-vs-measured comm-bytes cross-check against the
  :mod:`repro.telemetry.comm` site attribution, and per-rank
  load-imbalance metrics (straggler ratio, imbalance %, measured
  shard-arrival spread) for distributed solves.

Zero overhead when disarmed: ``session()`` defaults to ``perf=False``,
``api.solve`` checks one session attribute, and nothing here ever runs
at trace time inside a user jaxpr — eligibility explicitly rejects
tracers, so jaxprs traced under an armed session are untouched (the
same bitwise-identical contract as the rest of the telemetry stack).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo as hlo_mod
from repro.analysis import roofline as roofline_mod
from repro.telemetry import comm as comm_mod
from repro.telemetry import metrics as metrics_mod

# --------------------------------------------------------------------------
# machine profile: detected peaks, so "efficiency" means something on CI
# --------------------------------------------------------------------------

# TPU per-chip datasheet peaks (dense bf16 matmul FLOP/s, HBM B/s, ICI
# B/s per link), keyed by the exact ``device_kind`` jax reports (both of
# the names each chip goes by).  Source: Google Cloud TPU documentation,
# the per-generation "TPU v3" ... "TPU v6e" pages.  A TPU kind that is not
# here is an error, never a guess.
_V6E = dict(peak_flops=918e12, hbm_bw=1640e9, link_bw=100e9)
_V5P = dict(peak_flops=459e12, hbm_bw=2765e9, link_bw=100e9)
_V5E = dict(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)
_TPU_TABLE = {
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v4": dict(peak_flops=275e12, hbm_bw=1228e9, link_bw=50e9),
    "TPU v3": dict(peak_flops=123e12, hbm_bw=900e9, link_bw=70e9),
}


@dataclasses.dataclass(frozen=True)
class MachineProfile:
    """Per-device hardware peaks the roofline terms divide by.

    ``source`` records where the numbers came from: ``"table"`` (TPU
    datasheet), ``"calibrated"`` (measured micro-benchmarks on this
    host), or ``"override"`` (:func:`set_machine`, tests)."""
    name: str
    platform: str            # "cpu" | "gpu" | "tpu"
    peak_flops: float        # FLOP/s
    hbm_bw: float            # B/s
    link_bw: float           # B/s (inter-device; = hbm_bw on one host)
    source: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_MACHINE: MachineProfile | None = None


def _calibrate() -> tuple[float, float]:
    """Measured peak matmul FLOP/s and copy bandwidth on the default
    device — best-of-3 (we want the roof, not the average)."""
    n = 512
    a = jnp.asarray(np.linspace(0.0, 1.0, n * n, dtype=np.float32)
                    .reshape(n, n))
    mm = jax.jit(lambda x: x @ x)
    mm(a).block_until_ready()                       # compile outside timing
    t_mm = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        mm(a).block_until_ready()
        t_mm = min(t_mm, time.perf_counter() - t0)
    peak_flops = 2.0 * n ** 3 / max(t_mm, 1e-9)
    m = 1 << 22                                     # 16 MiB f32
    v = jnp.zeros((m,), jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)
    cp(v).block_until_ready()
    t_cp = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        cp(v).block_until_ready()
        t_cp = min(t_cp, time.perf_counter() - t0)
    hbm_bw = 2.0 * 4 * m / max(t_cp, 1e-9)          # one read + one write
    return peak_flops, hbm_bw


def detect(force: bool = False) -> MachineProfile:
    """The host's :class:`MachineProfile`, computed once and cached.
    TPU kinds come from the datasheet table (an unknown kind raises);
    CPU/GPU peaks are measured (≈ tens of ms, once per process)."""
    global _MACHINE
    if _MACHINE is not None and not force:
        return _MACHINE
    dev = jax.devices()[0]
    platform = dev.platform
    kind = str(dev.device_kind or platform)
    if platform == "tpu":
        if kind not in _TPU_TABLE:
            raise ValueError(f"no datasheet peaks for TPU device_kind "
                             f"{kind!r}; add it to perf._TPU_TABLE "
                             f"(known: {sorted(_TPU_TABLE)})")
        _MACHINE = MachineProfile(kind, "tpu", source="table",
                                  **_TPU_TABLE[kind])
        return _MACHINE
    peak_flops, hbm_bw = _calibrate()
    # single-host fabric: "the wire" is the memory system (cpu) or a
    # conservative fraction of it (gpu NVLink-less default)
    link_bw = hbm_bw if platform == "cpu" else hbm_bw / 4.0
    _MACHINE = MachineProfile(kind, platform, peak_flops, hbm_bw, link_bw,
                              "calibrated")
    return _MACHINE


def set_machine(profile: MachineProfile | None) -> None:
    """Override (or with ``None`` re-detect on next use) the cached
    machine profile — tests pin deterministic peaks through this."""
    global _MACHINE
    _MACHINE = profile


# --------------------------------------------------------------------------
# per-executable analysis (once per compile)
# --------------------------------------------------------------------------

def analyze_compiled(compiled) -> dict:
    """HLO cost model + memory stats of one compiled executable.  Runs
    the while-aware parser over ``compiled.as_text()`` and reads
    ``compiled.memory_analysis()`` — call once per compile and cache;
    parsing scales with module size, not solve count."""
    cost = hlo_mod.analyze_hlo(compiled.as_text())
    memory: dict = {}
    try:
        ma = compiled.memory_analysis()
        memory = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
        memory["peak_bytes"] = (memory["argument_bytes"]
                                + memory["output_bytes"]
                                + memory["temp_bytes"])
    except Exception:       # backends without memory stats
        pass
    return {"cost": cost, "memory": memory}


@dataclasses.dataclass
class PerfExec:
    """One analyzed executable: the AOT-compiled callable plus
    everything computed once at compile time."""
    fn: Callable
    cost: hlo_mod.HloCost
    memory: dict
    compile_s: float
    measured_comm_bytes: float       # trace-time site attribution, 1 run
    n_ranks: int
    rank_work: tuple                 # modeled per-rank work units
    iterative: bool = False          # Krylov loop: trip model = maxiter
    maxiter: int = 0
    calls: int = 0


def _mesh_ranks(mesh) -> int:
    try:
        return int(np.prod(list(mesh.shape.values())))
    except Exception:
        return 1


def rank_work_model(n: int, n_ranks: int, *, direct: bool,
                    block_size: int, grid=None) -> tuple:
    """Modeled per-rank work units for a distributed solve.

    Iterative spmd: contiguous block-rows — rank r's work ∝ its real
    (unpadded) rows, so a non-multiple ``n`` shows the padding
    imbalance.  Direct spmd: 2-D block-cyclic panels — work ∝ owned
    blocks weighted by how many elimination steps touch them (block
    (i, j) is updated ``min(i, j) + 1`` times), the ScaLAPACK balance
    argument made concrete."""
    if n_ranks <= 1:
        return (1.0,)
    if not direct:
        chunk = -(-n // n_ranks)                    # ceil
        return tuple(float(max(0, min(chunk, n - r * chunk)) * n)
                     for r in range(n_ranks))
    pr, pc = grid if grid is not None and len(grid) == 2 else (1, n_ranks)
    nb = max(1, int(block_size))
    nblocks = max(1, -(-n // nb))
    work = [[0.0] * pc for _ in range(pr)]
    for i in range(nblocks):
        for j in range(nblocks):
            work[i % pr][j % pc] += float(min(i, j) + 1)
    return tuple(w for row in work for w in row)


def shard_arrivals(out) -> list | None:
    """Per-shard completion offsets (seconds) of a sharded result —
    walked in shard order *before* the global block, so the spread is
    the measured straggler signal.  ``None`` for single-shard results
    (the common case pays one attribute access)."""
    x = getattr(out, "x", out)
    try:
        shards = x.addressable_shards
    except Exception:
        return None
    if len(shards) < 2:
        return None
    t0 = time.perf_counter()
    arrivals = []
    try:
        for sh in shards:
            sh.data.block_until_ready()
            arrivals.append(time.perf_counter() - t0)
    except Exception:
        return None
    return arrivals


# --------------------------------------------------------------------------
# the observatory
# --------------------------------------------------------------------------

class PerfObservatory:
    """Session-scoped model-vs-measurement bookkeeping.

    ``api.solve`` calls :meth:`eligible` / :meth:`prepare` on the
    dispatch path (compile + analyze once per configuration) and
    :meth:`attribute` after the execute-span block (cheap float math
    per solve).  One observatory per armed session, so cached
    executables were traced under exactly this session's arming."""

    def __init__(self, machine: MachineProfile | None = None):
        self._machine = machine
        self._cache: dict = {}
        self._bad: set = set()
        self.analyses = 0            # HLO analyses run (== compiles)
        self.compile_s_total = 0.0

    @property
    def machine(self) -> MachineProfile:
        if self._machine is None:
            self._machine = detect()
        return self._machine

    def executables(self) -> list[PerfExec]:
        return list(self._cache.values())

    def summary(self) -> dict:
        return {"executables": len(self._cache),
                "hlo_analyses": self.analyses,
                "compile_s_total": round(self.compile_s_total, 4)}

    # -- dispatch-path hooks ----------------------------------------------
    def eligible(self, a, b, kw: dict) -> bool:
        """Can this solve route through an observatory-owned AOT
        executable?  Concrete dense arrays, cache-keyable options."""
        if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
            return False
        if getattr(a, "is_sparse", False):
            return False
        if kw.get("policy") is not None or kw.get("x0") is not None \
                or kw.get("abft"):
            return False
        pc = kw.get("precond")
        if pc is not None and not isinstance(pc, str):
            return False
        shape = getattr(a, "shape", None)
        if not shape or len(shape) not in (2, 3):
            return False
        return getattr(b, "shape", None) is not None

    def _key(self, a, b, kw: dict):
        mesh = kw.get("mesh")
        mkey = None if mesh is None else (
            id(mesh), tuple(getattr(mesh, "shape", {}).items()))
        opts = tuple(sorted((k, v) for k, v in kw.items() if k != "mesh"))
        return (tuple(a.shape), str(a.dtype), tuple(b.shape),
                str(getattr(b, "dtype", "")), mkey, opts)

    def prepare(self, a, b, kw: dict, builder: Callable,
                kind: str = "iterative") -> PerfExec | None:
        """The analyzed executable for this solve configuration —
        compiled, parsed, and memory-profiled on first sight (timed as
        compile-seconds), a dict hit afterwards.  ``builder`` returns
        the jit function to lower (built by the caller so this module
        never imports the API layer); ``kind`` is the registry method
        kind (``"iterative"`` methods get their modeled cost scaled by
        actual iterations at attribution time — the while-trip model
        charges ``maxiter``, the loop exits at convergence).  Returns
        ``None`` when the configuration can't be AOT-compiled — the
        caller falls back to the plain eager path."""
        try:
            key = self._key(a, b, kw)
        except TypeError:           # unhashable option: not cacheable
            return None
        if key in self._bad:
            return None
        pex = self._cache.get(key)
        if pex is not None:
            return pex
        try:
            prof = comm_mod.active()
            before = prof.total_bytes() if prof is not None else 0
            t0 = time.perf_counter()
            lowered = builder().lower(a, b)
            measured_comm = (prof.total_bytes() - before) \
                if prof is not None else 0
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            info = analyze_compiled(compiled)
            mesh = kw.get("mesh")
            n_ranks = _mesh_ranks(mesh) if mesh is not None else 1
            grid = tuple(mesh.shape.values()) if mesh is not None else None
            work = rank_work_model(
                int(a.shape[-1]), n_ranks,
                direct=kind == "direct" and kw.get("engine") == "spmd",
                block_size=kw.get("block_size", 128), grid=grid)
            pex = PerfExec(fn=compiled, cost=info["cost"],
                           memory=info["memory"], compile_s=compile_s,
                           measured_comm_bytes=float(measured_comm),
                           n_ranks=n_ranks, rank_work=work,
                           iterative=kind == "iterative",
                           maxiter=int(kw.get("maxiter", 0) or 0))
            self._cache[key] = pex
            self.analyses += 1
            self.compile_s_total += compile_s
            metrics_mod.counter_inc("perf_compiles")
            metrics_mod.counter_inc("perf_compile_seconds", compile_s)
            return pex
        except Exception:           # un-AOT-able config: remember, skip
            self._bad.add(key)
            return None

    # -- per-solve attribution (cheap: float math + dict build) ------------
    def attribute(self, rec: dict, pex: PerfExec, t_execute_s: float,
                  arrivals: list | None = None) -> None:
        """Attach the ``perf`` sub-record to one per-solve record."""
        pex.calls += 1
        t = max(float(t_execute_s), 1e-9)
        cost = pex.cost
        # Krylov loops exit at convergence but the while-trip model
        # charges maxiter — scale the modeled cost down to the
        # iterations that actually ran, so efficiency compares like
        # with like (the scale rides out in the record).
        scale = 1.0
        it = rec.get("iterations")
        if pex.iterative and pex.maxiter and it is not None:
            scale = min(1.0, max(int(it), 1) / pex.maxiter)
        if scale != 1.0:
            scaled = hlo_mod.HloCost()
            scaled.add(cost, mult=scale)
            cost = scaled
        rep = roofline_mod.roofline(
            rec.get("key", "solve"), cost, chips=max(pex.n_ranks, 1),
            model_flops_global=0.0, peaks=self.machine)
        eff = rep.t_bound / t * 100.0
        perf: dict = {
            "t_execute_ms": t * 1e3,
            "compile_s": round(pex.compile_s, 4) if pex.calls == 1 else 0.0,
            "achieved_gflops": cost.flops / t / 1e9,
            "achieved_hbm_gbs": cost.traffic_bytes / t / 1e9,
            "modeled_flops": cost.flops,
            "modeled_bytes": cost.traffic_bytes,
            "iter_scale": round(scale, 6),
            "machine": self.machine.name,
            "roofline": {
                "t_bound_ms": rep.t_bound * 1e3,
                "t_compute_ms": rep.t_compute * 1e3,
                "t_memory_ms": rep.t_memory * 1e3,
                "t_collective_ms": rep.t_collective * 1e3,
                "bottleneck": rep.bottleneck,
                "efficiency_pct": eff,
            },
        }
        if pex.memory:
            perf["memory"] = dict(pex.memory)
            metrics_mod.gauge_set("perf_peak_live_bytes",
                                  pex.memory.get("peak_bytes", 0))
        modeled_comm = cost.total_collective_bytes
        if pex.measured_comm_bytes or modeled_comm:
            c = {"modeled_bytes": modeled_comm,
                 "measured_bytes": pex.measured_comm_bytes}
            if pex.measured_comm_bytes:
                c["model_over_measured"] = \
                    modeled_comm / pex.measured_comm_bytes
            perf["comm"] = c
        if pex.n_ranks > 1:
            work = pex.rank_work
            mean = sum(work) / len(work)
            ranks = {"n_ranks": pex.n_ranks,
                     "straggler_ratio": max(work) / mean if mean else 1.0,
                     "imbalance_pct": (max(work) / mean - 1.0) * 100.0
                     if mean else 0.0}
            if arrivals:
                ranks["rank_wait_ms"] = (max(arrivals) - min(arrivals)) * 1e3
                ranks["arrival_ms"] = [round(v * 1e3, 3) for v in arrivals]
            perf["ranks"] = ranks
        rec["perf"] = perf
        metrics_mod.histogram_observe("perf_roofline_efficiency_pct", eff,
                                      buckets=(1, 2, 5, 10, 20, 40, 60,
                                               80, 100))


__all__ = ["MachineProfile", "PerfObservatory", "PerfExec", "detect",
           "set_machine", "analyze_compiled", "rank_work_model",
           "shard_arrivals"]
