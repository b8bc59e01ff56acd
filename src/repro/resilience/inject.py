"""Deterministic fault injection at named sites.

Transient hardware faults (a flipped DRAM bit in a GPU matvec, a
corrupted MPI reduction payload) are the failure mode the source paper's
long-running cluster solves live with.  This module makes them
*reproducible*: solver hot paths call :func:`tap` at named sites, and a
test (or drill) arms exactly one site with an :class:`InjectionPlan` —
everything about the fault (site, perturbation mode, corrupted element,
how many times it fires) is keyed on the plan's seed, so every detector
downstream can be exercised deterministically.

Sites registered by the library (``SITES``):

========== =============================================================
site        where the tap sits
========== =============================================================
matvec      every ``LinearOperator.matvec`` output (all engines)
update      the fused Krylov x/r update's new residual vector
gram        ``block_dots`` Gram-matrix blocks (CA-Krylov reductions)
psum        every ``pblas.psum`` result (spmd collectives)
all_gather  every ``pblas.all_gather`` result
bcast       every ``pblas.bcast_local`` payload (panel broadcasts)
panel       the factored LU/Cholesky panel, before it is consumed
trailing    the trailing matrix right after a rank-nb update (ABFT's
            target: a silent error the unchecked factorization absorbs)
========== =============================================================

Semantics worth knowing before writing a test:

* **Disarmed is free.**  With no plan armed, :func:`tap` returns its
  argument *unchanged and by identity* — no jax op is emitted, jaxprs
  and collective counts are bit-identical to a build without this
  module (tests assert this via ``pblas.collective_counts`` parity).
* **Trip counting is trace-time.**  ``lax.while_loop``/``fori_loop``
  bodies trace once per Python-level solve call, so ``trips=1`` corrupts
  the *first solve attempt's* computation and leaves a retry's re-trace
  clean — exactly the transient-fault model the escalation policy
  recovers from.  A tap inside a loop body is corrupted for every
  runtime iteration of that attempt unless the site supplies a traced
  ``step`` and the plan pins ``at_step``.  The staged ``lu_factor``
  (32 block steps or more) traces its body once per stage, the first
  stage first: to pin a later step, give ``trips`` the stage count.
* **Everything is logged.**  The armed session records each corruption
  (site, mode, tap hit index) so tests can assert the fault actually
  fired and recovery wasn't vacuous.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

SITES = ("matvec", "update", "gram", "psum", "all_gather", "bcast",
         "panel", "trailing")
MODES = ("nan", "inf", "bitflip", "scale", "zero")


@dataclasses.dataclass(frozen=True)
class InjectionPlan:
    """One deterministic fault: where, what, and when.

    ``seed`` picks the corrupted element (flat index into the payload),
    ``skip`` passes over that many tap hits at the site before arming,
    ``trips`` bounds how many (trace-time) corruptions fire.  ``at_step``
    / ``at_rank`` optionally gate on traced values at sites that supply
    them (the factorization loop's step index, the spmd rank).
    """
    site: str
    mode: str = "nan"
    seed: int = 0
    trips: int = 1
    skip: int = 0
    at_step: int | None = None
    at_rank: int | None = None
    scale_by: float = 1e3
    bit: int = 20

    def __post_init__(self):
        if self.site not in SITES:
            raise ValueError(f"unknown injection site {self.site!r}; "
                             f"registered sites: {SITES}")
        if self.mode not in MODES:
            raise ValueError(f"unknown injection mode {self.mode!r}; "
                             f"modes: {MODES}")


class Session:
    """Armed injection state: the plan plus hit/fire accounting."""

    def __init__(self, plan: InjectionPlan):
        self.plan = plan
        self.hits = 0      # taps seen at the site (trace-time)
        self.fired = 0     # corruptions actually applied
        self.log: list[dict[str, Any]] = []


_SESSION: Session | None = None


def active() -> InjectionPlan | None:
    """The armed plan, or None (the common, zero-overhead case)."""
    return None if _SESSION is None else _SESSION.plan


@contextlib.contextmanager
def inject(plan: InjectionPlan | None = None, /, **kw):
    """Arm a fault for the duration of the block.

        with inject.inject(site="matvec", mode="nan") as session:
            result = api.solve(a, b, method="cg", return_info=True)
        assert session.fired == 1

    Keyword form builds the :class:`InjectionPlan` inline.  Nested arms
    restore the previous session on exit.
    """
    global _SESSION
    plan = plan if plan is not None else InjectionPlan(**kw)
    prev = _SESSION
    session = Session(plan)
    _SESSION = session
    try:
        yield session
    finally:
        _SESSION = prev


def tap(site: str, x, *, step=None, rank=None):
    """Corruption point: returns ``x`` (identity — no op emitted) unless
    an armed plan names this site and has trips left."""
    session = _SESSION
    if session is None or session.plan.site != site:
        return x
    plan = session.plan
    session.hits += 1
    if session.hits <= plan.skip or session.fired >= plan.trips:
        return x
    session.fired += 1
    session.log.append({"site": site, "mode": plan.mode,
                        "hit": session.hits, "seed": plan.seed,
                        "at_step": plan.at_step, "at_rank": plan.at_rank})
    return _corrupt(x, plan, step=step, rank=rank)


def _bitflip(val: jax.Array, bit: int) -> jax.Array:
    nbits = val.dtype.itemsize * 8
    uint = {16: jnp.uint16, 32: jnp.uint32, 64: jnp.uint64}[nbits]
    word = jax.lax.bitcast_convert_type(val, uint)
    word = word ^ jnp.asarray(np.uint64(1) << (bit % nbits), uint)
    return jax.lax.bitcast_convert_type(word, val.dtype)


def _corrupt(x, plan: InjectionPlan, *, step=None, rank=None):
    xa = jnp.asarray(x)
    size = max(int(np.prod(xa.shape)), 1)
    idx = int(np.random.default_rng(plan.seed).integers(size))
    flat = xa.reshape(-1)
    old = flat[idx]
    if plan.mode == "nan":
        bad = jnp.asarray(jnp.nan, xa.dtype)
    elif plan.mode == "inf":
        bad = jnp.asarray(jnp.inf, xa.dtype)
    elif plan.mode == "zero":
        bad = jnp.zeros_like(old)
    elif plan.mode == "scale":
        bad = old * jnp.asarray(plan.scale_by, xa.dtype)
    else:  # bitflip
        bad = _bitflip(old, plan.bit)
    hurt = flat.at[idx].set(bad).reshape(xa.shape)
    # optional traced gates: corrupt only on the pinned step / rank
    pred = None
    if step is not None and plan.at_step is not None:
        pred = jnp.asarray(step) == plan.at_step
    if rank is not None and plan.at_rank is not None:
        g = jnp.asarray(rank) == plan.at_rank
        pred = g if pred is None else (pred & g)
    if pred is not None:
        hurt = jnp.where(pred, hurt, xa)
    return hurt
