"""Resilience layer (PR 7): fault injection, ABFT, escalation policy,
validation, checkpointed solves.

Two layers, same structure as tests/test_distributed_direct.py:

* in-process tests on a (1, 1) mesh (or the real device set under CI's
  8-virtual-device spmd job): injection-harness semantics and the
  zero-overhead guarantee, ABFT detection, the policy ladder per
  injection site, input validation, warm starts, checkpoint
  save → kill → resume;
* a subprocess battery (repro.launch.selftest_resilience) at 2 and 8
  virtual devices — ABFT and the escalation ladder on real meshes.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api, cholesky, dist, lu, pblas
from repro.resilience import abft, inject, monitor
from repro.launch import mesh as mesh_lib

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _mesh():
    ndev = len(jax.devices())
    if ndev >= 8:
        return mesh_lib.make_mesh((4, 2), ("data", "model"),
                                  devices=jax.devices()[:8])
    return dist.single_device_mesh()


def _system(n, spd=False, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(dtype)
    if spd:
        a = (a @ a.T / n + 4.0 * np.eye(n)).astype(dtype)
    else:
        a = (a + n * np.eye(n)).astype(dtype)
    b = rng.standard_normal(n).astype(dtype)
    return a, b


def _resid(a, b, x):
    return float(np.linalg.norm(np.asarray(a) @ np.asarray(x)
                                - np.asarray(b))
                 / np.linalg.norm(np.asarray(b)))


@pytest.fixture()
def f64():
    old = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


# --------------------------------------------------------------------------
# injection harness semantics (acceptance: disarmed is FREE — identity,
# no op emitted — and armed faults are deterministic and logged)
# --------------------------------------------------------------------------

def test_disarmed_tap_is_identity():
    x = jnp.arange(8.0)
    assert inject.tap("matvec", x) is x       # no jax op emitted


def test_disarmed_tap_leaves_jaxpr_unchanged():
    x = jnp.arange(8.0)
    tapped = str(jax.make_jaxpr(lambda v: inject.tap("matvec", v) * 2)(x))
    plain = str(jax.make_jaxpr(lambda v: v * 2)(x))
    assert tapped == plain


def test_disarmed_collective_counts_parity(f64):
    """The spmd drivers are tap-instrumented at every collective; with no
    plan armed the traced program (collective tally) is identical to a
    build without the resilience module."""
    a, b = _system(64, spd=True)
    kw = dict(method="cg", mesh=_mesh(), engine="spmd", tol=1e-8)
    with pblas.collective_counts() as c_plain:
        api.solve(jnp.asarray(a), jnp.asarray(b), **kw)
    with inject.inject(site="matvec", mode="nan", trips=0):
        # armed-but-zero-trips still exercises the tap bookkeeping path
        with pblas.collective_counts() as c_armed:
            api.solve(jnp.asarray(a), jnp.asarray(b), **kw)
    assert dict(c_plain) == dict(c_armed)


def test_armed_fault_is_deterministic():
    x = jnp.arange(16.0)
    outs = []
    for _ in range(2):
        with inject.inject(site="update", mode="scale", seed=5) as ses:
            outs.append(np.asarray(inject.tap("update", x)))
        assert ses.fired == 1 and ses.log[0]["site"] == "update"
    assert np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[0], np.asarray(x))


def test_unknown_site_and_mode_rejected():
    with pytest.raises(ValueError, match="unknown injection site"):
        inject.InjectionPlan(site="nope")
    with pytest.raises(ValueError, match="unknown injection mode"):
        inject.InjectionPlan(site="matvec", mode="nope")


def test_trip_budget_and_skip():
    x = jnp.ones(4)
    with inject.inject(site="gram", mode="zero", trips=2, skip=1) as ses:
        hits = [inject.tap("gram", x) for _ in range(4)]
    assert ses.hits == 4 and ses.fired == 2
    assert hits[0] is x                      # skipped
    assert hits[3] is x                      # budget spent
    assert not np.array_equal(np.asarray(hits[1]), np.asarray(x))


# --------------------------------------------------------------------------
# monitor taxonomy surfaced in SolveResult.info
# --------------------------------------------------------------------------

def test_monitor_classification_names():
    assert [monitor.classify(c) for c in range(5)] == [
        "ok", "non_finite", "divergence", "stagnation", "breakdown"]


def test_monitor_info_in_solve_result(f64):
    a, b = _system(64, spd=True)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg", tol=1e-10,
                  return_info=True)
    assert int(r.info["fail_code"]) == monitor.OK
    assert "fail_iter" in r.info


def test_monitor_flags_non_finite(f64):
    a, b = _system(64, spd=True)
    with inject.inject(site="update", mode="nan", trips=2) as ses:
        r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg",
                      tol=1e-10, return_info=True)
    assert ses.fired >= 1
    assert int(r.info["fail_code"]) == monitor.NON_FINITE
    assert not bool(r.converged)


# --------------------------------------------------------------------------
# ABFT (acceptance: a corrupted trailing-update element the unchecked
# factorization silently absorbs raises FactorCorruption; abft=True
# keeps the factor BITWISE identical and errs under the threshold clean)
# --------------------------------------------------------------------------

def test_abft_lu_clean_and_bitwise(f64):
    a, _ = _system(128)
    st0 = lu.lu_factor_spmd(jnp.asarray(a), block_size=16, mesh=_mesh())
    st1 = lu.lu_factor_spmd(jnp.asarray(a), block_size=16, mesh=_mesh(),
                            abft=True)
    assert st0.abft_err is None
    assert float(st1.abft_err) <= abft.checksum_threshold(
        st1.layout.n, st1.lu.dtype)
    assert np.array_equal(np.asarray(st0.lu), np.asarray(st1.lu))
    assert np.array_equal(np.asarray(st0.perm), np.asarray(st1.perm))
    abft.verify(st1)                          # no raise


def test_abft_cholesky_clean_and_bitwise(f64):
    a, _ = _system(128, spd=True)
    c0 = cholesky.cholesky_factor_spmd(jnp.asarray(a), block_size=16,
                                       mesh=_mesh())
    c1 = cholesky.cholesky_factor_spmd(jnp.asarray(a), block_size=16,
                                       mesh=_mesh(), abft=True)
    assert float(c1.abft_err) <= abft.checksum_threshold(
        c1.layout.n, c1.l.dtype)
    assert np.array_equal(np.asarray(c0.l), np.asarray(c1.l))
    abft.verify(c1)


def test_abft_lu_detects_what_unchecked_absorbs(f64):
    """The acceptance drill: one scaled trailing-update element — the
    unchecked path returns a finite, silently WRONG solution; abft=True
    raises a structured FactorCorruption."""
    a, b = _system(128)
    drill = dict(site="trailing", mode="scale", seed=7, at_step=1,
                 at_rank=0)
    with inject.inject(**drill) as ses:
        st_bad = lu.lu_factor_spmd(jnp.asarray(a), block_size=16,
                                   mesh=_mesh(), abft=True)
    assert ses.fired >= 1
    with pytest.raises(abft.FactorCorruption, match="checksum"):
        abft.verify(st_bad)
    with inject.inject(**drill):
        st_silent = lu.lu_factor_spmd(jnp.asarray(a), block_size=16,
                                      mesh=_mesh())
    x_bad = lu.lu_apply_spmd(st_silent, jnp.asarray(b))
    assert np.isfinite(np.asarray(x_bad)).all()
    assert _resid(a, b, x_bad) > 1e-6         # finite but wrong


def test_abft_cholesky_detects_corruption(f64):
    a, _ = _system(128, spd=True)
    with inject.inject(site="trailing", mode="scale", seed=3, at_step=0,
                       at_rank=0) as ses:
        c_bad = cholesky.cholesky_factor_spmd(jnp.asarray(a), block_size=16,
                                              mesh=_mesh(), abft=True)
    assert ses.fired >= 1
    with pytest.raises(abft.FactorCorruption):
        abft.verify(c_bad)


def test_abft_panel_corruption_detected(f64):
    """A fault in the broadcast panel payload (site="panel") also breaks
    the carried-checksum invariant."""
    a, _ = _system(128)
    with inject.inject(site="panel", mode="scale", seed=1, at_step=0) as s:
        st = lu.lu_factor_spmd(jnp.asarray(a), block_size=16, mesh=_mesh(),
                               abft=True)
    assert s.fired >= 1
    with pytest.raises(abft.FactorCorruption):
        abft.verify(st)


def test_abft_lookahead_parity(f64):
    a, _ = _system(128)
    st1 = lu.lu_factor_spmd(jnp.asarray(a), block_size=16, mesh=_mesh(),
                            abft=True, lookahead=True)
    st2 = lu.lu_factor_spmd(jnp.asarray(a), block_size=16, mesh=_mesh(),
                            abft=True, lookahead=False)
    assert np.array_equal(np.asarray(st1.lu), np.asarray(st2.lu))


def test_abft_constant_collective_overhead(f64):
    """The checksum rides the existing schedule: abft adds a CONSTANT
    number of exit-check reductions (2 for LU's carried + product
    checks, 1 for Cholesky), not per-step collectives."""
    a, _ = _system(128)
    s, _ = _system(128, spd=True)
    for factor, mat, extra in (
            (lu.lu_factor_spmd, a, 2),
            (cholesky.cholesky_factor_spmd, s, 1)):
        with pblas.collective_counts() as c_off:
            factor(jnp.asarray(mat), block_size=16, mesh=_mesh())
        with pblas.collective_counts() as c_on:
            factor(jnp.asarray(mat), block_size=16, mesh=_mesh(),
                   abft=True)
        assert c_on["psum"] == c_off["psum"] + extra
        assert c_on["bcast"] == c_off["bcast"]


def test_api_abft_guard_and_solve(f64):
    a, b = _system(96)
    with pytest.raises(ValueError, match="abft"):
        api.solve(jnp.asarray(a), jnp.asarray(b), method="lu", abft=True)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  mesh=_mesh(), engine="spmd", block_size=16, abft=True)
    assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-10
    with inject.inject(site="trailing", mode="scale", at_rank=0):
        with pytest.raises(abft.FactorCorruption):
            api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                      mesh=_mesh(), engine="spmd", block_size=16,
                      abft=True)


# --------------------------------------------------------------------------
# escalation policy (acceptance: injected faults at every detector's
# site recovered by policy="resilient" to residual <= 1e-8 in f64,
# deterministic + auditable attempt history)
# --------------------------------------------------------------------------

def test_resilient_clean_single_attempt(f64):
    a, b = _system(64, spd=True)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg", tol=1e-10,
                  policy="resilient", return_info=True)
    assert r.info["policy"] == "resilient"
    assert len(r.info["attempts"]) == 1
    assert r.info["attempts"][0]["reason"] == "ok"
    assert _resid(a, b, r.x) <= 1e-8


@pytest.mark.parametrize("site,mode,kw", [
    ("matvec", "nan", {}),
    ("matvec", "bitflip", {"bit": 62}),   # exponent MSB: material in f64
    ("update", "inf", {}),
])
def test_resilient_recovers_iterative_faults(f64, site, mode, kw):
    """Transient trace faults die on the retry's re-trace — the attempt
    history shows the classified failure, then ok."""
    a, b = _system(64, spd=True)
    with inject.inject(site=site, mode=mode, **kw) as ses:
        r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg",
                      tol=1e-10, policy="resilient", return_info=True)
    assert ses.fired >= 1
    reasons = [t["reason"] for t in r.info["attempts"]]
    assert reasons[-1] == "ok" and len(reasons) >= 2
    assert _resid(a, b, r.x) <= 1e-8


def test_resilient_ca_cg_gram_fault(f64):
    a, b = _system(64, spd=True)
    with inject.inject(site="gram", mode="scale", scale_by=1e6,
                       trips=2) as ses:
        r = api.solve(jnp.asarray(a), jnp.asarray(b), method="ca_cg", s=2,
                      tol=1e-10, policy="resilient", return_info=True)
    assert ses.fired >= 1
    assert _resid(a, b, r.x) <= 1e-8


def test_resilient_spmd_psum_corruption(f64):
    """An Inf in the ‖b‖ reduction makes the driver's tolerance infinite
    — it 'converges' at iteration 0.  The independent residual audit
    catches the lie and the retry recovers."""
    a, b = _system(64, spd=True)
    with inject.inject(site="psum", mode="inf") as ses:
        r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg",
                      tol=1e-10, mesh=_mesh(), engine="spmd",
                      policy="resilient", return_info=True)
    assert ses.fired >= 1
    reasons = [t["reason"] for t in r.info["attempts"]]
    assert reasons[-1] == "ok" and reasons[0] != "ok"
    assert _resid(a, b, r.x) <= 1e-8


def test_resilient_spmd_direct_abft_retry(f64):
    """policy="resilient" turns abft on for spmd lu/cholesky: the
    corrupted attempt is classified (FactorCorruption caught), the
    retry's clean re-trace succeeds."""
    a, b = _system(64)
    with inject.inject(site="trailing", mode="scale", at_rank=0) as ses:
        r = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                      mesh=_mesh(), engine="spmd", block_size=16,
                      policy="resilient", return_info=True)
    assert ses.fired >= 1
    assert r.info["attempts"][0]["reason"].startswith("error")
    assert r.info["attempts"][-1]["reason"] == "ok"
    assert _resid(a, b, r.x) <= 1e-8


def test_resilient_fallback_chain_and_register(f64):
    from repro.resilience import policy
    assert policy.fallback_chain("ca_cg") == ["cg", "gmres", "lu"]
    api.register_fallback("cg", "bicgstab")
    try:
        assert policy.fallback_chain("cg") == ["bicgstab", "gmres", "lu"]
        a, b = _system(64, spd=True)
        # cg traces two matvec taps per attempt: trips=4 burns attempts
        # 1 (as-requested) and 2 (retry), so the override rung runs
        with inject.inject(site="matvec", mode="nan", trips=4):
            r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg",
                          tol=1e-10, policy="resilient", return_info=True)
        assert r.info["attempts"][2]["method"] == "bicgstab"
        assert _resid(a, b, r.x) <= 1e-8
    finally:
        api.register_fallback("cg", "gmres")
    with pytest.raises(ValueError, match="unknown method"):
        api.register_fallback("cg", "not_a_method")


def test_resilient_exhaustion_raises_with_history(f64):
    """When every rung errors (here: one ABFT-guarded attempt against a
    persistent fault), the policy raises with the audit trail instead of
    returning a silently bad iterate."""
    a, b = _system(64)
    with inject.inject(site="trailing", mode="scale", at_rank=0):
        with pytest.raises(RuntimeError, match="exhausted 1 attempt"):
            api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                      mesh=_mesh(), engine="spmd", block_size=16,
                      policy="resilient", max_attempts=1)


def test_resilient_pallas_drops_to_ref(f64):
    """backend="pallas" gets a ref rung before the fallback chain."""
    from repro.resilience import policy
    a, b = _system(64, spd=True)
    r = policy.resilient_solve(jnp.asarray(a), jnp.asarray(b), method="cg",
                               backend="pallas", tol=1e-10,
                               return_info=True)
    assert _resid(a, b, r.x) <= 1e-8
    ladder = [(t["method"], t["backend"]) for t in r.info["attempts"]]
    assert ladder[0] == ("cg", "pallas")


def test_policy_unknown_rejected():
    a, b = _system(16, dtype=np.float32)
    with pytest.raises(ValueError, match="policy"):
        api.solve(jnp.asarray(a), jnp.asarray(b), policy="heroic")


# --------------------------------------------------------------------------
# input validation + warm starts
# --------------------------------------------------------------------------

def test_validate_rejects_non_finite():
    a, b = _system(16, dtype=np.float32)
    bad = jnp.asarray(a).at[3, 4].set(jnp.nan)
    with pytest.raises(ValueError, match="non-finite"):
        api.solve(bad, jnp.asarray(b))
    with pytest.raises(ValueError, match="non-finite"):
        api.solve(jnp.asarray(a), jnp.asarray(b).at[0].set(jnp.inf))
    with pytest.raises(ValueError, match="non-finite"):
        api.factorize(bad)
    with pytest.raises(ValueError, match="non-finite"):
        api.eigsolve(bad, k=2)
    # validate=False restores the old behavior (garbage in, garbage out)
    x = api.solve(bad, jnp.asarray(b), validate=False)
    assert not np.isfinite(np.asarray(x)).all()


def test_validate_rejects_non_spd_hints():
    a, b = _system(16, dtype=np.float32)     # general, not symmetric
    with pytest.raises(ValueError, match="symmetr"):
        api.solve(jnp.asarray(a), jnp.asarray(b), method="cholesky")
    spd, _ = _system(16, spd=True, dtype=np.float32)
    spd[2, 2] = -1.0
    with pytest.raises(ValueError, match="diagonal"):
        api.solve(jnp.asarray(spd), jnp.asarray(b), method="cholesky")


def test_validate_skips_tracers():
    """Under jit everything is a tracer: the checks vanish (zero jaxpr
    overhead) instead of forcing a device sync."""
    a, b = _system(16, spd=True, dtype=np.float32)
    x = jax.jit(lambda A, B: api.solve(A, B, method="cholesky"))(
        jnp.asarray(a), jnp.asarray(b))
    assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-3


def test_x0_warm_start(f64):
    a, b = _system(64, spd=True)
    x_ref = np.linalg.solve(a, b)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg", tol=1e-8,
                  x0=jnp.asarray(x_ref), return_info=True)
    assert int(r.iterations) <= 2
    r_cold = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg",
                       tol=1e-8, return_info=True)
    assert int(r_cold.iterations) > int(r.iterations)


def test_x0_spmd_engine(f64):
    a, b = _system(64, spd=True)
    x_ref = np.linalg.solve(a, b)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cg", tol=1e-8,
                  mesh=_mesh(), engine="spmd", x0=jnp.asarray(x_ref),
                  return_info=True)
    assert int(jnp.max(r.iterations)) <= 2


def test_x0_direct_rejected():
    a, b = _system(16, dtype=np.float32)
    with pytest.raises(ValueError, match="x0"):
        api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  x0=jnp.asarray(b))


# --------------------------------------------------------------------------
# checkpointed solves (acceptance: save -> kill -> resume continues from
# the persisted iterate, recoveries audited in info)
# --------------------------------------------------------------------------

def test_checkpointed_save_kill_resume(f64, tmp_path):
    from repro.distributed import fault_tolerance as ft
    from repro.resilience import runner
    a, b = _system(96, spd=True, seed=2)
    res = runner.checkpointed_solve(
        jnp.asarray(a), jnp.asarray(b), directory=str(tmp_path),
        method="cg", tol=1e-10, maxiter=200, every=10,
        injector=ft.FailureInjector({1}))
    assert res.info["recoveries"] == 1
    assert res.info["checkpoint_steps"]           # something persisted
    assert bool(res.converged)
    assert _resid(a, b, res.x) <= 1e-8


def test_checkpointed_resume_across_processes(f64, tmp_path):
    """The kill half: run a bounded chunk, 'crash', start over from the
    directory — the second run resumes past the persisted iterate."""
    from repro.resilience import runner
    a, b = _system(96, spd=True, seed=2)
    r1 = runner.checkpointed_solve(
        jnp.asarray(a), jnp.asarray(b), directory=str(tmp_path),
        method="cg", tol=1e-12, maxiter=10, every=5)
    assert int(r1.iterations) == 10 and not bool(r1.converged)
    r2 = runner.checkpointed_solve(
        jnp.asarray(a), jnp.asarray(b), directory=str(tmp_path),
        method="cg", tol=1e-10, maxiter=400, every=50)
    assert r2.info["resumed_from"] >= 10 - 5      # warm, not from zero
    assert bool(r2.converged)
    assert _resid(a, b, r2.x) <= 1e-8
    # resume=False ignores the checkpoints and starts cold
    r3 = runner.checkpointed_solve(
        jnp.asarray(a), jnp.asarray(b), directory=str(tmp_path),
        method="cg", tol=1e-10, maxiter=400, every=400, resume=False)
    assert r3.info["resumed_from"] == 0


def test_checkpointed_too_many_failures(f64, tmp_path):
    from repro.distributed import fault_tolerance as ft
    from repro.resilience import runner
    a, b = _system(64, spd=True)
    with pytest.raises(ft.NodeFailure):
        runner.checkpointed_solve(
            jnp.asarray(a), jnp.asarray(b), directory=str(tmp_path),
            method="cg", tol=1e-14, maxiter=100, every=5, max_failures=1,
            injector=ft.FailureInjector(set(range(20))))


# --------------------------------------------------------------------------
# multi-device subprocess battery (2 and 8 virtual devices)
# --------------------------------------------------------------------------

@pytest.mark.timeout(600)
@pytest.mark.parametrize("ndev", [2, 8])
def test_resilience_battery_subprocess(ndev):
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(SRC),
               RESILIENCE_DEVICES=str(ndev),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.selftest_resilience"],
        capture_output=True, text=True, env=env, timeout=550)
    assert "RESILIENCE PASS" in proc.stdout, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
