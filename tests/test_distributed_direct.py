"""Distributed direct solvers (block-cyclic SPMD LU/Cholesky, PR 4).

Two layers:

* in-process tests on a (1, 1) mesh (or the real device set when the run
  is launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  — CI's spmd job does this): parity, padding, the single-shard_map
  guarantee, API surface;
* subprocess parity batteries at 2 and 8 virtual devices (the main pytest
  process must keep its 1-device view — same pattern as
  tests/test_multidevice.py).
"""
import functools
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api, blocking, cholesky, dist, lu, triangular
from repro.launch import mesh as mesh_lib

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _mesh():
    """Largest supported mesh for the current device count: (4, 2) under
    CI's 8-virtual-device spmd job, (1, 1) in the default tier-1 run."""
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


@pytest.fixture()
def f64():
    old = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


# --------------------------------------------------------------------------
# parity (acceptance: <= 1e-10 in f64, local == spmd)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method,spd", [("lu", False), ("cholesky", True)])
def test_spmd_direct_parity_f64(f64, method, spd):
    mesh = _mesh()
    n = 128
    a, b = _system(n, spd=spd)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method=method, mesh=mesh,
                  engine="spmd", block_size=16)
    x_loc = api.solve(jnp.asarray(a), jnp.asarray(b), method=method,
                      block_size=16)
    assert np.abs(np.asarray(x) - np.asarray(x_loc)).max() <= 1e-10
    assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-10


@pytest.mark.parametrize("method,spd", [("lu", False), ("cholesky", True)])
def test_spmd_direct_padded_f64(f64, method, spd):
    """n % nb != 0 goes through the core/blocking identity-pad policy."""
    mesh = _mesh()
    n = 110
    a, b = _system(n, spd=spd, seed=3)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method=method, mesh=mesh,
                  engine="spmd", block_size=32)
    assert x.shape == (n,)
    assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-10


def test_spmd_factor_matches_local_cyclic_storage(f64):
    """The distributed factor IS the local factor, columns cyclicly
    stored; pivot sequences are identical."""
    mesh = _mesh()
    n = 128
    a, _ = _system(n)
    st = lu.lu_factor_spmd(jnp.asarray(a), block_size=16, mesh=mesh)
    lu_loc, perm_loc = lu.lu_factor(jnp.asarray(a), block_size=16)
    assert np.abs(np.asarray(st.lu)
                  - np.asarray(lu_loc)[:, st.layout.colperm]).max() <= 1e-10
    assert (np.asarray(st.perm) == np.asarray(perm_loc)).all()


def test_spmd_multi_rhs_and_factorize_reuse(f64):
    mesh = _mesh()
    n = 96
    a, _ = _system(n, spd=True, seed=5)
    solver = api.factorize(jnp.asarray(a), method="cholesky", mesh=mesh,
                           engine="spmd", block_size=16)
    rng = np.random.default_rng(7)
    for _ in range(2):
        b = rng.standard_normal((n, 3))
        x = solver(jnp.asarray(b))
        assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-10


def test_spmd_triangular_solves(f64):
    mesh = _mesh()
    n = 96
    rng = np.random.default_rng(2)
    t = np.tril(rng.standard_normal((n, n))) / n + 4 * np.eye(n)
    b = rng.standard_normal(n)
    y = triangular.solve_lower_spmd(jnp.asarray(t), jnp.asarray(b),
                                    block_size=16, mesh=mesh)
    y_loc = triangular.solve_lower_blocked(jnp.asarray(t), jnp.asarray(b),
                                           block_size=16)
    assert np.abs(np.asarray(y) - np.asarray(y_loc)).max() <= 1e-10
    x = triangular.solve_upper_spmd(jnp.asarray(t.T), jnp.asarray(b),
                                    block_size=16, mesh=mesh)
    x_loc = triangular.solve_upper_blocked(jnp.asarray(t.T), jnp.asarray(b),
                                           block_size=16)
    assert np.abs(np.asarray(x) - np.asarray(x_loc)).max() <= 1e-10


# --------------------------------------------------------------------------
# lookahead pipeline (acceptance: BITWISE parity with the non-lookahead
# schedule — both consume byte-identical panel inputs — and exactly one
# extra pipeline-fill broadcast in the lookahead trace)
# --------------------------------------------------------------------------

def _factor_bytes(method, a, lookahead):
    if method == "lu":
        st = lu.lu_factor_spmd(a, block_size=16, mesh=_mesh(),
                               lookahead=lookahead)
        return np.asarray(st.lu), np.asarray(st.perm)
    st = cholesky.cholesky_factor_spmd(a, block_size=16, mesh=_mesh(),
                                       lookahead=lookahead)
    return np.asarray(st.l), None


@pytest.mark.parametrize("method,spd", [("lu", False), ("cholesky", True)])
def test_lookahead_bitwise_parity(f64, method, spd):
    n = 128
    a, _ = _system(n, spd=spd, seed=11)
    f_la, p_la = _factor_bytes(method, jnp.asarray(a), True)
    f_no, p_no = _factor_bytes(method, jnp.asarray(a), False)
    assert np.array_equal(f_la, f_no)          # bitwise (== semantics)
    if p_la is not None:
        assert np.array_equal(p_la, p_no)


@pytest.mark.parametrize("factor", [
    functools.partial(lu.lu_factor_spmd, block_size=16),
    functools.partial(cholesky.cholesky_factor_spmd, block_size=16),
])
def test_lookahead_one_extra_panel_broadcast(f64, factor):
    """Trace-time collective tally: the fori_loop body traces ONCE, so
    the steady-state schedule costs 1 broadcast per trace in both modes
    and the lookahead adds exactly its pipeline-fill prologue."""
    from repro.core import pblas
    n = 128
    a, _ = _system(n, spd=True, seed=12)
    with pblas.collective_counts() as c_la:
        factor(jnp.asarray(a), mesh=_mesh(), lookahead=True)
    with pblas.collective_counts() as c_no:
        factor(jnp.asarray(a), mesh=_mesh(), lookahead=False)
    assert c_la["bcast"] == c_no["bcast"] + 1


# --------------------------------------------------------------------------
# the single-shard_map guarantee (acceptance: ONE shard_map-wrapped
# factorization, no per-step re-entry)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mod,factor_name,spd", [
    (lu, "lu_factor_spmd", False),
    (cholesky, "cholesky_factor_spmd", True),
])
def test_exactly_one_shard_map_per_factorization(monkeypatch, mod,
                                                 factor_name, spd):
    mesh = _mesh()
    calls = {"n": 0}
    orig = mod.shard_map

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(mod, "shard_map", spy)
    n = 128   # 8 block steps at nb=16: a per-step re-entry would show
    a, _ = _system(n, spd=spd, dtype=np.float32)
    getattr(mod, factor_name)(jnp.asarray(a), block_size=16, mesh=mesh)
    assert calls["n"] == 1


# --------------------------------------------------------------------------
# Pallas kernels per-shard (backend="pallas" is legal on the spmd path)
# --------------------------------------------------------------------------

def test_spmd_pallas_backend_runs_gemm_kernel(monkeypatch):
    from repro.kernels import gemm
    calls = {"n": 0}
    orig = gemm.matmul

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(gemm, "matmul", spy)
    mesh = _mesh()
    n = 64
    a, b = _system(n, dtype=np.float32)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu", mesh=mesh,
                  engine="spmd", block_size=32, backend="pallas")
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)
    assert calls["n"] > 0   # trailing rank-nb update ran the Pallas GEMM


def test_spmd_pallas_f64_falls_back_to_exact_ref(f64):
    """Same silent-fallback rule as everywhere else: f64 never degrades."""
    mesh = _mesh()
    n = 64
    a, b = _system(n)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu", mesh=mesh,
                  engine="spmd", block_size=16, backend="pallas")
    assert x.dtype == jnp.float64
    assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-10


# --------------------------------------------------------------------------
# API surface / audited error messages
# --------------------------------------------------------------------------

def test_spmd_direct_requires_mesh():
    a, b = _system(32, dtype=np.float32)
    with pytest.raises(ValueError, match="requires a mesh"):
        api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  engine="spmd")
    with pytest.raises(ValueError, match="requires a mesh"):
        api.factorize(jnp.asarray(a), method="lu", engine="spmd")


def test_spmd_direct_without_split_names_alternatives():
    api.register_method("legacy_direct",
                        lambda a, b, *, block_size, mesh: lu.solve(
                            a, b, block_size=block_size, mesh=mesh),
                        kind="direct")
    try:
        a, b = _system(32, dtype=np.float32)
        with pytest.raises(ValueError, match="cholesky.*lu|lu.*cholesky"):
            api.solve(jnp.asarray(a), jnp.asarray(b), method="legacy_direct",
                      mesh=_mesh(), engine="spmd")
    finally:
        api._REGISTRY.pop("legacy_direct", None)


def test_factorize_works_for_spmd_only_method(f64):
    """A direct method may register ONLY the distributed pair; factorize
    must reach the spmd dispatch before demanding a local split."""
    api.register_method("dist_only", lu.solve_spmd, kind="direct",
                        spmd_factor=lu.lu_factor_spmd,
                        spmd_apply=lu.lu_apply_spmd)
    try:
        a, b = _system(48, seed=9)
        solver = api.factorize(jnp.asarray(a), method="dist_only",
                               mesh=_mesh(), engine="spmd", block_size=16)
        x = solver(jnp.asarray(b))
        assert np.abs(np.asarray(x) - np.linalg.solve(a, b)).max() <= 1e-10
        with pytest.raises(ValueError, match="factor/apply"):
            api.factorize(jnp.asarray(a), method="dist_only")
    finally:
        api._REGISTRY.pop("dist_only", None)


def test_register_spmd_pair_validation():
    with pytest.raises(ValueError, match="spmd_factor"):
        api.register_method("bad_spmd", lambda a, b: b, kind="direct",
                            factor=lambda a: (a,), apply=lambda s, b: b,
                            spmd_factor=lambda a: (a,))
    api._REGISTRY.pop("bad_spmd", None)


def test_spmd_methods_listed():
    assert api._spmd_direct_methods() == ("cholesky", "lu", "qr")


# --------------------------------------------------------------------------
# multi-device subprocess batteries (2 and 8 virtual devices)
# --------------------------------------------------------------------------

@pytest.mark.timeout(600)
@pytest.mark.parametrize("ndev", [2, 8])
def test_distributed_battery_subprocess(ndev):
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(SRC),
               DIRECT_SPMD_DEVICES=str(ndev),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.selftest_direct"],
        capture_output=True, text=True, env=env, timeout=550)
    assert "DIRECT SPMD PASS" in proc.stdout, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
