"""Least-squares & eigenvalue subsystem (PR 5): blocked Householder QR,
distributed TSQR, LSQR/CGLS, Lanczos/Arnoldi.

Mirrors the structure of tests/test_direct_fast.py /
test_distributed_direct.py: f64 parity batteries, Pallas kernel spies,
the exactly-one-shard_map guarantee, API-surface audits, and a subprocess
multi-device battery (2 and 8 virtual devices) via
``repro.launch.selftest_eigls``.
"""
import functools
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api, blocking, dist, krylov, qr
from repro.core.operator import DenseOperator
from repro.sparse import BSR, problems
from repro.launch import mesh as mesh_lib

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture()
def f64():
    old = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


def _rect(m, n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, n)).astype(dtype)
    b = rng.standard_normal(m).astype(dtype)
    return a, b


def _mesh():
    ndev = len(jax.devices())
    if ndev >= 8:
        return mesh_lib.make_mesh((4, 2), ("data", "model"),
                                  devices=jax.devices()[:8])
    return dist.single_device_mesh()


# --------------------------------------------------------------------------
# blocked QR: parity vs jnp.linalg.qr (acceptance: f64 <= 1e-10)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,bs", [(128, 128, 32), (192, 96, 32),
                                    (150, 70, 32), (100, 37, 16)])
def test_qr_parity_vs_jnp(f64, m, n, bs):
    a, _ = _rect(m, n)
    q, r = qr.reduced(jnp.asarray(a), block_size=bs)
    qj, rj = jnp.linalg.qr(jnp.asarray(a))
    s = np.sign(np.diag(np.asarray(rj)))
    s[s == 0] = 1
    assert np.abs(np.asarray(q) - np.asarray(qj) * s[None, :]).max() <= 1e-10
    assert np.abs(np.asarray(r) - np.asarray(rj) * s[:, None]).max() <= 1e-10
    assert np.abs(np.asarray(q) @ np.asarray(r) - a).max() <= 1e-10


def test_qr_jaxpr_size_independent_of_mn():
    """Same O(1)-trace guarantee as the square direct factorizations."""
    def count(m, n):
        fn = functools.partial(qr.qr_factor, block_size=32)
        jaxpr = jax.make_jaxpr(fn)(jnp.zeros((m, n), jnp.float32)).jaxpr

        def total(jx):
            tot = len(jx.eqns)
            for eq in jx.eqns:
                for v in eq.params.values():
                    subs = v if isinstance(v, (list, tuple)) else (v,)
                    for s in subs:
                        if hasattr(s, "jaxpr"):
                            tot += total(s.jaxpr)
            return tot
        return total(jaxpr)
    assert count(256, 128) == count(1024, 512)


@pytest.mark.parametrize("m,n", [(160, 64), (128, 128)])
def test_qr_least_squares_solve(f64, m, n):
    a, b = _rect(m, n, seed=3)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr",
                  block_size=32)
    xo = np.linalg.lstsq(a, b, rcond=None)[0]
    assert np.abs(np.asarray(x) - xo).max() <= 1e-10


def test_qr_pallas_parity_and_kernel_spy(monkeypatch):
    from repro.kernels import qr_fused
    calls = {"n": 0}
    orig = qr_fused.qr_panel_update

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(qr_fused, "qr_panel_update", spy)
    a, b = _rect(128, 64, dtype=np.float32, seed=1)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr",
                  backend="pallas", block_size=32)
    xo = np.linalg.lstsq(a, b, rcond=None)[0]
    np.testing.assert_allclose(np.asarray(x), xo, rtol=1e-3, atol=1e-4)
    assert calls["n"] > 0            # fused panel kernel ran in the loop


def test_qr_pallas_unfused_composes_gemm(monkeypatch):
    from repro.kernels import gemm
    calls = {"n": 0}
    orig = gemm.matmul

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(gemm, "matmul", spy)
    a, _ = _rect(96, 48, dtype=np.float32, seed=2)
    st = qr.qr_factor(jnp.asarray(a), block_size=16, backend="pallas",
                      fuse_panel=False)
    st_ref = qr.qr_factor(jnp.asarray(a), block_size=16)
    np.testing.assert_allclose(np.asarray(st.qr), np.asarray(st_ref.qr),
                               rtol=1e-3, atol=1e-4)
    assert calls["n"] > 0            # kernels/gemm.matmul composition


def test_qr_batched_and_multirhs(f64):
    B, m, n = 3, 96, 40
    rng = np.random.default_rng(5)
    a = rng.standard_normal((B, m, n))
    b = rng.standard_normal((B, m))
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr", block_size=16)
    for i in range(B):
        xo = np.linalg.lstsq(a[i], b[i], rcond=None)[0]
        assert np.abs(np.asarray(x[i]) - xo).max() <= 1e-10
    # multi-rhs through factorize reuse
    solver = api.factorize(jnp.asarray(a[0]), method="qr", block_size=16)
    bm = rng.standard_normal((m, 2))
    xm = solver(jnp.asarray(bm))
    xo = np.linalg.lstsq(a[0], bm, rcond=None)[0]
    assert np.abs(np.asarray(xm) - xo).max() <= 1e-10


def test_pad_rect_policy():
    a = jnp.zeros((70, 33))
    ap, nb, m_pad, n_pad = blocking.pad_rect(a, 32)
    assert (m_pad % nb, n_pad % nb) == (0, 0)
    assert m_pad - 70 >= n_pad - 33       # pad rows host the unit columns
    with pytest.raises(ValueError, match="underdetermined"):
        blocking.pad_rect(jnp.zeros((33, 70)), 32)
    with pytest.raises(ValueError, match="block_size"):
        blocking.pad_rect(a, 0)


# --------------------------------------------------------------------------
# LSQR / CGLS vs the normal-equations oracle (dense + BSR, ref + pallas)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["lsqr", "cgls"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_ls_iterative_dense(f64, method, backend):
    a, b = _rect(300, 80, seed=7)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method=method,
                  backend=backend, tol=1e-12, maxiter=400, return_info=True)
    xo = np.linalg.solve(a.T @ a, a.T @ b)        # normal-equations oracle
    assert bool(r.converged)
    assert np.abs(np.asarray(r.x) - xo).max() <= 1e-9


@pytest.mark.parametrize("method", ["lsqr", "cgls"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_ls_iterative_bsr(f64, method, backend):
    rng = np.random.default_rng(11)
    m, n = 320, 96
    d = rng.standard_normal((m, n))
    d[np.abs(d) < 1.0] = 0
    b = rng.standard_normal(m)
    a = BSR.from_dense(d, block_size=16)
    r = api.solve(a, jnp.asarray(b), method=method, backend=backend,
                  tol=1e-12, maxiter=400, return_info=True)
    xo = np.linalg.solve(d.T @ d, d.T @ b)
    assert bool(r.converged)
    assert np.abs(np.asarray(r.x) - xo).max() <= 1e-9


@pytest.mark.timeout(300)
def test_lsqr_acceptance_shape_4096x512():
    """Acceptance: lsqr converges on a rectangular 4096x512 dense and BSR
    problem (f32; the pallas-backend sweep runs on the smaller shapes
    above — interpret-mode SpMV at this size is minutes, not signal)."""
    rng = np.random.default_rng(41)
    m, n = 4096, 512
    a = rng.standard_normal((m, n)).astype(np.float32)
    b = rng.standard_normal(m).astype(np.float32)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="lsqr", tol=1e-5,
                  maxiter=200, return_info=True)
    xo = np.linalg.lstsq(a, b, rcond=None)[0]
    assert bool(r.converged)
    assert np.abs(np.asarray(r.x) - xo).max() <= 1e-4
    d = a.copy()
    d[np.abs(d) < 2.3] = 0                 # ~2% density: a real sparse LS
    bsr = BSR.from_dense(d, block_size=16)
    r = api.solve(bsr, jnp.asarray(b), method="lsqr", tol=1e-5,
                  maxiter=300, return_info=True)
    xs = np.linalg.lstsq(d, b, rcond=None)[0]
    assert bool(r.converged)
    assert np.abs(np.asarray(r.x) - xs).max() <= 1e-3


def test_cgls_pallas_runs_fused_update_on_square(monkeypatch):
    """Square least squares drives the fused axpy-pair kernel."""
    from repro.kernels import krylov_fused
    calls = {"n": 0}
    orig = krylov_fused.fused_cg_update_auto

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(krylov_fused, "fused_cg_update_auto", spy)
    rng = np.random.default_rng(0)
    n = 128
    a = (rng.standard_normal((n, n)) + n * np.eye(n)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="cgls",
                  backend="pallas", tol=1e-6, maxiter=300)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-3)
    assert calls["n"] > 0


def test_ls_matrix_free_callable(f64):
    a, b = _rect(200, 50, seed=13)
    aj = jnp.asarray(a)
    r = krylov.lsqr(lambda v: aj @ v, jnp.asarray(b),
                    matvec_t=lambda v: aj.T @ v, tol=1e-12, maxiter=200)
    xo = np.linalg.solve(a.T @ a, a.T @ b)
    assert np.abs(np.asarray(r.x) - xo).max() <= 1e-9


def test_cgls_f32_returns_best_iterate():
    """Past its attainable floor f32 CGLS diverges; the driver must return
    the best iterate, not the diverged one."""
    a, b = _rect(384, 96, dtype=np.float32, seed=0)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="cgls",
                  tol=1e-9, maxiter=500, return_info=True)
    xo = np.linalg.lstsq(a, b, rcond=None)[0]
    assert np.abs(np.asarray(r.x) - xo).max() <= 1e-5
    assert int(r.iterations) < 500          # divergence cutoff fired


# --------------------------------------------------------------------------
# TSQR (spmd == local parity + the one-shard_map guarantee)
# --------------------------------------------------------------------------

def test_tsqr_matches_local_qr(f64):
    from repro.eigls import tsqr
    mesh = _mesh()
    a, b = _rect(256, 32, seed=17)   # m/P >= n on the CI (4, 2) mesh too
    qd, rd = tsqr.tsqr(jnp.asarray(a), mesh)
    ql, rl = qr.reduced(jnp.asarray(a), block_size=16)
    assert np.abs(np.asarray(qd) - np.asarray(ql)).max() <= 1e-10
    assert np.abs(np.asarray(rd) - np.asarray(rl)).max() <= 1e-10
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr",
                  engine="spmd", mesh=mesh)
    x_loc = api.solve(jnp.asarray(a), jnp.asarray(b), method="qr",
                      block_size=16)
    assert np.abs(np.asarray(x) - np.asarray(x_loc)).max() <= 1e-10


def test_tsqr_exactly_one_shard_map(monkeypatch, f64):
    from repro.eigls import tsqr
    calls = {"n": 0}
    orig = tsqr.shard_map

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(tsqr, "shard_map", spy)
    a, _ = _rect(128, 16, seed=19)
    tsqr.tsqr_factor_spmd(jnp.asarray(a), mesh=_mesh())
    assert calls["n"] == 1


def test_tsqr_factorize_reuse_and_padded_rows(f64):
    mesh = _mesh()
    m, n = 250, 30                      # m % P != 0 on the (4, 2) mesh
    a, _ = _rect(m, n, seed=23)
    solver = api.factorize(jnp.asarray(a), method="qr", engine="spmd",
                           mesh=mesh)
    rng = np.random.default_rng(29)
    for _ in range(2):
        b = rng.standard_normal(m)
        x = solver(jnp.asarray(b))
        xo = np.linalg.lstsq(a, b, rcond=None)[0]
        assert np.abs(np.asarray(x) - xo).max() <= 1e-10


def test_tsqr_error_paths():
    from repro.eigls import tsqr
    a = jnp.zeros((64, 32), jnp.float32)
    with pytest.raises(ValueError, match="requires a mesh"):
        tsqr.tsqr_factor_spmd(a)
    with pytest.raises(ValueError, match="underdetermined"):
        tsqr.tsqr_factor_spmd(jnp.zeros((32, 64)), mesh=_mesh())


# --------------------------------------------------------------------------
# eigenvalues: Lanczos vs eigvalsh on poisson_2d (acceptance), Arnoldi
# --------------------------------------------------------------------------

def test_lanczos_poisson_extreme_eigenvalues(f64):
    """Acceptance: 5 extreme eigenvalues of poisson_2d(64) to <= 1e-8,
    matrix-free on BSR (multiplicity-2 pairs from the grid symmetry
    included — full reorthogonalization resolves them)."""
    a = problems.poisson_2d(64, dtype=np.float64)          # n = 4096
    bsr = BSR.from_dense(a, block_size=16)
    res = api.eigsolve(bsr, k=5, which="LA", ncv=400)
    wtrue = np.linalg.eigvalsh(a)[::-1][:5]
    got = np.sort(np.asarray(res.eigenvalues))[::-1]
    assert np.abs(got - wtrue).max() <= 1e-8
    # Ritz vectors are actual eigenvectors: ||A x - λ x|| small (paired in
    # the driver's own ordering)
    w = np.asarray(res.eigenvalues)
    x = np.asarray(res.eigenvectors)
    for i in range(5):
        assert np.linalg.norm(a @ x[:, i] - w[i] * x[:, i]) <= 1e-5


def test_lanczos_smallest_and_both_ends(f64):
    a = problems.poisson_2d(16, dtype=np.float64)          # n = 256
    w = np.linalg.eigvalsh(a)
    res = api.eigsolve(jnp.asarray(a), k=3, which="SA", ncv=256)
    assert np.abs(np.sort(np.asarray(res.eigenvalues)) - w[:3]).max() <= 1e-8
    res = api.eigsolve(jnp.asarray(a), k=4, which="BE", ncv=256)
    got = np.sort(np.asarray(res.eigenvalues))
    want = np.sort(np.concatenate([w[:2], w[-2:]]))
    assert np.abs(got - want).max() <= 1e-8


def test_lanczos_matrix_free_and_spy(monkeypatch, f64):
    """eigsolve on BSR with backend='pallas' streams the SpMV kernel —
    never densifies."""
    from repro.kernels import spmv
    calls = {"n": 0}
    orig = spmv.bsr_matvec

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(spmv, "bsr_matvec", spy)
    a = problems.poisson_2d(16, dtype=np.float64)
    bsr = BSR.from_dense(a, block_size=16)
    res = api.eigsolve(bsr, k=3, which="LA", ncv=100, backend="pallas")
    wtrue = np.linalg.eigvalsh(a)[::-1][:3]
    assert np.abs(np.sort(np.asarray(res.eigenvalues))[::-1]
                  - wtrue).max() <= 1e-8
    assert calls["n"] > 0


def test_arnoldi_general_matrix(f64):
    rng = np.random.default_rng(31)
    n = 160
    a = rng.standard_normal((n, n)) / np.sqrt(n)
    res = api.eigsolve(jnp.asarray(a), k=4, which="LM", method="arnoldi",
                       ncv=120)
    w = np.linalg.eigvals(a)
    want = np.sort(np.abs(w))[::-1][:4]
    got = np.sort(np.abs(np.asarray(res.eigenvalues)))[::-1]
    assert np.abs(got - want).max() <= 1e-6


def test_eigsolve_gspmd_mesh(f64):
    """The same driver runs on the GSPMD-sharded engine."""
    a = problems.poisson_2d(16, dtype=np.float64)
    res = api.eigsolve(jnp.asarray(a), k=3, which="LA", ncv=100,
                       mesh=_mesh())
    wtrue = np.linalg.eigvalsh(a)[::-1][:3]
    assert np.abs(np.sort(np.asarray(res.eigenvalues))[::-1]
                  - wtrue).max() <= 1e-8


def test_eigsolve_api_surface():
    a = jnp.eye(16)
    with pytest.raises(ValueError, match="unknown eig method"):
        api.eigsolve(a, method="qz")
    with pytest.raises(ValueError, match="which"):
        api.eigsolve(a, which="XX")
    with pytest.raises(ValueError, match="square"):
        api.eigsolve(jnp.zeros((16, 8)))
    with pytest.raises(ValueError, match="needs n="):
        api.eigsolve(lambda v: v)
    # bare callable with explicit n works
    res = api.eigsolve(lambda v: 2.0 * v, k=2, n=16, ncv=8)
    assert np.allclose(np.asarray(res.eigenvalues), 2.0, atol=1e-5)


# --------------------------------------------------------------------------
# multi-device subprocess battery (2 and 8 virtual devices)
# --------------------------------------------------------------------------

@pytest.mark.timeout(600)
@pytest.mark.parametrize("ndev", [2, 8])
def test_eigls_battery_subprocess(ndev):
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(SRC),
               EIGLS_DEVICES=str(ndev),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.selftest_eigls"],
        capture_output=True, text=True, env=env, timeout=550)
    assert "EIGLS PASS" in proc.stdout, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
