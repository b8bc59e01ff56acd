"""Fast direct-solver path (PR 2): fori_loop factorizations, Pallas
backend, batched solves, padding policy, registry factorize."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api, blocking, cholesky, dist, lu, triangular


def _system(n, spd=False, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(dtype)
    if spd:
        a = (a @ a.T / n + 4.0 * np.eye(n)).astype(dtype)
    else:
        a = (a + n * np.eye(n)).astype(dtype)
    b = rng.standard_normal(n).astype(dtype)
    return a, b


def _batch(B, n, spd=False, seed=0):
    mats, rhs = [], []
    for i in range(B):
        a, b = _system(n, spd=spd, seed=seed + i)
        mats.append(a)
        rhs.append(b)
    return np.stack(mats), np.stack(rhs)


# --------------------------------------------------------------------------
# compile guard: trace size is O(1) in n (the tentpole's whole point)
# --------------------------------------------------------------------------

def _total_eqns(jaxpr):
    tot = len(jaxpr.eqns)
    for eq in jaxpr.eqns:
        for v in eq.params.values():
            subs = v if isinstance(v, (list, tuple)) else (v,)
            for s in subs:
                if hasattr(s, "jaxpr"):
                    tot += _total_eqns(s.jaxpr)
    return tot


@pytest.mark.parametrize("factor", [
    functools.partial(lu.lu_factor, block_size=128),
    functools.partial(cholesky.cholesky_factor, block_size=128),
    functools.partial(triangular.solve_lower_blocked, block_size=128),
])
def test_jaxpr_size_independent_of_n(factor):
    def count(n):
        args = (jnp.zeros((n, n), jnp.float32),)
        if "blocked" in getattr(factor.func, "__name__", ""):
            args += (jnp.zeros((n,), jnp.float32),)
        return _total_eqns(jax.make_jaxpr(factor)(*args).jaxpr)
    assert count(256) == count(1024)


def _gather_modes(jaxpr):
    modes = []
    for eq in jaxpr.eqns:
        if eq.primitive.name == "gather":
            modes.append(eq.params["mode"])
        for v in eq.params.values():
            for s in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(s, "eqns"):
                    modes += _gather_modes(s)
                elif hasattr(s, "jaxpr"):
                    modes += _gather_modes(s.jaxpr)
    return modes


def _spmd_factor(a):
    st = lu.lu_factor_spmd(a, block_size=128,
                           mesh=dist.single_device_mesh())
    return st.lu, st.perm


@pytest.mark.parametrize("path,args", [
    (functools.partial(lu.lu_factor, block_size=128), ("a",)),
    (functools.partial(lu.lu_solve, block_size=128), ("a", "perm", "b")),
    (_spmd_factor, ("a",)),
    (functools.partial(lu.lu_factor, block_size=16), ("a",)),
], ids=["lu_factor", "lu_solve", "lu_factor_spmd", "lu_factor_staged"])
def test_row_permutation_gathers_promise_in_bounds(path, args):
    """Pivoting applies permutations of arange(n), which never leave the
    matrix: no gather may carry the out-of-bounds fill (a select over all
    of its output once lowered)."""
    from jax.lax import GatherScatterMode
    n = 512
    avals = {"a": jnp.zeros((n, n), jnp.float32),
             "perm": jnp.arange(n), "b": jnp.zeros((n,), jnp.float32)}
    modes = _gather_modes(
        jax.make_jaxpr(path)(*(avals[k] for k in args)).jaxpr)
    assert set(modes) == {GatherScatterMode.PROMISE_IN_BOUNDS}


# --------------------------------------------------------------------------
# staged trailing windows: the schedule, and bitwise parity with one stage
# --------------------------------------------------------------------------

@pytest.mark.parametrize("nblocks", [1, 2, 8, 31, 32, 33, 64, 128, 224,
                                     256, 1000])
def test_stage_bounds(nblocks):
    """Stages cover every block step once, in whole blocks (so each window
    is a multiple of nb); the first window is at most n/√2 wide and the
    first block boundary that is; the rest are equal; 16 stages at most;
    one stage for small systems and with a mesh."""
    b = lu.stage_bounds(nblocks)
    assert b[0] == 0 and b[-1] == nblocks
    assert all(type(s) is int for s in b)
    assert all(s0 < s1 for s0, s1 in zip(b, b[1:]))
    assert len(b) - 1 <= 16
    assert lu.stage_bounds(nblocks, mesh=dist.single_device_mesh()) \
        == (0, nblocks)
    if nblocks < 32:
        assert b == (0, nblocks)
        return
    w1 = nblocks - b[1]
    assert 2 * w1 ** 2 <= nblocks ** 2 < 2 * (w1 + 1) ** 2
    rest = [s1 - s0 for s0, s1 in zip(b[1:], b[2:])]
    assert len(b) - 1 == 16 and max(rest) - min(rest) <= 1


def _bounds(*b):
    return lambda nblocks, mesh=None: b


def _factor(a, nb, batch=False, **kw):
    f = functools.partial(lu.lu_factor, block_size=nb, **kw)
    return jax.jit(jax.vmap(f) if batch else f)(jnp.asarray(a))


@pytest.mark.parametrize("n,nb,schedule,kw", [
    (2048, 64, None, {}),
    (1024, 64, _bounds(0, 4, 8, 12, 16), {}),
    (1024, 64, _bounds(*range(17)), {}),
    (1000, 64, _bounds(0, 5, 9, 16), {}),
    (512, 64, _bounds(0, 2, 4, 6, 8), {"backend": "pallas"}),
    (512, 64, _bounds(0, 3, 8), {"backend": "pallas", "fuse_panel": False}),
    (1024, 64, _bounds(0, 5, 10, 16), {"batch": True}),
], ids=["default-32-blocks", "4-stages", "16-stages", "padded-uneven",
        "pallas-fused", "pallas-unfused", "vmap"])
def test_staged_lu_bitwise_equals_one_stage(monkeypatch, n, nb, schedule,
                                            kw):
    """The staged factorization IS the one-stage loop: factors and pivots
    bitwise equal.  (XLA's CPU product was seen to round differently in
    windows whose rows are an odd multiple of 32; every window here is a
    multiple of 64.)"""
    rng = np.random.default_rng(n + nb)
    shape = (3, n, n) if kw.get("batch") else (n, n)
    a = rng.uniform(-0.5, 0.5, shape).astype(np.float32)
    nblocks = -(-n // nb)
    if schedule is None:
        assert len(lu.stage_bounds(nblocks)) > 2
    else:
        monkeypatch.setattr(lu, "stage_bounds", schedule)
    staged = _factor(a, nb, **kw)
    monkeypatch.setattr(lu, "stage_bounds", _bounds(0, nblocks))
    one = _factor(a, nb, **kw)
    for x, y in zip(one, staged):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n,stages,share", [(28672, 16, 0.4235),
                                            (1024, 1, 1.0)])
def test_lu_stage_gauges(n, stages, share):
    """Traced under an armed telemetry session, ``lu_factor`` records its
    schedule: the stage count and the share of the whole-matrix loop's
    area that its windows pass over (tracing only; nothing runs)."""
    from repro import telemetry
    with telemetry.session("lu-stages", convergence=False, comm=False):
        jax.make_jaxpr(functools.partial(lu.lu_factor, block_size=128))(
            jax.ShapeDtypeStruct((n, n), jnp.float32))
    assert telemetry.metrics.get_gauge("lu.stages") == stages
    assert telemetry.metrics.get_gauge("lu.window_area_share") \
        == pytest.approx(share, abs=5e-5)


def test_lu_jaxpr_size_bounded_by_stages():
    """Past the one-stage sizes the trace grows with the stage count, not
    with n: 32 and 128 block steps both run 16 stages."""
    def count(n):
        return _total_eqns(jax.make_jaxpr(
            functools.partial(lu.lu_factor, block_size=128))(
                jax.ShapeDtypeStruct((n, n), jnp.float32)).jaxpr)
    assert count(4096) == count(16384)


# --------------------------------------------------------------------------
# Pallas backend parity (interpret mode on CPU)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method,spd", [("lu", False), ("cholesky", True)])
def test_pallas_backend_direct_parity(method, spd):
    n = 128
    a, b = _system(n, spd=spd)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method=method,
                  backend="pallas", block_size=32)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)


def test_pallas_backend_runs_pallas_kernels(monkeypatch):
    """backend='pallas' must actually dispatch to the Pallas kernels."""
    from repro.kernels import factor_fused, trsm
    calls = {"fused": 0, "trsm": 0}
    orig_fused = factor_fused.lu_panel_update
    orig_trsm = trsm.trsm_lower_auto

    def spy_fused(*a, **kw):
        calls["fused"] += 1
        return orig_fused(*a, **kw)

    def spy_trsm(*a, **kw):
        calls["trsm"] += 1
        return orig_trsm(*a, **kw)

    monkeypatch.setattr(factor_fused, "lu_panel_update", spy_fused)
    monkeypatch.setattr(trsm, "trsm_lower_auto", spy_trsm)
    n = 64
    a, b = _system(n)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  backend="pallas", block_size=32)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)
    assert calls["fused"] > 0          # fused panel kernel in the factor loop
    assert calls["trsm"] > 0           # Pallas TRSM in the blocked solves


@pytest.mark.parametrize("spd", [False, True])
def test_pallas_unfused_gemm_trsm_path(spd):
    """fuse_panel=False composes kernels/gemm.matmul + kernels/trsm."""
    n = 96
    a, _ = _system(n, spd=spd)
    if spd:
        l = cholesky.cholesky_factor(jnp.asarray(a), block_size=32,
                                     backend="pallas", fuse_panel=False)
        np.testing.assert_allclose(np.asarray(l @ l.T), a, rtol=1e-3,
                                   atol=1e-3)
    else:
        packed, perm = lu.lu_factor(jnp.asarray(a), block_size=32,
                                    backend="pallas", fuse_panel=False)
        low, up = lu.unpack(packed)
        np.testing.assert_allclose(np.asarray(low @ up), a[np.asarray(perm)],
                                   rtol=1e-4, atol=1e-3 * n)


# --------------------------------------------------------------------------
# batched direct solves (acceptance: match jnp.linalg.solve to 1e-5)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("method,spd", [("lu", False), ("cholesky", True)])
def test_batched_direct_parity(method, spd):
    B, n = 4, 64
    a, b = _batch(B, n, spd=spd)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method=method,
                  block_size=32)
    want = np.asarray(jnp.linalg.solve(jnp.asarray(a),
                                       jnp.asarray(b)[..., None]))[..., 0]
    np.testing.assert_allclose(np.asarray(x), want, atol=1e-5)


def test_batched_direct_pallas_backend():
    B, n = 2, 64
    a, b = _batch(B, n)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  block_size=32, backend="pallas")
    want = np.asarray(jnp.linalg.solve(jnp.asarray(a),
                                       jnp.asarray(b)[..., None]))[..., 0]
    np.testing.assert_allclose(np.asarray(x), want, atol=1e-5)


def test_batched_direct_return_info():
    B, n = 3, 48
    a, b = _batch(B, n)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  block_size=16, return_info=True)
    assert r.iterations.shape == (B,)
    assert bool(jnp.all(r.converged))
    assert r.x.shape == (B, n)


def test_batched_factorize_reuse():
    B, n = 3, 48
    a, _ = _batch(B, n, spd=True)
    solver = api.factorize(jnp.asarray(a), method="cholesky", block_size=16)
    rng = np.random.default_rng(7)
    for _ in range(2):
        b = rng.standard_normal((B, n)).astype(np.float32)
        x = solver(jnp.asarray(b))
        want = np.asarray(jnp.linalg.solve(jnp.asarray(a),
                                           jnp.asarray(b)[..., None]))[..., 0]
        np.testing.assert_allclose(np.asarray(x), want, atol=1e-5)


# --------------------------------------------------------------------------
# padding policy (one rule for lu/cholesky/triangular)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,bs", [(100, 32), (65, 16), (7, 4)])
def test_lu_pad_or_raise_pads(n, bs):
    a, b = _system(n)
    x = lu.solve(jnp.asarray(a), jnp.asarray(b), block_size=bs)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("n,bs", [(100, 32), (65, 16)])
def test_cholesky_pad(n, bs):
    a, b = _system(n, spd=True)
    x = cholesky.solve(jnp.asarray(a), jnp.asarray(b), block_size=bs)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)


def test_triangular_pad_and_message():
    n = 90
    rng = np.random.default_rng(3)
    t = np.tril(rng.standard_normal((n, n))).astype(np.float32) \
        + 4 * np.eye(n, dtype=np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    y = triangular.solve_lower_blocked(jnp.asarray(t), jnp.asarray(b),
                                       block_size=32)
    np.testing.assert_allclose(np.asarray(y), np.linalg.solve(t, b),
                               rtol=1e-4, atol=1e-4)
    x = triangular.solve_upper_blocked(jnp.asarray(t.T), jnp.asarray(b),
                                       block_size=32)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(t.T, b),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="block_size"):
        blocking.choose_block(n, 0)


def test_factor_state_spans_pad():
    """lu_solve/cholesky_solve accept the original-length rhs against a
    padded factor and slice the pad rows away."""
    n, bs = 70, 32
    a, b = _system(n)
    state = lu.lu_factor(jnp.asarray(a), block_size=bs)
    assert state[0].shape[0] == blocking.padded_size(n, bs)
    x = lu.lu_apply(state, jnp.asarray(b), block_size=bs)
    assert x.shape == (n,)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------------
# api surface: validation + registry
# --------------------------------------------------------------------------

def test_direct_rejects_bad_backend_and_engine():
    a, b = _system(32)
    with pytest.raises(ValueError, match="backend"):
        api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  backend="cuda")
    # direct + engine='spmd' is now a real path — but it needs a mesh
    with pytest.raises(ValueError, match="requires a mesh"):
        api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  engine="spmd")
    with pytest.raises(ValueError, match="backend"):
        api.factorize(jnp.asarray(a), method="lu", backend="cuda")


def test_factorize_rejects_iterative_methods():
    a, _ = _system(32)
    with pytest.raises(ValueError, match="direct"):
        api.factorize(jnp.asarray(a), method="cg")


def test_register_direct_requires_factor_apply_pair():
    with pytest.raises(ValueError, match="factor"):
        api.register_method("bad_direct", lambda a, b: b, kind="direct",
                            factor=lambda a: (a,))
    api._REGISTRY.pop("bad_direct", None)


def test_direct_multi_rhs():
    n = 64
    a, _ = _system(n)
    rng = np.random.default_rng(5)
    b = rng.standard_normal((n, 3)).astype(np.float32)
    x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu", block_size=16)
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)


def test_batched_multi_rhs_return_info():
    B, n, k = 2, 32, 3
    a, _ = _batch(B, n)
    rng = np.random.default_rng(6)
    b = rng.standard_normal((B, n, k)).astype(np.float32)
    r = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                  block_size=16, return_info=True)
    assert r.x.shape == (B, n, k)
    assert r.residual.shape == (B,)
    assert bool(jnp.all(r.converged))
    np.testing.assert_allclose(np.asarray(r.x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)


def test_legacy_direct_registration_without_split():
    """kind='direct' with only fn still solves (and rejects what it can't)."""
    api.register_method("legacy_direct",
                        lambda a, b, *, block_size, mesh: lu.solve(
                            a, b, block_size=block_size, mesh=mesh),
                        kind="direct")
    try:
        n = 32
        a, b = _system(n)
        x = api.solve(jnp.asarray(a), jnp.asarray(b),
                      method="legacy_direct", block_size=16)
        np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                                   rtol=1e-3, atol=1e-4)
        with pytest.raises(ValueError, match="factor/apply"):
            api.solve(jnp.asarray(a), jnp.asarray(b), method="legacy_direct",
                      backend="pallas")
        ab, bb = _batch(2, n)
        with pytest.raises(ValueError, match="factor/apply"):
            api.solve(jnp.asarray(ab), jnp.asarray(bb),
                      method="legacy_direct")
    finally:
        api._REGISTRY.pop("legacy_direct", None)


def test_pallas_backend_fp64_keeps_f64_accuracy():
    """Non-f32 dtypes fall back to the exact jnp path (same rule as the
    iterative DenseOperator) instead of silently accumulating in f32."""
    jax.config.update("jax_enable_x64", True)
    try:
        n = 64
        a, b = _system(n, dtype=np.float64)
        x = api.solve(jnp.asarray(a), jnp.asarray(b), method="lu",
                      block_size=16, backend="pallas")
        assert x.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                                   rtol=1e-10, atol=1e-10)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_direct_solve_under_jit():
    n = 64
    a, b = _system(n)
    fn = jax.jit(lambda A, B: api.solve(A, B, method="lu", block_size=32,
                                        backend="pallas"))
    x = fn(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(x), np.linalg.solve(a, b),
                               rtol=1e-3, atol=1e-4)
