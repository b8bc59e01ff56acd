"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gemm, krylov_fused, ref, trsm


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 128, 64),
                                   (128, 256, 256), (512, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_sweep(m, n, k, dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    a = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(k2, (k, n), jnp.float32).astype(dtype)
    got = gemm.matmul(a, b, bm=128, bn=128, bk=64, interpret=True)
    want = ref.matmul(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * k)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 256), (32, 128)])
def test_gemm_block_shapes(blocks):
    bm, bk = blocks
    a = jax.random.normal(jax.random.key(1), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.key(2), (256, 256), jnp.float32)
    got = gemm.matmul(a, b, bm=bm, bn=128, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul(a, b)),
                               rtol=1e-4, atol=0.05)


@pytest.mark.parametrize("n,m,sb,bc", [(128, 128, 32, 64), (256, 128, 64, 128),
                                       (128, 256, 128, 128)])
@pytest.mark.parametrize("unit", [False, True])
def test_trsm_sweep(n, m, sb, bc, unit):
    k1, k2 = jax.random.split(jax.random.key(3))
    l = jnp.tril(jax.random.normal(k1, (n, n), jnp.float32) * 0.1) \
        + 2.0 * jnp.eye(n)
    b = jax.random.normal(k2, (n, m), jnp.float32)
    got = trsm.trsm_lower(l, b, unit_diagonal=unit, sb=sb, bc=bc,
                          interpret=True)
    want = ref.trsm_lower(l, b, unit_diagonal=unit)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n", [1024, 4096, 128 * 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_cg_update_sweep(n, dtype):
    ks = jax.random.split(jax.random.key(7), 4)
    x, r, p, ap = (jax.random.normal(k, (n,), jnp.float32).astype(dtype)
                   for k in ks)
    got = krylov_fused.fused_cg_update(x, r, p, ap, 0.37, block_rows=2,
                                       interpret=True)
    want = ref.fused_cg_update(x, r, p, ap, 0.37)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)
    np.testing.assert_allclose(float(got[2]), float(want[2]),
                               rtol=max(tol, 1e-4) * 10)


@pytest.mark.parametrize("n", [1024, 128 * 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_pipelined_dots_sweep(n, dtype):
    ks = jax.random.split(jax.random.key(8), 3)
    r, u, w = (jax.random.normal(k, (n,), jnp.float32).astype(dtype)
               for k in ks)
    got = krylov_fused.fused_pipelined_dots(r, u, w, block_rows=2,
                                            interpret=True)
    want = ref.fused_pipelined_dots(r, u, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for g, v in zip(got, want):
        np.testing.assert_allclose(float(g), float(v), rtol=tol,
                                   atol=tol * n)


def test_fused_auto_padding_matches_ref():
    """Auto-padded dispatch (n not a lane multiple) is exact."""
    n = 130
    ks = jax.random.split(jax.random.key(9), 4)
    x, r, p, ap = (jax.random.normal(k, (n,), jnp.float32) for k in ks)
    got = krylov_fused.fused_cg_update_auto(x, r, p, ap, 0.41)
    want = ref.fused_cg_update(x, r, p, ap, 0.41)
    for g, v in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_allclose(float(got[2]), float(want[2]), rtol=1e-4)
    got_d = krylov_fused.fused_pipelined_dots_auto(x, r, p)
    want_d = ref.fused_pipelined_dots(x, r, p)
    for g, v in zip(got_d, want_d):
        np.testing.assert_allclose(float(g), float(v), rtol=1e-4)


def test_gemm_rejects_untiled():
    a = jnp.zeros((100, 128))
    b = jnp.zeros((128, 128))
    with pytest.raises(ValueError):
        gemm.matmul(a, b, bm=64, bn=64, bk=64, interpret=True)


@pytest.mark.parametrize("n,m,sb", [(128, 128, 32), (256, 64, 64)])
def test_trsm_upper_sweep(n, m, sb):
    k1, k2 = jax.random.split(jax.random.key(10))
    u = jnp.triu(jax.random.normal(k1, (n, n), jnp.float32) * 0.1) \
        + 2.0 * jnp.eye(n)
    b = jax.random.normal(k2, (n, m), jnp.float32)
    got = trsm.trsm_upper(u, b, sb=sb, bc=64, interpret=True)
    want = jax.scipy.linalg.solve_triangular(u, b, lower=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,m", [(100, 1), (130, 7)])
def test_trsm_auto_padding(n, m):
    """Arbitrary (n, m) via the identity/zero pad wrappers (exact)."""
    k1, k2 = jax.random.split(jax.random.key(11))
    l = jnp.tril(jax.random.normal(k1, (n, n), jnp.float32) * 0.1) \
        + 2.0 * jnp.eye(n)
    b = jax.random.normal(k2, (n, m), jnp.float32)
    b = b[:, 0] if m == 1 else b
    got = trsm.trsm_lower_auto(l, b, sb=32)
    want = jax.scipy.linalg.solve_triangular(l, b, lower=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    got_u = trsm.trsm_upper_auto(l.T, b, sb=32)
    want_u = jax.scipy.linalg.solve_triangular(l.T, b, lower=False)
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,nb,k", [(128, 32, 0), (128, 32, 64),
                                    (128, 32, 96), (256, 64, 64)])
def test_lu_panel_update_kernel(n, nb, k):
    """Fused TRSM + rank-nb GEMM step vs the straightforward oracle."""
    from repro.kernels import factor_fused
    rng = np.random.default_rng(12)
    a = rng.standard_normal((n, n)).astype(np.float32)
    l11 = np.tril(rng.standard_normal((nb, nb)), -1).astype(np.float32) \
        + np.eye(nb, dtype=np.float32)
    a[k:k + nb, k:k + nb] = l11 + np.triu(a[k:k + nb, k:k + nb])
    linv = np.linalg.inv(l11).astype(np.float32)

    want = a.copy()
    u12 = linv @ a[k:k + nb, k + nb:]
    want[k:k + nb, k + nb:] = u12
    want[k + nb:, k + nb:] -= a[k + nb:, k:k + nb] @ u12

    got = factor_fused.lu_panel_update(jnp.asarray(a), jnp.asarray(linv),
                                       k, nb=nb, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("n,nb,k", [(128, 32, 0), (128, 32, 64),
                                    (128, 32, 96)])
def test_cholesky_panel_update_kernel(n, nb, k):
    from repro.kernels import factor_fused
    rng = np.random.default_rng(13)
    a = rng.standard_normal((n, n)).astype(np.float32)
    a = (a @ a.T / n + 4 * np.eye(n)).astype(np.float32)
    lkk = np.linalg.cholesky(a[k:k + nb, k:k + nb]).astype(np.float32)
    a[k:k + nb, k:k + nb] = lkk
    linv = np.linalg.inv(lkk).astype(np.float32)

    want = a.copy()
    l21 = a[k + nb:, k:k + nb] @ linv.T
    want[k + nb:, k:k + nb] = l21
    want[k + nb:, k + nb:] -= l21 @ l21.T

    got = factor_fused.cholesky_panel_update(jnp.asarray(a),
                                             jnp.asarray(linv), k, nb=nb,
                                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-3)
