"""Parallel BLAS on the (1,1) mesh (communication-free degenerate case —
the multi-device cases run in the selftest battery)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pblas


def test_pmatvec_spmd(mesh1, rng):
    a = rng.standard_normal((64, 64)).astype(np.float32)
    x = rng.standard_normal(64).astype(np.float32)
    y = pblas.pmatvec_spmd(jnp.asarray(a), jnp.asarray(x), mesh1)
    np.testing.assert_allclose(np.asarray(y), a @ x, rtol=1e-5, atol=1e-4)


def test_pmatvec_t_spmd(mesh1, rng):
    a = rng.standard_normal((64, 64)).astype(np.float32)
    x = rng.standard_normal(64).astype(np.float32)
    y = pblas.pmatvec_t_spmd(jnp.asarray(a), jnp.asarray(x), mesh1)
    np.testing.assert_allclose(np.asarray(y), a.T @ x, rtol=1e-5, atol=1e-4)


def test_pdot_pnorm_paxpy(mesh1, rng):
    x = rng.standard_normal(128).astype(np.float32)
    y = rng.standard_normal(128).astype(np.float32)
    assert float(pblas.pdot_spmd(jnp.asarray(x), jnp.asarray(y), mesh1)) \
        == pytest.approx(float(x @ y), rel=1e-5)
    assert float(pblas.pnorm_spmd(jnp.asarray(x), mesh1)) \
        == pytest.approx(float(np.linalg.norm(x)), rel=1e-5)
    z = pblas.paxpy_spmd(2.5, jnp.asarray(x), jnp.asarray(y), mesh1)
    np.testing.assert_allclose(np.asarray(z), 2.5 * x + y, rtol=1e-5)


def test_pgemm_summa(mesh1, rng):
    a = rng.standard_normal((32, 32)).astype(np.float32)
    b = rng.standard_normal((32, 32)).astype(np.float32)
    c = pblas.pgemm_summa(jnp.asarray(a), jnp.asarray(b), mesh1)
    np.testing.assert_allclose(np.asarray(c), a @ b, rtol=1e-4, atol=1e-4)


def test_gspmd_engine(mesh1, rng):
    a = rng.standard_normal((32, 32)).astype(np.float32)
    x = rng.standard_normal(32).astype(np.float32)
    y = pblas.pmatvec_gspmd(jnp.asarray(a), jnp.asarray(x), mesh1)
    np.testing.assert_allclose(np.asarray(y), a @ x, rtol=1e-5, atol=1e-4)


def test_collective_counts_kind_complete(mesh1):
    """The tally dict is kind-complete (every wrapper pre-seeded at 0)
    and the ppermute/all_to_all wrappers both tally and compute."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import jax

    x = jnp.arange(8, dtype=jnp.float32)

    def body(xl):
        y = pblas.ppermute(xl, "data", [(0, 0)])
        z = pblas.all_to_all(y[None, :], "model", 0, 0)
        return z[0]

    with pblas.collective_counts() as c:
        out = jax.jit(shard_map(
            body, mesh=mesh1, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))(x)
    assert set(c) == set(pblas.KINDS)
    assert c["ppermute"] == 1 and c["all_to_all"] == 1
    np.testing.assert_allclose(np.asarray(out), np.arange(8), rtol=1e-6)
