"""Hypothesis property-based tests on the system's invariants."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import lu as lu_mod

_settings = settings(max_examples=25, deadline=None)


# --------------------------------------------------------------------------
# LU: PA = LU for arbitrary well-conditioned matrices and block sizes
# --------------------------------------------------------------------------

@_settings
@given(n_blocks=st.integers(1, 4), bs=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 10_000))
def test_lu_factorization_property(n_blocks, bs, seed):
    n = n_blocks * bs
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32) + n * np.eye(
        n, dtype=np.float32)
    packed, perm = lu_mod.lu_factor(jnp.asarray(a), block_size=bs)
    l, u = lu_mod.unpack(packed)
    np.testing.assert_allclose(np.asarray(l @ u), a[np.asarray(perm)],
                               rtol=1e-3, atol=1e-2)
    # perm is a permutation
    assert sorted(np.asarray(perm).tolist()) == list(range(n))
