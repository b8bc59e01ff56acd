"""Int8 gradient compression: quantization bounds + error feedback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import compression as C
from repro.launch import mesh as mesh_lib


@pytest.mark.parametrize("n", [128, 1000, 4096])
def test_quantize_roundtrip_bound(n):
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n).astype(np.float32) * 10
    q, s, m = C.quantize_int8(jnp.asarray(x))
    back = np.asarray(C.dequantize_int8(q, s, m, (n,)))
    # error per element ≤ half a quant step of its block scale
    blocks = np.resize(x, (-(-n // C.BLOCK), C.BLOCK))
    step = np.abs(blocks).max(1) / 127
    bound = np.repeat(step, C.BLOCK)[:n] * 0.51
    assert (np.abs(back - x) <= bound + 1e-7).all()


def test_quantize_zero_block():
    x = jnp.zeros((256,))
    q, s, n = C.quantize_int8(x)
    back = C.dequantize_int8(q, s, n, (256,))
    np.testing.assert_array_equal(np.asarray(back), 0.0)


def test_error_feedback_unbiased_over_time():
    """With EF, the *cumulative* applied signal tracks the cumulative true
    gradient (bias does not accumulate) — the property that preserves
    convergence under compression."""
    rng = np.random.default_rng(1)
    g_true = rng.standard_normal(512).astype(np.float32) * 1e-3  # tiny grads
    ef = jnp.zeros(512)
    applied = np.zeros(512)
    for t in range(50):
        val, ef = C._roundtrip_with_ef(jnp.asarray(g_true), ef)
        applied += np.asarray(val)
    # without EF, int8 on tiny values with shared block scale can round to
    # zero forever; with EF the mean applied value converges to g_true
    err = np.abs(applied / 50 - g_true).max() / np.abs(g_true).max()
    assert err < 0.05, err


def test_ring_allreduce_single_device():
    """axis size 1 → identity (no hops)."""
    mesh = mesh_lib.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    x = jnp.arange(256.0)
    f = shard_map(lambda v: C.ring_allreduce_int8(v, "data"),
                  mesh=mesh, in_specs=(P(),), out_specs=P(),
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))
