"""The benchmark's seeded systems, its checks and HPL's count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_cells  # noqa: F401  (puts the checkout on the path)
from bench import peaks, systems


def test_hpl_count_and_bytes():
    assert systems.hpl_flops(3) == pytest.approx(2 / 3 * 27 + 18)
    n = 28672
    assert systems.hpl_flops(n) == pytest.approx(1.57155e13, rel=1e-4)
    assert systems.hpl_bytes(n) == 4 * (3 * n * n + 4 * n)
    # compute bounds the n = 28672 solve on a v5e: 0.080 s of operations
    p = peaks.peaks("TPU v5 lite")
    assert systems.hpl_flops(n) / p["bf16_flops"] == pytest.approx(
        0.0798, rel=1e-2)
    assert systems.hpl_bytes(n) / p["hbm_bytes_per_s"] < 0.02


def test_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_seeds_above_32_bits_stay_distinct():
    a = systems.key32(2**40)
    assert a == systems.key32(2**40)
    assert len({systems.key32(2**40 + i) for i in range(100)}) == 100
    assert systems.key32(2**40) != systems.key32(0)
    assert 0 <= a < 2**32


@pytest.mark.parametrize("kind", sorted(systems.KINDS))
def test_generators_repeat_for_a_seed(kind):
    make = jax.jit(lambda s: systems.KINDS[kind](jax.random.key(s), 64))
    a1, b1 = make(np.uint32(7))
    a2, b2 = make(np.uint32(7))
    a3, _ = make(np.uint32(8))
    assert np.array_equal(a1, a2) and np.array_equal(b1, b2)
    assert not np.array_equal(a1, a3)
    assert a1.dtype == jnp.float32 and a1.shape == (64, 64)


def test_check_passes_a_solve_and_fails_a_wrong_answer():
    a, b = systems.hpl_system(jax.random.key(3), 128)
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    x = np.linalg.solve(a64, b64).astype(np.float32)
    good = float(systems.hpl_ratio(a, x, b))
    assert good < 1.0
    # the same ratio in float64 on the host
    r = np.abs(a64 @ x - b64).max()
    scale = (np.finfo(np.float32).eps * 128
             * (np.abs(a64).sum(axis=1).max() * np.abs(x).max()
                + np.abs(b64).max()))
    assert good == pytest.approx(r / scale, rel=0.5, abs=0.05)
    bad = x.copy()
    bad[0] += 0.1
    assert float(systems.hpl_ratio(a, bad, b)) > 16
