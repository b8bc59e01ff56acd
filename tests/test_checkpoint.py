"""Checkpointing: atomic commit, async save, bf16 round-trip, retention,
restart determinism of a checkpointed solve."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _state(step=0):
    return {
        "params": {"w": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.float32) * step},
        "step": jnp.asarray(step, jnp.int32),
    }


def test_roundtrip_bf16(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    s = _state(5)
    mgr.save(5, s, blocking=True)
    restored, step = mgr.restore(jax.eval_shape(lambda: s))
    assert step == 5
    assert restored["params"]["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"],
                                             np.float32),
                                  np.asarray(s["params"]["w"], np.float32))


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_atomicity_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1), blocking=True)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(s), blocking=True)
    assert mgr.all_steps() == [3, 4]


def test_restore_specific_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    for s in (1, 2, 3):
        mgr.save(s, _state(s), blocking=True)
    restored, step = mgr.restore(jax.eval_shape(lambda: _state()), step=2)
    assert step == 2
    assert float(restored["params"]["b"][0]) == 2.0


def test_structure_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1), blocking=True)
    bad = {"params": {"w": jnp.zeros((3, 4), jnp.bfloat16)}}
    with pytest.raises(ValueError):
        mgr.restore(jax.eval_shape(lambda: bad))


def test_restart_determinism(tmp_path):
    """A checkpointed CG solve stopped after two chunks and resumed from
    its directory ends bitwise equal to one run straight through."""
    from repro.resilience import runner

    rng = np.random.default_rng(0)
    m = rng.standard_normal((64, 64)).astype(np.float32)
    a = jnp.asarray(m @ m.T / 64 + 4 * np.eye(64, dtype=np.float32))
    b = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    kw = dict(method="cg", tol=1e-6, every=4)

    straight = runner.checkpointed_solve(
        a, b, directory=str(tmp_path / "straight"), maxiter=200, **kw)
    stopped = runner.checkpointed_solve(
        a, b, directory=str(tmp_path / "resumed"), maxiter=8, **kw)
    assert int(stopped.iterations) == 8 and not bool(stopped.converged)
    resumed = runner.checkpointed_solve(
        a, b, directory=str(tmp_path / "resumed"), maxiter=200, **kw)

    assert resumed.info["resumed_from"] >= 8
    assert bool(straight.converged) and bool(resumed.converged)
    assert int(resumed.iterations) == int(straight.iterations)
    np.testing.assert_array_equal(np.asarray(resumed.x),
                                  np.asarray(straight.x))
