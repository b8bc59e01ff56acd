"""Solver-in-the-optimizer: the paper's CG driving a damped-Newton step."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.second_order import cg_newton_step


def test_cg_newton_quadratic_one_shot():
    """On a quadratic, one undamped Newton step lands at the optimum."""
    rng = np.random.default_rng(0)
    m = rng.standard_normal((8, 8)).astype(np.float32)
    h = jnp.asarray(m @ m.T + 8 * np.eye(8, dtype=np.float32))
    opt = jnp.asarray(rng.standard_normal(8).astype(np.float32))

    def loss(params, batch):
        d = params["x"] - opt
        return 0.5 * d @ h @ d

    params = {"x": jnp.zeros(8)}
    new, aux = cg_newton_step(loss, params, None, damping=0.0,
                              cg_tol=1e-10, cg_iters=50)
    np.testing.assert_allclose(np.asarray(new["x"]), np.asarray(opt),
                               atol=1e-4)
    assert float(loss(new, None)) < 1e-8


def test_cg_newton_on_tiny_mlp():
    """A two-layer tanh MLP (non-convex loss) in plain jnp, float32:
    damping + backtracking make the step descend."""
    kx, kt, k1, k2 = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(kx, (32, 8))
    y = jnp.sin(x @ jax.random.normal(kt, (8, 1)))
    params = {"w1": jax.random.normal(k1, (8, 16)) / jnp.sqrt(8.0),
              "b1": jnp.zeros(16),
              "w2": jax.random.normal(k2, (16, 1)) / jnp.sqrt(16.0),
              "b2": jnp.zeros(1)}

    def loss_fn(p, b):
        xb, yb = b
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] + p["b2"] - yb) ** 2)

    batch = (x, y)
    l0 = float(loss_fn(params, batch))
    # damping + backtracking = trust-region-flavored step: must not ascend
    new, aux = cg_newton_step(loss_fn, params, batch, damping=1.0,
                              cg_iters=10, lr=1.0, backtrack=6)
    l1 = float(loss_fn(new, batch))
    assert np.isfinite(l1) and l1 < l0, (l0, l1)
    assert int(aux["cg_iters"]) >= 1
    assert float(aux["lr"]) > 0.0
    assert jax.tree.structure(new) == jax.tree.structure(params)
