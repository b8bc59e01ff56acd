"""Second-order fitting example: the paper's CG solver drives a
damped-Newton step on a small two-layer MLP written in plain ``jax.numpy``
(solver-in-the-optimizer integration).

    PYTHONPATH=src python examples/cg_newton.py
"""
import jax
import jax.numpy as jnp

from repro.optim.second_order import cg_newton_step

# float32 throughout: bf16 Hessian-vector products are too noisy for CG
kx, kt, k1, k2 = jax.random.split(jax.random.key(0), 4)
x = jax.random.normal(kx, (256, 8))
y = jnp.sin(x @ jax.random.normal(kt, (8, 1)))          # smooth target
params = {"w1": jax.random.normal(k1, (8, 32)) / jnp.sqrt(8.0),
          "b1": jnp.zeros(32),
          "w2": jax.random.normal(k2, (32, 1)) / jnp.sqrt(32.0),
          "b2": jnp.zeros(1)}
batch = (x, y)


def loss_fn(p, b):
    xb, yb = b
    h = jnp.tanh(xb @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] + p["b2"] - yb) ** 2)


print(f"initial loss: {float(loss_fn(params, batch)):.4f}")
for it in range(5):
    params, aux = cg_newton_step(loss_fn, params, batch, damping=1e-2,
                                 cg_iters=20, lr=1.0)
    print(f"newton iter {it}: loss {float(aux['loss']):.4f} "
          f"(cg iters {int(aux['cg_iters'])}, "
          f"residual {float(aux['residual']):.2e})")
print(f"final loss: {float(loss_fn(params, batch)):.4f}")
