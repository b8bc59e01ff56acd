"""Second-order step via the paper's Krylov suite (solver-in-the-optimizer).

The CUPLSS solvers are model-agnostic, matrix-free Krylov drivers — the
natural place they appear inside a training loop is solving the damped
Gauss-Newton/Hessian system

    (H + λ I) d = g

with Hessian-vector products (``jax.jvp`` over ``jax.grad``) as the matvec.
This is the paper's CG applied verbatim; it demonstrates the library
composing with a model written in plain ``jax.numpy`` (see
examples/cg_newton.py and tests/test_second_order.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import krylov


def _tree_to_vec(tree):
    leaves, tdef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [l.size for l in leaves]
    vec = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
    return vec, (tdef, shapes, sizes)


def _vec_to_tree(vec, meta, like):
    tdef, shapes, sizes = meta
    out, off = [], 0
    for shape, size, ref in zip(shapes, sizes, jax.tree.leaves(like)):
        out.append(vec[off:off + size].reshape(shape).astype(ref.dtype))
        off += size
    return jax.tree.unflatten(tdef, out)


def cg_newton_step(loss_fn: Callable, params, batch, *, damping: float = 1e-3,
                   cg_tol: float = 1e-4, cg_iters: int = 20,
                   lr: float = 1.0, backtrack: int = 4):
    """One damped-Newton step: solve (H + λI) d = ∇L with the library CG,
    then backtracking line search along d (training losses are non-convex; an
    indefinite H can make the raw CG direction an ascent direction).

    Returns (new_params, aux) with aux = {loss, cg_iters, residual, lr}.
    """
    # NOTE: run this with float32 parameters and activations —
    # bf16 Hessian-vector products are quantization noise and destroy CG's
    # conjugacy (see tests/test_second_order.py).
    loss, g_tree = jax.value_and_grad(loss_fn)(params, batch)
    g_vec, meta = _tree_to_vec(g_tree)

    def hvp(v_vec):
        v_tree = _vec_to_tree(v_vec, meta, params)
        hv = jax.jvp(lambda p: jax.grad(loss_fn)(p, batch), (params,),
                     (v_tree,))[1]
        hv_vec, _ = _tree_to_vec(hv)
        return hv_vec + damping * v_vec

    result = krylov.cg(hvp, g_vec, tol=cg_tol, maxiter=cg_iters)
    d_vec = result.x
    # descent guard: on an indefinite Hessian truncated CG may return an
    # ascent direction — fall back to the gradient, and cap the step norm
    # (a cheap trust region) so backtracking starts from a sane scale
    gd = jnp.vdot(g_vec, d_vec)
    d_vec = jnp.where(gd > 0, d_vec, g_vec)
    gnorm = jnp.linalg.norm(g_vec)
    dnorm = jnp.linalg.norm(d_vec)
    d_vec = d_vec * jnp.minimum(1.0, 10.0 * gnorm / jnp.maximum(dnorm, 1e-30))
    d_tree = _vec_to_tree(d_vec, meta, params)

    def at(step_size):
        return jax.tree.map(
            lambda p, d: (p.astype(jnp.float32)
                          - step_size * d.astype(jnp.float32)
                          ).astype(p.dtype), params, d_tree)

    new_params, used_lr = params, 0.0
    cur = float(loss_fn(params, batch))   # re-eval at the *stored* dtype
    for k in range(backtrack + 1):
        cand_lr = lr * (0.5 ** k)
        cand = at(cand_lr)
        cand_loss = float(loss_fn(cand, batch))
        if cand_loss < cur:
            new_params, used_lr = cand, cand_lr
            break
    return new_params, {"loss": loss, "cg_iters": result.iterations,
                        "residual": result.residual, "lr": used_lr}
