"""Adam / AdamW from scratch (no optax).

Functional API:
  state = adamw_init(params)
  params, state = adamw_update(params, grads, state, step, lr=..., ...)

Supports masked updates (``mask`` pytree of bools) so the federated client
can train LoRA leaves only while the quantized base stays frozen — the
paper's PEFT setup (C2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def adamw_init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params)}


def _leaf_update(p, g, mu, nu, c1, c2, *, lr, b1, b2, eps, weight_decay):
    """One AdamW leaf update."""
    g32 = g.astype(jnp.float32)
    mu2 = b1 * mu + (1 - b1) * g32
    nu2 = b2 * nu + (1 - b2) * jnp.square(g32)
    mhat = mu2 / c1
    nhat = nu2 / c2
    delta = mhat / (jnp.sqrt(nhat) + eps)
    if weight_decay > 0:
        delta = delta + weight_decay * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), mu2, nu2


def adamw_update(params, grads, state, step, *, lr=1e-3, b1=0.9, b2=0.999,
                 eps=1e-8, weight_decay=0.0, mask=None):
    """step: 1-based int or traced scalar."""
    step = jnp.asarray(step, jnp.float32)
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step

    if mask is None:
        mask = jax.tree.map(lambda _: True, params)
    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    flat_m = jax.tree.leaves(mask)
    out_p, out_mu, out_nu = [], [], []
    for p, g, mu, nu, m in zip(flat_p, flat_g, flat_mu, flat_nu, flat_m):
        if m is False:
            p2, mu2, nu2 = p, mu, nu
        else:
            p2, mu2, nu2 = _leaf_update(p, g, mu, nu, c1, c2, lr=lr, b1=b1,
                                        b2=b2, eps=eps,
                                        weight_decay=weight_decay)
        out_p.append(p2)
        out_mu.append(mu2)
        out_nu.append(nu2)
    return (jax.tree.unflatten(tdef, out_p),
            {"mu": jax.tree.unflatten(tdef, out_mu),
             "nu": jax.tree.unflatten(tdef, out_nu)})


def sgd_update(params, grads, *, lr=1e-2):
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32) -
                      lr * g.astype(jnp.float32)).astype(p.dtype),
        params, grads)
