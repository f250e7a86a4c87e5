"""What every architecture's ``params`` (``bench/models/<model_type>.py``)
draws its random weights with: the key from ``--seed`` and the draws of a
weight matrix and of a norm scale.  Each makes its weights on the device,
in one jitted call, in the dtype they are served in and in the parameter
layout the program reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (also past 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _dense(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * shape[-2] ** -0.5).astype(dtype)


def _scale(key, shape):
    """Norm scales near 1, drawn so a norm that ignores them shows."""
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
