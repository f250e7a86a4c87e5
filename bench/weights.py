"""Random weights made on the device from ``--seed``, in one jitted call,
in the dtype they are served in and in the parameter layout the program
reads.  The plain references rebuild the same values from the same seed
with this module; neither side takes weights the other has made.
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


def _layers(key, cfg, dtype):
    L, d, f = cfg.num_layers, cfg.d_model, cfg.d_ff
    dh = cfg.resolved_head_dim()
    q, kv = cfg.num_heads * dh, cfg.num_kv_heads * dh
    k = jax.random.split(key, 11)
    return {
        "attn_norm": {"scale": _scale(k[0], (L, d))},
        "attn": {"wq": {"w": _dense(k[1], (L, d, q), dtype)},
                 "wk": {"w": _dense(k[2], (L, d, kv), dtype)},
                 "wv": {"w": _dense(k[3], (L, d, kv), dtype)},
                 "wo": {"w": _dense(k[4], (L, q, d), dtype)},
                 "q_norm": {"scale": _scale(k[5], (L, dh))},
                 "k_norm": {"scale": _scale(k[6], (L, dh))}},
        "mlp_norm": {"scale": _scale(k[7], (L, d))},
        "mlp": {"gate": {"w": _dense(k[8], (L, d, f), dtype)},
                "up": {"w": _dense(k[9], (L, d, f), dtype)},
                "down": {"w": _dense(k[10], (L, f, d), dtype)}},
    }


def lm_params(cfg, seed: int, dtype=None):
    """Decoder LM with tied embeddings: ``embed``, ``layers``,
    ``final_norm``."""
    dtype = jnp.dtype(dtype or cfg.param_dtype)

    @jax.jit
    def make(key):
        ke, kl, kn = jax.random.split(key, 3)
        return {"embed": {"table": (0.02 * jax.random.normal(
                    ke, (cfg.vocab_size, cfg.d_model))).astype(dtype)},
                "layers": _layers(kl, cfg, dtype),
                "final_norm": {"scale": _scale(kn, (cfg.d_model,))}}

    return make(seed_key(seed))
