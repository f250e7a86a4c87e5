"""Plain float32 reference of the Qwen3 decoder (arXiv:2505.09388; the
``config.json`` of Qwen/Qwen3-0.6B): RMSNorm pre-norm blocks, grouped-query
attention with per-head RMSNorm on queries and keys, rotary positions
(rotate-half, base ``rope_theta``), SwiGLU MLP, tied embeddings.

Straight ``jax.numpy`` at ``highest`` matmul precision, one sequence at a
time, no cache, no kernels, no batching; it imports nothing of the
program.  ``int8=True`` is the control, the model one precision step
below the bfloat16 it is served in: every projection, MLP and logit matmul
takes int8 operands (activations per row, weights per output column,
symmetric absmax) and gives a bfloat16 result, and the residual stream is
kept in bfloat16; attention scores and softmax stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def fake_int8(x, axis):
    """Symmetric absmax int8 round trip along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def bf16(x):
    """Round to bfloat16 and back."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def matmul(x, w, int8: bool):
    """x (..., k) @ w (k, n) in float32, or with int8 operands and a
    bfloat16 result."""
    if int8:
        return bf16(fake_int8(x, -1) @ fake_int8(w, 0))
    return x @ w


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, heads, dh), positions 0..S-1, halves rotated."""
    S, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def attention(h, lp, cfg, int8):
    S = h.shape[0]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    a = lp["attn"]
    q = matmul(h, a["wq"]["w"], int8).reshape(S, H, dh)
    k = matmul(h, a["wk"]["w"], int8).reshape(S, Hk, dh)
    v = matmul(h, a["wv"]["w"], int8).reshape(S, Hk, dh)
    q = rope(rms(q, a["q_norm"]["scale"], eps), cfg["rope_theta"])
    k = rope(rms(k, a["k_norm"]["scale"], eps), cfg["rope_theta"])
    k = jnp.repeat(k, H // Hk, axis=1)            # query head h reads h // G
    v = jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return matmul(o.reshape(S, H * dh), a["wo"]["w"], int8)


def block(x, lp, cfg, int8):
    eps = cfg["rms_norm_eps"]
    keep = bf16 if int8 else (lambda t: t)
    x = keep(x + attention(rms(x, lp["attn_norm"]["scale"], eps), lp, cfg,
                           int8))
    h = rms(x, lp["mlp_norm"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(matmul(h, m["gate"]["w"], int8))
    return keep(x + matmul(g * matmul(h, m["up"]["w"], int8), m["down"]["w"],
                           int8))


@functools.partial(jax.jit, static_argnames=("cfg_items", "int8"))
def _logits_at(params, tokens, rows, cfg_items, int8):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = f32["embed"]["table"][tokens]
        x, _ = jax.lax.scan(
            lambda h, lp: (block(h, lp, cfg, int8), None), x, f32["layers"])
        x = rms(x[rows], f32["final_norm"]["scale"], cfg["rms_norm_eps"])
        return matmul(x, f32["embed"]["table"].T, int8)


def logits_at(params, cfg: dict, tokens, rows, *, int8: bool = False):
    """Logits (len(rows), vocab) at the given rows of one sequence.

    ``cfg`` holds the Hugging Face keys of the configuration file;
    ``tokens`` is padded on the right to a fixed length so one compiled
    program serves every request (the causal mask keeps padding out of
    every row before it)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return _logits_at(params, tokens, rows,
                      tuple((k, cfg[k]) for k in keys), int8)
