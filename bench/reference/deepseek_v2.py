"""Plain float32 reference of the DeepSeek-V2 decoder (arXiv:2405.04434;
the ``config.json`` and ``modeling_deepseek.py`` of
deepseek-ai/DeepSeek-V2-Lite), at one chip's share of an expert-parallel
deployment.

* RMSNorm pre-norm blocks; embeddings and an untied output head.
* Multi-head latent attention, not absorbed, as published: q from
  ``q_proj`` (no ``q_lora``); ``kv_a_proj_with_mqa`` gives a latent of
  ``kv_lora_rank`` and a rope key of ``qk_rope_head_dim``; the latent goes
  through ``kv_a_layernorm`` and ``kv_b_proj`` to each head's k_nope and v;
  the rope key is shared by every head.  RoPE with YaRN (``rope_scaling``):
  frequencies blended by a linear ramp over the correction range, cos and
  sin scaled by mscale(mscale) / mscale(mscale_all_dim), the softmax scale
  ``q_head_dim ** -0.5 * mscale(mscale_all_dim) ** 2``; the rotary part is
  de-interleaved ((x0, x1), (x2, x3), ... pairs to halves) and then
  rotated by halves, as ``apply_rotary_pos_emb`` does.
* The first ``first_k_dense_replace`` layers a SwiGLU MLP; the rest
  DeepSeekMoE: a float32 softmax over every expert's logit (the router's
  published width), greedy top-k, the gates renormalised only if
  ``norm_topk_prob``, times ``routed_scaling_factor``; the shared experts'
  SwiGLU always.  Of the routed experts only those this chip holds (ids
  ``expert_parallel.expert_offset`` on, as many as ``n_routed_experts``)
  add their share; each is computed for every token and weighted by the
  token's gate for it (zero where it was not chosen).

Straight ``jax.numpy`` at ``highest`` matmul precision, one sequence at a
time, no cache, no kernels, no batching; it imports nothing of the
program.  One layer's weights are cast to float32 at a time (a float32
copy of them all would not fit beside the engine) and attention runs in
blocks of queries.  ``int8=True`` is the control, the model one precision
step below the bfloat16 it is served in: every projection, expert and
logit matmul takes int8 operands (activations per row, weights per output
column, symmetric absmax) and gives a bfloat16 result, and the residual
stream is kept in bfloat16; attention scores, softmax and the router stay
float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


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


def latent_norm(c, scale, eps):
    """``kv_a_layernorm``: RMSNorm of the compressed latent."""
    return rms(c, scale, eps)


def swiglu(h, m, int8):
    g = jax.nn.silu(matmul(h, m["gate"]["w"], int8))
    return matmul(g * matmul(h, m["up"]["w"], int8), m["down"]["w"], int8)


# ---------------------------------------------------------------------------
# YaRN rotary embedding
# ---------------------------------------------------------------------------

def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(dim, base, rs):
    """(inv_freq (dim/2,), cos/sin scale) of ``DeepseekV2YarnRotary-
    Embedding`` for ``rope_scaling`` ``rs``."""
    f = rs["factor"]
    pos = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    freq_extra = 1.0 / base ** pos
    freq_inter = 1.0 / (f * base ** pos)
    orig = rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = freq_inter * (1 - mask) + freq_extra * mask
    return inv, _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope(x, cfg):
    """x (S, heads, d), positions 0..S-1: de-interleaved, then rotated by
    halves with YaRN's frequencies and scale."""
    S, heads, d = x.shape
    inv, m = yarn(d, cfg["rope_theta"], cfg["rope_scaling"])
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[:, None]          # (S, 1, d)
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    x = x.reshape(S, heads, d // 2, 2).swapaxes(-1, -2).reshape(S, heads, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def attention(h, a, cfg, int8):
    S = h.shape[0]
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = matmul(h, a["wq"]["w"], int8).reshape(S, H, nope + rd)
    ckv = matmul(h, a["wkv_a"]["w"], int8)
    c = latent_norm(ckv[:, :r], a["kv_norm"]["scale"], eps)
    kv = matmul(c, a["wkv_b"]["w"], int8).reshape(S, H, nope + vd)
    k_pe = rope(ckv[:, None, r:r + rd], cfg)                # (S, 1, rd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rd))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(cfg)
    nb = S // QUERY_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        causal = qpos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * vd)
    return matmul(o, a["wo"]["w"], int8)


def topk_gates(probs, k, norm):
    """Greedy top-k of the router's probabilities: (gates, ids), the gates
    renormalised over the k only when ``norm`` (``norm_topk_prob``)."""
    gates, ids = jax.lax.top_k(probs, k)
    if norm:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates, ids


def moe(h, m, cfg, int8):
    """This chip's routed experts' share, plus the shared experts."""
    probs = jax.nn.softmax(h @ m["router"]["w"], -1)       # float32 router
    gates, ids = topk_gates(probs, cfg["num_experts_per_tok"],
                            cfg["norm_topk_prob"])
    gates = gates * cfg["routed_scaling_factor"]
    held = m["gate_proj"].shape[0]
    y = swiglu(h, m["shared"], int8)
    for e in range(held):
        w = jnp.sum(jnp.where(ids == cfg["expert_offset"] + e, gates, 0.0),
                    -1)
        g = jax.nn.silu(matmul(h, m["gate_proj"][e], int8))
        out = matmul(g * matmul(h, m["up_proj"][e], int8),
                     m["down_proj"][e], int8)
        y = y + w[:, None] * out
    return y


def block(x, lp, cfg, int8):
    eps = cfg["rms_norm_eps"]
    keep = bf16 if int8 else (lambda t: t)
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)  # this layer only
    x = keep(x + attention(rms(x, lp["attn_norm"]["scale"], eps),
                           lp["attn"], cfg, int8))
    if "mlp" in lp:
        return keep(x + swiglu(rms(x, lp["mlp_norm"]["scale"], eps),
                               lp["mlp"], int8))
    return keep(x + moe(rms(x, lp["moe_norm"]["scale"], eps), lp["moe"],
                        cfg, int8))


@functools.partial(jax.jit, static_argnames=("cfg_items", "int8"))
def _logits_at(params, tokens, rows, cfg_items, int8):
    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens].astype(jnp.float32)
        for stack in ("dense_layers", "layers"):
            if stack in params:
                x, _ = jax.lax.scan(
                    lambda h, lp: (block(h, lp, cfg, int8), None), x,
                    params[stack])
        x = rms(x[rows], params["final_norm"]["scale"].astype(jnp.float32),
                cfg["rms_norm_eps"])
        return matmul(x, params["lm_head"]["w"].astype(jnp.float32), int8)


KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")


def logits_at(params, cfg: dict, tokens, rows, *, int8: bool = False):
    """Logits (len(rows), vocab) at the given rows of one sequence.

    ``cfg`` holds the Hugging Face keys of the configuration file;
    ``tokens`` is padded on the right to a multiple of ``QUERY_BLOCK`` so
    one compiled program serves every request (the causal mask keeps
    padding out of every row before it)."""
    items = tuple((k, cfg[k]) for k in KEYS)
    items += (("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),
              ("expert_offset", cfg["expert_parallel"]["expert_offset"]))
    S = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -S % QUERY_BLOCK))
    return _logits_at(params, tokens, rows, items, int8)
