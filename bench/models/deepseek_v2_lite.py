"""DeepSeek-V2-Lite (arXiv:2405.04434; published ``model_type``
``deepseek_v2``): everything the harness knows of this architecture, found
by ``common.model_module`` from the ``model_type`` of a configuration file,
``deepseek_v2_lite``.  It is the Lite variant of DeepSeek-V2 alone: no
``q_lora``, and greedy top-k over one expert group.  DeepSeek-V2 itself
(``q_lora_rank`` 1,536, group-limited routing) has no module, and
``model_config`` refuses its settings.  A configuration file states the
published ``model_type`` under ``published``.

* ``model_config(data)``: the program's ``ModelConfig`` (the ``moe``
  family with latent attention) from the file's Hugging Face keys.  The
  file's ``n_routed_experts`` is how many experts this chip holds,
  ``expert_parallel.expert_offset`` the first of them, and
  ``published.n_routed_experts`` the router's width;
* ``params(cfg, seed)``: random weights made on the device from the seed,
  in one jitted call, in the dtype they are served in and the layout the
  program reads (the dense first layer as a stack of its own);
* ``logits_at(...)``: the plain float32 reference,
  ``reference/deepseek_v2.py``;
* the model FLOPs and the latent decode's HBM bytes that ``serve.mfu`` and
  ``mla_decode_roofline`` divide.

Counting conventions (model FLOPs, no recomputation counted): a matmul
with a weight of ``n`` parameters costs ``2 n`` FLOPs per token.  Routed
experts count at the expected assignments a token to the held experts,
``top_k x held / router width`` (6 x 8 / 64 = 0.75), whatever the routing
did; the router, the shared experts, the dense layer, attention's
projections and the head count in full.  Attention's projections are
q, kv_a, kv_b and o for prefill (not absorbed) and the same parameter
count for decode (absorbed: kv_b's two halves act on the query and the
output instead of on every cached position).  Prefill attention costs
``2 H (q_head_dim + v_head_dim)`` FLOPs per (query, visible key) pair;
absorbed decode attention ``H (2 (rank + rope) + 2 rank)`` per visible
position and layer (scores over the whole entry, values its latent).
Decode reads each lane's latent entries once per layer at their needed
width, ``rank + rope`` (the stored 128-lane padding is not counted).
"""

from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp

from flops import causal_pairs
from reference import deepseek_v2 as ref
from weights import _dense, _scale, seed_key


def _require(data: dict, key: str, want) -> None:
    if data[key] != want:
        raise ValueError(f"{data['name']}: {key}={data[key]!r} is not "
                         f"supported (the program implements {want!r})")


def model_config(data: dict):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    for key, want in (("q_lora_rank", None), ("scoring_func", "softmax"),
                      ("topk_method", "greedy"), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("routed_scaling_factor", 1), ("hidden_act", "silu"),
                      ("attention_bias", False)):
        _require(data, key, want)
    rs = data["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r} is not yarn")
    H = data["num_attention_heads"]
    return ModelConfig(
        name=data["name"],
        family="moe",
        num_layers=data["num_hidden_layers"],
        d_model=data["hidden_size"],
        num_heads=H,
        num_kv_heads=H,
        head_dim=data["qk_nope_head_dim"] + data["qk_rope_head_dim"],
        d_ff=data["intermediate_size"],
        vocab_size=data["vocab_size"],
        rope_theta=float(data["rope_theta"]),
        norm_eps=float(data["rms_norm_eps"]),
        activation="swiglu",
        tie_embeddings=bool(data["tie_word_embeddings"]),
        param_dtype=data["torch_dtype"],
        compute_dtype=data["torch_dtype"],
        source=data["source"],
        moe=MoEConfig(
            num_experts=data["published"]["n_routed_experts"],
            top_k=data["num_experts_per_tok"],
            num_shared_experts=data["n_shared_experts"],
            expert_d_ff=data["moe_intermediate_size"],
            norm_topk_prob=bool(data["norm_topk_prob"]),
            expert_offset=data["expert_parallel"]["expert_offset"],
            experts_held=data["n_routed_experts"],
            first_dense_layers=data["first_k_dense_replace"]),
        mla=MLAConfig(
            kv_lora_rank=data["kv_lora_rank"],
            qk_nope_head_dim=data["qk_nope_head_dim"],
            qk_rope_head_dim=data["qk_rope_head_dim"],
            v_head_dim=data["v_head_dim"],
            rope_factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _attn(key, cfg, L, dtype):
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    k = jax.random.split(key, 5)
    return {"wq": {"w": _dense(k[0], (L, d, H * (m.qk_nope_head_dim
                                                 + m.qk_rope_head_dim)),
                               dtype)},
            "wkv_a": {"w": _dense(k[1], (L, d, m.entry_dim), dtype)},
            "kv_norm": {"scale": _scale(k[2], (L, m.kv_lora_rank))},
            "wkv_b": {"w": _dense(k[3], (L, m.kv_lora_rank,
                                         H * (m.qk_nope_head_dim
                                              + m.v_head_dim)), dtype)},
            "wo": {"w": _dense(k[4], (L, H * m.v_head_dim, d), dtype)}}


def _swiglu(key, lead, d, f, dtype):
    k = jax.random.split(key, 3)
    return {"gate": {"w": _dense(k[0], lead + (d, f), dtype)},
            "up": {"w": _dense(k[1], lead + (d, f), dtype)},
            "down": {"w": _dense(k[2], lead + (f, d), dtype)}}


def _stack(key, cfg, L, dtype, ffn):
    d = cfg.d_model
    k = jax.random.split(key, 5)
    p = {"attn_norm": {"scale": _scale(k[0], (L, d))},
         "attn": _attn(k[1], cfg, L, dtype)}
    if ffn == "mlp":
        p["mlp_norm"] = {"scale": _scale(k[2], (L, d))}
        p["mlp"] = _swiglu(k[3], (L,), d, cfg.d_ff, dtype)
        return p
    m = cfg.moe
    f, n = m.expert_d_ff, m.held
    e = jax.random.split(k[4], 5)
    p["moe_norm"] = {"scale": _scale(k[2], (L, d))}
    p["moe"] = {
        "router": {"w": _dense(e[0], (L, d, m.num_experts), jnp.float32)},
        "gate_proj": _dense(e[1], (L, n, d, f), dtype),
        "up_proj": _dense(e[2], (L, n, d, f), dtype),
        "down_proj": _dense(e[3], (L, n, f, d), dtype),
        "shared": _swiglu(e[4], (L,), d, m.num_shared_experts * f, dtype)}
    return p


def params(cfg, seed: int):
    """``embed``, ``dense_layers``, ``layers``, ``final_norm``,
    ``lm_head``."""
    dtype = jnp.dtype(cfg.param_dtype)
    nd = cfg.moe.first_dense_layers

    @jax.jit
    def make(key):
        ke, kd, kl, kn, kh = jax.random.split(key, 5)
        return {"embed": {"table": (0.02 * jax.random.normal(
                    ke, (cfg.vocab_size, cfg.d_model))).astype(dtype)},
                "dense_layers": _stack(kd, cfg, nd, dtype, "mlp"),
                "layers": _stack(kl, cfg, cfg.num_layers - nd, dtype, "moe"),
                "final_norm": {"scale": _scale(kn, (cfg.d_model,))},
                "lm_head": {"w": _dense(kh, (cfg.d_model, cfg.vocab_size),
                                        dtype)}}

    return make(seed_key(seed))


# (params, data, tokens, rows, *, int8=False): the plain reference's logits
# at ``rows`` of one sequence; ``int8=True`` is the control
logits_at = ref.logits_at


# ---------------------------------------------------------------------------
# FLOPs and bytes
# ---------------------------------------------------------------------------

def token_matmul_params(cfg) -> float:
    """Weight parameters one token passes through, with the routed experts
    at their expected share (module docstring)."""
    m, a, d = cfg.moe, cfg.mla, cfg.d_model
    H, L, nd = cfg.num_heads, cfg.num_layers, cfg.moe.first_dense_layers
    attn = (d * H * (a.qk_nope_head_dim + a.qk_rope_head_dim)
            + d * a.entry_dim
            + a.kv_lora_rank * H * (a.qk_nope_head_dim + a.v_head_dim)
            + H * a.v_head_dim * d)
    expert = 3 * d * m.expert_d_ff
    moe = (d * m.num_experts + m.num_shared_experts * expert
           + m.top_k * m.held / m.num_experts * expert)
    return (L * attn + nd * 3 * d * cfg.d_ff + (L - nd) * moe
            + d * cfg.vocab_size)


def prefill_flops(cfg, prompt_len: int) -> float:
    """One prompt through the trunk, logits for its last token only."""
    a, H = cfg.mla, cfg.num_heads
    body = token_matmul_params(cfg) - cfg.d_model * cfg.vocab_size
    pair = 2 * H * (a.qk_nope_head_dim + a.qk_rope_head_dim + a.v_head_dim)
    return float(2 * body * prompt_len
                 + pair * cfg.num_layers * causal_pairs(prompt_len)
                 + 2 * cfg.d_model * cfg.vocab_size)


def decode_flops(cfg, position: int) -> float:
    """One decoded token at ``position`` (it attends positions 0..position)."""
    return float(2 * token_matmul_params(cfg)
                 + decode_attention_flops(cfg, position))


def decode_attention_flops(cfg, position: int) -> float:
    """The absorbed latent attention of one decoded token at ``position``
    over all layers: scores over the whole entry and values over the
    latent, for each of its ``position + 1`` visible entries."""
    a = cfg.mla
    return float(cfg.num_heads * (2 * a.entry_dim + 2 * a.kv_lora_rank)
                 * cfg.num_layers * (position + 1))


def paged_decode_bytes(cfg, positions: Iterable[int], block_size: int,
                       kv_itemsize: int = 2) -> float:
    """HBM bytes the latent decode kernel needs for one decode step, over
    all layers: for each active lane at ``position``, the pages holding
    positions 0..position at the entry's needed width and their position
    rows, plus the query read and the output written (bf16)."""
    a, H = cfg.mla, cfg.num_heads
    per_block = block_size * (a.entry_dim * kv_itemsize + 4)
    total = 0
    for p in positions:
        total += ((p // block_size + 1) * per_block
                  + 2 * H * (a.entry_dim + a.kv_lora_rank))
    return float(total * cfg.num_layers)
