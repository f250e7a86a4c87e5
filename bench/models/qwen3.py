"""Qwen3 (``model_type`` ``qwen3``; arXiv:2505.09388): everything the
harness knows of this architecture, found by ``common.model_module`` from
the ``model_type`` of a configuration file.

* ``model_config(data)``: the program's ``ModelConfig`` from the file's
  Hugging Face keys, so that the file, and nothing else, sets the sizes;
* ``params(cfg, seed)``: random weights made on the device from the seed,
  in one jitted call, in the dtype they are served in and the layout the
  program reads (tied embeddings);
* ``logits_at(...)``: the plain float32 reference, ``reference/qwen3.py``;
* the model FLOPs and the paged decode's HBM bytes that ``serve.mfu`` and
  ``flash_decode_roofline`` divide.

Counting conventions (model FLOPs, no recomputation counted): a matmul
with a weight of ``n`` parameters costs ``2 n`` FLOPs per token; causal
attention costs ``4 H Dh`` FLOPs per (query, visible key) pair (scores
and values); decode reads each cached key/value once per layer.
"""

from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp

from flops import causal_pairs
from reference import qwen3 as ref
from weights import _dense, _scale, seed_key


def model_config(data: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=data["name"],
        family="dense",
        num_layers=data["num_hidden_layers"],
        d_model=data["hidden_size"],
        num_heads=data["num_attention_heads"],
        num_kv_heads=data["num_key_value_heads"],
        head_dim=data["head_dim"],
        d_ff=data["intermediate_size"],
        vocab_size=data["vocab_size"],
        qk_norm=True,
        rope_theta=float(data["rope_theta"]),
        norm_eps=float(data["rms_norm_eps"]),
        activation={"silu": "swiglu"}[data["hidden_act"]],
        tie_embeddings=bool(data["tie_word_embeddings"]),
        param_dtype=data["torch_dtype"],
        compute_dtype=data["torch_dtype"],
        source=data["source"],
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

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


def params(cfg, seed: int):
    """Decoder LM with tied embeddings: ``embed``, ``layers``,
    ``final_norm``."""
    dtype = jnp.dtype(cfg.param_dtype)

    @jax.jit
    def make(key):
        ke, kl, kn = jax.random.split(key, 3)
        return {"embed": {"table": (0.02 * jax.random.normal(
                    ke, (cfg.vocab_size, cfg.d_model))).astype(dtype)},
                "layers": _layers(kl, cfg, dtype),
                "final_norm": {"scale": _scale(kn, (cfg.d_model,))}}

    return make(seed_key(seed))


# (params, data, tokens, rows, *, int8=False): the plain reference's logits
# at ``rows`` of one sequence; ``int8=True`` is the control
logits_at = ref.logits_at


# ---------------------------------------------------------------------------
# FLOPs and bytes
# ---------------------------------------------------------------------------

def layer_matmul_params(cfg) -> tuple:
    """(attention, mlp) weight parameters of one decoder layer."""
    d, dh = cfg.d_model, cfg.resolved_head_dim()
    q, kv = cfg.num_heads * dh, cfg.num_kv_heads * dh
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if cfg.activation in ("swiglu", "geglu") else 2) * d * cfg.d_ff
    return attn, mlp


def prefill_flops(cfg, prompt_len: int) -> float:
    """One prompt through the trunk, logits for its last token only."""
    attn, mlp = layer_matmul_params(cfg)
    L, H, dh = cfg.num_layers, cfg.num_heads, cfg.resolved_head_dim()
    return float(2 * L * (attn + mlp) * prompt_len
                 + 4 * H * dh * L * causal_pairs(prompt_len)
                 + 2 * cfg.d_model * cfg.vocab_size)


def decode_flops(cfg, position: int) -> float:
    """One decoded token at ``position`` (it attends positions 0..position)."""
    attn, mlp = layer_matmul_params(cfg)
    return float(2 * cfg.num_layers * (attn + mlp)
                 + 2 * cfg.d_model * cfg.vocab_size
                 + decode_attention_flops(cfg, position))


def decode_attention_flops(cfg, position: int) -> float:
    """The attention of one decoded token at ``position`` over all layers:
    scores and values against its ``position + 1`` visible keys."""
    return float(4 * cfg.num_heads * cfg.resolved_head_dim()
                 * cfg.num_layers * (position + 1))


def paged_decode_bytes(cfg, positions: Iterable[int], block_size: int,
                       kv_itemsize: int = 2) -> float:
    """HBM bytes the paged flash-decode kernel needs for one decode step,
    over all layers: for each active lane at ``position``, the K and V
    blocks holding positions 0..position and their position rows, plus the
    query read and the output written (bf16)."""
    dh, hk, h = (cfg.resolved_head_dim(), cfg.num_kv_heads, cfg.num_heads)
    per_block = block_size * (2 * hk * dh * kv_itemsize + 4)
    total = 0
    for p in positions:
        total += (p // block_size + 1) * per_block + 2 * h * dh * 2
    return float(total * cfg.num_layers)
