"""The program's model configuration built from a configuration file.

The file (``bench/configs/<config>.json``) holds the Hugging Face keys of
the published ``config.json`` as they are run; this maps them onto
``repro.configs.base.ModelConfig`` so that the file, and nothing else,
sets the sizes.
"""

from __future__ import annotations


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
