"""Configuration system for the repro framework.

A single ``ModelConfig`` dataclass describes every architecture family the
framework supports (dense decoder, MoE decoder, encoder-decoder, VLM, SSM,
hybrid).  Each assigned architecture gets one module in ``repro.configs``
exporting ``CONFIG`` (the exact published dims) and ``smoke_config()`` (a
reduced variant for CPU smoke tests).

Configs are plain frozen dataclasses — hashable so they can be closed over
by jitted functions as static data.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration: GShard capacity dispatch for
    the training loss, dropless grouped matmuls for serving."""

    num_experts: int = 8                 # the router's width (all experts)
    top_k: int = 2
    num_shared_experts: int = 0          # Qwen2-MoE style always-on experts
    capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.01
    # d_ff of each routed expert (may differ from the dense d_ff)
    expert_d_ff: int = 0
    norm_topk_prob: bool = True          # renormalise the top-k gates
    # the experts this chip holds under expert parallelism: ids
    # [expert_offset, expert_offset + experts_held); 0 => all of them
    expert_offset: int = 0
    experts_held: int = 0
    # leading dense SwiGLU layers of width ModelConfig.d_ff (DeepSeek
    # ``first_k_dense_replace``)
    first_dense_layers: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values come from
    one ``kv_lora_rank``-wide latent per position (RMS-normed) through
    ``kv_b``, beside a decoupled RoPE key of ``qk_rope_head_dim`` shared by
    every head.  The cache holds the latent and the roped key, one entry of
    ``kv_lora_rank + qk_rope_head_dim`` a position (stored ``cache_dim``
    wide).  RoPE is YaRN-scaled
    when ``rope_factor`` > 1 (``rope_scaling`` type ``yarn``)."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0

    @property
    def entry_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_dim(self) -> int:
        """Stored width of an entry: ``entry_dim`` zero-padded to whole
        128-lane tiles, as the TPU's HBM layout holds it anyway and as a
        page DMA has to move it."""
        return -(-self.entry_dim // 128) * 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block configuration."""

    state_dim: int = 64                  # N — per-head SSM state size
    head_dim: int = 64                   # P — channels per SSM head
    expand: int = 2                      # d_inner = expand * d_model
    conv_width: int = 4                  # depthwise causal conv width
    chunk_size: int = 128                # chunked-scan block length


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block configuration (mLSTM + sLSTM mix)."""

    slstm_every: int = 6                 # every k-th block is sLSTM (rest mLSTM)
    mlstm_proj_factor: float = 2.0       # up-projection factor for mLSTM blocks
    slstm_proj_factor: float = 1.333     # FFN factor for sLSTM blocks
    conv_width: int = 4
    chunk_size: int = 128


@dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style hybrid: Mamba2 backbone + shared attention block."""

    shared_attn_every: int = 6           # apply the (weight-shared) attn block
                                         # every k mamba layers
    num_shared_blocks: int = 2           # distinct shared transformer blocks
                                         # (Zamba2 uses 2, round-robin)


@dataclass(frozen=True)
class EncDecConfig:
    """Encoder-decoder split (seamless-m4t style backbone)."""

    encoder_layers: int = 12
    # decoder layer count == ModelConfig.num_layers
    encoder_bidirectional: bool = True
    max_source_len: int = 4096           # frame-embedding memory length cap


@dataclass(frozen=True)
class VLMConfig:
    """VLM backbone (paligemma style): prefix-LM over stub patch embeddings."""

    num_image_tokens: int = 256          # SigLIP 224px/14 => 256 patches
    vision_embed_dim: int = 1152         # SigLIP-So400m width (stub output)
    prefix_lm: bool = True               # bidirectional attention over prefix


@dataclass(frozen=True)
class FedTimeConfig:
    """The paper's TS front-end (C1) + federation hyper-params (C3/C5)."""

    # --- PatchTST-style front end ---
    lookback: int = 512                  # L
    horizon: int = 96                    # T
    patch_len: int = 16                  # P
    patch_stride: int = 8                # S (overlapping patches)
    revin: bool = True                   # RevIN in forecasting-FT phase
    # --- federation ---
    num_clients: int = 555               # paper's setup
    num_clusters: int = 8                # K in K-means
    clients_per_round: int = 16
    local_steps: int = 40                # paper grid: {40, 80, 200}
    # --- PEFT ---
    lora_rank: int = 8
    lora_alpha: float = 16.0
    lora_dropout: float = 0.0
    qlora: bool = True                   # NF4-quantize frozen base weights
    qlora_block: int = 64                # absmax block size (NF4 default)
    # --- DPO alignment ---
    dpo_beta: float = 0.1
    dpo_pairs: int = 10_000              # paper: 10K comparison pairs


@dataclass(frozen=True)
class ModelConfig:
    """One config to describe every supported architecture."""

    name: str
    family: str                          # dense | moe | encdec | vlm | ssm | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                    # 0 => d_model // num_heads
    # --- attention variants ---
    qk_norm: bool = False                # Qwen3-style per-head RMSNorm on q,k
    attn_logit_softcap: float = 0.0      # Gemma2 (50.0); 0 disables
    final_logit_softcap: float = 0.0     # Gemma2 (30.0); 0 disables
    sliding_window: int = 0              # 0 => full attention
    local_global_alternating: bool = False   # Gemma2 local/global layer pairs
    rope_theta: float = 10_000.0
    max_seq_len: int = 524_288
    # --- MLP ---
    activation: str = "swiglu"           # swiglu | geglu | gelu
    # --- norm / embedding ---
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    embedding_multiplier: float = 1.0    # Gemma scales embeds by sqrt(d_model)
    post_block_norm: bool = False        # Gemma2 post-norms
    # --- family sub-configs ---
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None      # latent attention in place of GQA
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    vlm: Optional[VLMConfig] = None
    fedtime: Optional[FedTimeConfig] = None
    # --- numerics ---
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # --- provenance ---
    source: str = ""                     # citation (model card / arXiv)
    # --- decode-time overrides ---
    # For pure full-attention archs, long_500k decode runs under this
    # sliding-window variant (see DESIGN.md §4 long_500k policy).
    decode_sliding_window: int = 0

    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim()

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim()

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def validate(self) -> None:
        assert self.family in ("dense", "moe", "encdec", "vlm", "ssm", "hybrid"), self.family
        assert self.num_heads % max(self.num_kv_heads, 1) == 0 or self.family in ("ssm",), (
            f"{self.name}: num_heads={self.num_heads} not divisible by "
            f"num_kv_heads={self.num_kv_heads}"
        )
        if self.family == "moe":
            assert self.moe is not None
        if self.family == "ssm":
            assert self.xlstm is not None or self.ssm is not None
        if self.family == "hybrid":
            assert self.ssm is not None and self.hybrid is not None
        if self.family == "encdec":
            assert self.encdec is not None
        if self.family == "vlm":
            assert self.vlm is not None


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (public pool).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                            # train | prefill | decode


INPUT_SHAPES: Tuple[InputShape, ...] = (
    InputShape("train_4k", 4_096, 256, "train"),
    InputShape("prefill_32k", 32_768, 32, "prefill"),
    InputShape("decode_32k", 32_768, 128, "decode"),
    InputShape("long_500k", 524_288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}
