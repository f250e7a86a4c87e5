"""Operations and bytes that the algorithm needs, counted from shapes —
the numerators of the utilization and roofline metrics — and the table of
peaks they are divided by.

Conventions (model FLOPs, no recomputation counted):

* a matmul with a weight of ``n`` parameters costs ``2 n`` FLOPs per token;
* causal attention costs ``4 H Dh`` FLOPs per (query, visible key) pair
  (scores and values);
* decode reads each cached key/value once per layer.
"""

from __future__ import annotations

import json
import math
import os
from typing import Iterable

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that is
    not in ``peaks.json`` is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(cfg) -> tuple:
    """(attention, mlp) weight parameters of one decoder layer."""
    d, dh = cfg.d_model, cfg.resolved_head_dim()
    q, kv = cfg.num_heads * dh, cfg.num_kv_heads * dh
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if cfg.activation in ("swiglu", "geglu") else 2) * d * cfg.d_ff
    return attn, mlp


def causal_pairs(s: int) -> int:
    """(query, key) pairs a causal sequence of length ``s`` attends."""
    return s * (s + 1) // 2


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
    L, H, dh = cfg.num_layers, cfg.num_heads, cfg.resolved_head_dim()
    return float(2 * L * (attn + mlp) + 2 * cfg.d_model * cfg.vocab_size
                 + 4 * H * dh * L * (position + 1))


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


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share(numerator_s: float, measured_s: float):
    """A share of a roofline or peak in percent, or None with nothing
    measured (never 0 for want of a reading)."""
    if not measured_s or measured_s <= 0 or not math.isfinite(numerator_s):
        return None
    return 100.0 * numerator_s / measured_s
