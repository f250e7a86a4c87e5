"""Jit-able step functions lowered by the dry-run and used by launchers.

  train_step   — full fine-tuning: value_and_grad + AdamW
  fed_train_step — the paper's step: LoRA-only grads, cluster-weighted psum
                 aggregation over the data (+pod) axes folded into the step
                 (DESIGN.md §3: federation mapped onto mesh collectives)
  prefill_step — full forward building the KV/SSM cache + last logits
  serve_step   — one-token decode against the cache, through the fused
                 flash-decode kernel path (repro.kernels.ops.flash_decode;
                 seq-sharded caches combine per-shard partials over the
                 ``model`` axis via repro.dist.decode)

All are pure; cfg/api are closed over (static).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.registry import get_model
from repro.optim.adamw import adamw_update


def make_train_step(cfg: ModelConfig, *, lr: float = 1e-4, accum: int = 1):
    """``accum`` > 1 enables gradient accumulation (microbatching): the
    global batch is split into ``accum`` microbatches scanned sequentially,
    dividing activation memory by ~accum at equal total FLOPs (§Perf
    memory-term lever for the large train_4k configs)."""
    api = get_model(cfg)

    def train_step(params, opt_state, batch, step):
        if accum <= 1:
            loss, grads = jax.value_and_grad(api.loss)(params, cfg, batch)
        else:
            # pin the f32 accumulation carry to the ZeRO layout — otherwise
            # it persists model-sharded-only (6.75 GiB/device at 27B) across
            # all microbatches (§Perf iteration 7)
            from repro.dist.sharding import (current_mesh, opt_state_specs,
                                             to_shardings)
            mesh = current_mesh()

            def pin(tree):
                if mesh is None:
                    return tree
                sh = to_shardings(opt_state_specs(tree, mesh), mesh)
                return jax.tree.map(jax.lax.with_sharding_constraint,
                                    tree, sh)

            # grad accumulation dtype: bf16 halves the dominant train-step
            # temp (transient grad tree + carry) at a documented precision
            # cost (§Perf iteration 8) — f32 default.
            import os
            acc_dt = jnp.bfloat16 if os.environ.get(
                "REPRO_GRAD_DTYPE") == "bf16" else jnp.float32

            def micro(carry, mb):
                l_acc, g_acc = carry
                l, g = jax.value_and_grad(api.loss)(params, cfg, mb)
                g_acc = pin(jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32) +
                                  b.astype(jnp.float32)).astype(acc_dt),
                    g_acc, g))
                return (l_acc + l, g_acc), None

            micro_batches = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) +
                                    x.shape[1:]), batch)
            zero = (jnp.zeros((), jnp.float32),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt),
                                 params))
            (loss, grads), _ = jax.lax.scan(micro, zero, micro_batches)
            loss = loss / accum
            grads = jax.tree.map(lambda g: g / accum, grads)
        params, opt_state = adamw_update(params, grads, opt_state, step + 1,
                                         lr=lr)
        return params, opt_state, loss

    return train_step


def make_fed_train_step(cfg: ModelConfig, *, lr: float = 1e-3):
    """The paper's local step at mesh scale: every data-axis slice is a
    cluster member training its LoRA adapters on its own shard; the
    weighted adapter-delta aggregation (Algorithm 1, line 12) is a psum
    over ``data`` (+``pod`` cross-site).  Base weights receive no grads and
    no traffic — exactly FedTime's comm profile."""
    api = get_model(cfg)
    from repro.core.lora import lora_tree, merge_lora

    def fed_train_step(params, opt_state, batch, step):
        # differentiate w.r.t. the adapter subtree ONLY: the NF4-quantized
        # base (uint8 codes) is frozen and carries no tangents — exactly
        # the paper's client step
        adapters = lora_tree(params)

        def loss_fn(ad):
            return api.loss(merge_lora(params, ad), cfg, batch)

        loss, grads = jax.value_and_grad(loss_fn)(adapters)
        adapters, opt_state = adamw_update(adapters, grads, opt_state,
                                           step + 1, lr=lr)
        params = merge_lora(params, adapters)
        return params, opt_state, loss

    return fed_train_step


def make_prefill_step(cfg: ModelConfig, *, force_window: int = 0):
    api = get_model(cfg)

    def prefill_step(params, batch):
        return api.prefill(params, cfg, batch, force_window=force_window)

    return prefill_step


def make_serve_step(cfg: ModelConfig, *, force_window: int = 0,
                    sampling: bool = False, guard: bool = False):
    """One-token decode step.  Attention over the ring cache runs the fused
    flash-decode path (Pallas on TPU, blockwise XLA elsewhere; int8 caches
    dequantized tile-by-tile in the streamed pass); REPRO_FLASH_DECODE=0
    restores the legacy dequantize-then-sdpa step for A/B comparison.

    Two batch layouts share the one compiled step:

      * synchronous: ``{"token": (B,1), "pos": scalar}`` — every row at the
        same position (the fixed-batch launcher / dry-run shape).
      * ragged (continuous batching): ``pos`` is (B,) with per-slot
        positions, ``-1`` marking inactive lanes.  Inactive lanes are fully
        masked in attention, their cache lanes are frozen (SSM states
        included), and their token passes through unchanged — batch
        composition changes step to step without re-jit.  With a paged pool
        (``block_tbl``/``ring_len`` in the batch) the attention cache is one
        shared block pool: inactive-lane writes are already dropped at the
        scatter (out-of-bounds index, mode="drop"), so the freeze select is
        skipped — it has no batch axis to select over.

    ``sampling=True`` additionally reads per-slot ``temperature``/``top_k``/
    ``top_p`` ((B,) arrays), base PRNG keys ``key`` ((B, 2) uint32) and
    per-slot sample counters ``t`` ((B,)), routing logits through
    ``repro.serve.sampling.sample_vec`` (rows with temperature <= 0 stay
    greedy — bit-identical to the argmax path).

    ``guard=True`` (the fault-tolerant engine's step) additionally reads a
    (B,) bool ``poison`` batch row — the chaos harness's in-jit NaN
    injector, which overwrites a poisoned lane's logits row with NaN
    *before* sampling — and returns ``(next_token, ok, new_cache)`` where
    ``ok`` is ``fault.guard.logits_finite`` evaluated per lane on the
    post-injection logits slice (inactive lanes report ok, they produced
    nothing).  The injector and the screen live in the same compiled step
    so arming/disarming chaos never adds a jit signature."""
    api = get_model(cfg)

    def serve_step(params, cache, batch):
        pos = jnp.asarray(batch["pos"], jnp.int32)
        logits, new_cache = api.decode_step(params, cfg, cache, batch,
                                            force_window=force_window)
        lg = logits[:, -1, :]
        if guard:
            from repro.fault.guard import logits_finite
            poison = jnp.asarray(batch["poison"], bool)
            lg = jnp.where(poison[:, None], jnp.asarray(jnp.nan, lg.dtype),
                           lg)
            ok = logits_finite(lg)
        if sampling:
            from repro.serve.sampling import sample_vec
            keys = jax.vmap(jax.random.fold_in)(batch["key"], batch["t"])
            next_token = sample_vec(keys, lg,
                                    temperature=batch["temperature"],
                                    top_k=batch["top_k"],
                                    top_p=batch["top_p"])[:, None]
        else:
            next_token = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
        if pos.ndim == 1:
            active = pos >= 0
            if "block_tbl" not in batch:
                from repro.serve.cache_pool import (cache_batch_axes,
                                                    freeze_inactive)
                new_cache = freeze_inactive(cache, new_cache, active,
                                            cache_batch_axes(api, cfg))
            next_token = jnp.where(active[:, None], next_token,
                                   batch["token"])
            if guard:
                ok = ok | ~active          # inactive lanes produced nothing
        if guard:
            return next_token, ok, new_cache
        return next_token, new_cache

    return serve_step


def decode_force_window(cfg: ModelConfig, seq_len: int) -> int:
    """long_500k policy (DESIGN.md §4): pure full-attention archs decode
    under the sliding-window variant; windowed/recurrent archs run native."""
    if seq_len >= 262_144 and cfg.sliding_window == 0 and \
            cfg.family not in ("ssm", "hybrid"):
        return cfg.decode_sliding_window or 4096
    return 0
