"""Token sampling: greedy / temperature / top-k / top-p (nucleus).

Pure functions over logits (B, V) so they compose with any family's
decode_step under jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_NEG = jnp.finfo(jnp.float32).min


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample(key, logits: jnp.ndarray, *, temperature: float = 1.0,
           top_k: int = 0, top_p: float = 0.0) -> jnp.ndarray:
    """logits (B, V) -> tokens (B,).  The knobs are read in float32 and
    the temperature floored at 1e-6, as ``sample_vec`` reads them: a
    positive temperature that underflows to 0 decodes greedily and a tiny
    one never divides the logits to inf/nan; a top_p that underflows to 0
    keeps the whole distribution, and any nucleus keeps the top token."""
    logits = logits.astype(jnp.float32)
    temperature, top_p = np.float32(temperature), np.float32(top_p)
    if temperature <= 0.0:
        return greedy(logits)
    logits = logits / max(temperature, np.float32(1e-6))
    if top_k > 0:
        # clamp to the vocab: top_k > V would index past the sorted logits
        k_eff = min(int(top_k), logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[:, -k_eff][:, None]
        logits = jnp.where(logits < kth, _NEG, logits)
    if 0.0 < top_p < 1.0:
        # top_p >= 1.0 keeps the whole distribution; skipping the cutoff
        # avoids the degenerate all-excluded row when cumsum rounds past 1
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest logit value still inside the nucleus
        # the top token always stays: a subnormal top_p that the device
        # flushes to zero would otherwise keep none
        keep = (cum - probs < top_p).at[:, 0].set(True)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, _NEG, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_vec(keys, logits: jnp.ndarray, *, temperature, top_k,
               top_p) -> jnp.ndarray:
    """Per-row sampling for ragged serving batches: logits (B, V) ->
    tokens (B,).

    ``keys`` is a (B, 2) uint32 array (one independent PRNG key per row —
    request isolation: a row's stream never depends on its batch
    neighbours); ``temperature``/``top_k``/``top_p`` are (B,) arrays so the
    request mix changes without re-jitting the serve step.  Rows with
    ``temperature <= 0`` (in float32) decode greedily; ``top_k`` is
    clamped to the vocab and ``top_p >= 1`` disables the nucleus cutoff,
    mirroring ``sample``.  The ops run under ``jax.named_scope("obs.sample")``
    so the compiled program's ``op_name`` metadata (and the HLO cost
    attribution of ``obs.devmem.scope_costs``) names them.
    """
    with jax.named_scope("obs.sample"):
        B, V = logits.shape
        logits = logits.astype(jnp.float32)
        greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        temperature = jnp.asarray(temperature, jnp.float32)
        top_k = jnp.asarray(top_k, jnp.int32)
        top_p = jnp.asarray(top_p, jnp.float32)

        lg = logits / jnp.maximum(temperature, 1e-6)[:, None]
        sorted_desc = jnp.sort(lg, axis=-1)[:, ::-1]
        kk = jnp.clip(top_k, 0, V)
        kth = sorted_desc[jnp.arange(B), jnp.maximum(kk - 1, 0)][:, None]
        lg = jnp.where((kk[:, None] > 0) & (lg < kth), _NEG, lg)

        sorted_k = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_k, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p[:, None]
        cutoff = jnp.min(jnp.where(keep, sorted_k, jnp.inf), axis=-1,
                         keepdims=True)
        use_p = ((top_p > 0.0) & (top_p < 1.0))[:, None]
        lg = jnp.where(use_p & (lg < cutoff), _NEG, lg)

        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(keys, lg)
        return jnp.where(temperature <= 0.0, greedy_tok,
                         sampled.astype(jnp.int32))


def generate(api, params, cfg, cache, first_token, *, steps: int,
             start_pos: int, key=None, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 0.0, force_window: int = 0):
    """Autoregressive generation loop (lax.scan — jit-able end to end).

    first_token: (B, 1) int32 from prefill. Returns (tokens (B, steps),
    final cache)."""
    B = first_token.shape[0]
    key = key if key is not None else jax.random.PRNGKey(0)

    def step(carry, i):
        tok, cache, k = carry
        logits, cache = api.decode_step(
            params, cfg, cache, {"token": tok, "pos": start_pos + i},
            force_window=force_window)
        k, sub = jax.random.split(k)
        nxt = sample(sub, logits[:, -1, :], temperature=temperature,
                     top_k=top_k, top_p=top_p)[:, None]
        return (nxt, cache, k), nxt[:, 0]

    (_, cache, _), toks = jax.lax.scan(
        step, (first_token, cache, key),
        jnp.arange(steps, dtype=jnp.int32))
    return toks.T, cache                          # (B, steps)
