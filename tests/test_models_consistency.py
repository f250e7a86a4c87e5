"""Cross-path consistency: prefill + decode must agree with the teacher-
forced forward pass for every family that serves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer
from repro.models.registry import get_model


def _logits_from_forward(api, params, cfg, batch):
    """Teacher-forced logits at every position via the loss path's hidden."""
    if cfg.family == "moe":
        from repro.models import moe_transformer
        h, _ = moe_transformer.forward(params, cfg, batch["tokens"],
                                       remat=False)
        return transformer.logits_fn(params, cfg, h)
    if cfg.family == "ssm":
        from repro.models import xlstm_model
        h = xlstm_model.forward(params, cfg, batch["tokens"], remat=False)
        return transformer.logits_fn(params, cfg, h)
    if cfg.family == "hybrid":
        from repro.models import zamba2
        h = zamba2.forward(params, cfg, batch["tokens"], remat=False)
        return transformer.logits_fn(params, cfg, h)
    h = transformer.forward(params, cfg, batch["tokens"], remat=False)
    return transformer.logits_fn(params, cfg, h)


@pytest.mark.parametrize("arch,tol", [
    ("qwen3-0.6b", 2e-3),
    ("gemma2-27b", 2e-3),
    ("mixtral-8x7b", 5e-3),
    ("zamba2-2.7b", 5e-3),
])
def test_prefill_logits_match_forward(arch, tol):
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        # prefill routes dropless; the training forward's capacity dispatch
        # gets room for every token (capacity >= group), so that both
        # compute the same experts for every token
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}
    cache, last_logits = api.prefill(params, cfg, batch)
    full_logits = _logits_from_forward(api, params, cfg, batch)
    np.testing.assert_allclose(
        np.asarray(last_logits[:, 0], np.float32),
        np.asarray(full_logits[:, -1], np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "qwen2-moe-a2.7b"])
def test_training_capacity_gap_is_the_dropped_tokens(arch):
    """At the config's own capacity the training dispatch drops assignments
    that serving's dropless dispatch keeps: the two layers' outputs differ
    by exactly the gated expert outputs of the dropped assignments."""
    from repro.models.layers import moe
    cfg = get_smoke_config(arch)
    m = cfg.moe
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    T, d = 64, cfg.d_model
    # a shared direction that the router favours for expert 0, so that
    # expert 0 is over its capacity
    u = jax.random.normal(jax.random.PRNGKey(1), (d,))
    x = (jax.random.normal(jax.random.PRNGKey(2), (1, T, d)) + u)
    params["router"]["w"] = params["router"]["w"].at[:, 0].add(8 * u / d)
    y_train, _ = moe.moe_block(params, cfg, x, group_size=T)
    y_serve = moe.moe_serve(params, cfg, x)

    xt = np.asarray(x[0], np.float64)
    probs = np.asarray(jax.nn.softmax(x[0] @ params["router"]["w"], -1))
    idx = np.argsort(-probs, -1, kind="stable")[:, :m.top_k]
    gates = np.take_along_axis(probs, idx, -1)
    gates = gates / gates.sum(-1, keepdims=True)      # norm_topk_prob
    C = moe._capacity(T, m.top_k, m.num_experts, m.capacity_factor)
    seen = np.zeros(m.num_experts, int)
    dropped = np.zeros((T, d))
    n_drop = 0
    for t in range(T):                 # the dispatch's (token, k) order
        for k in range(m.top_k):
            e = idx[t, k]
            if seen[e] >= C:
                n_drop += 1
                w = {n: np.asarray(params[n][e], np.float64)
                     for n in ("gate_proj", "up_proj", "down_proj")}
                g = xt[t] @ w["gate_proj"]
                h = g / (1 + np.exp(-g)) * (xt[t] @ w["up_proj"])
                dropped[t] += gates[t, k] * (h @ w["down_proj"])
            seen[e] += 1
    assert n_drop > 0
    np.testing.assert_allclose(np.asarray(y_serve - y_train)[0], dropped,
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("arch,tol", [
    ("qwen3-0.6b", 2e-3),
    ("smollm-360m", 2e-3),
    ("xlstm-350m", 5e-3),        # chunked-vs-recurrent numerics
    ("zamba2-2.7b", 5e-3),
])
def test_decode_continuation_matches_forward(arch, tol):
    """prefill(t[0:n]) then decode t[n] must equal forward(t[0:n+1])'s last
    logits."""
    cfg = get_smoke_config(arch)
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    B, S = 2, 33
    # xlstm chunked prefill needs S % chunk == 0
    n = 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    cache, _ = api.prefill(params, cfg, {"tokens": tokens[:, :n]},
                           cache_len=S)
    logits_dec, _ = api.decode_step(
        params, cfg, cache,
        {"token": tokens[:, n:n + 1], "pos": jnp.asarray(n, jnp.int32)})
    full = _logits_from_forward(api, params, cfg,
                                {"tokens": tokens[:, :n + 1]})
    np.testing.assert_allclose(np.asarray(logits_dec[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               rtol=tol, atol=tol)


def test_chunked_ce_equals_naive():
    from repro.models.losses import chunked_ce
    cfg = get_smoke_config("qwen3-0.6b")
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    B, S = 2, 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                cfg.vocab_size)
    h = transformer.forward(params, cfg, tokens, remat=False)
    l_chunk = chunked_ce(h, params, cfg, labels, chunk=16)
    logits = transformer.logits_fn(params, cfg, h).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    l_naive = (lse - gold).mean()
    np.testing.assert_allclose(float(l_chunk), float(l_naive), rtol=1e-5)


def test_chunked_ce_ignores_masked_labels():
    from repro.models.losses import chunked_ce
    cfg = get_smoke_config("qwen3-0.6b")
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 512)
    labels = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, 512)
    labels_masked = labels.at[:, 16:].set(-1)
    h = transformer.forward(params, cfg, tokens, remat=False)
    l1 = chunked_ce(h, params, cfg, labels_masked, chunk=8)
    # same result as computing CE on the first half only
    h_half = h[:, :16]
    l2 = chunked_ce(h_half, params, cfg, labels[:, :16], chunk=8)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
