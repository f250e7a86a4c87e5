"""DeepSeek-V2's blocks at a small size on the CPU: multi-head latent
attention over the paged latent pool, and dropless routing over the
experts one chip holds, against the plain float32 reference
(``bench/reference/deepseek_v2.py``, which imports nothing of the program).

The preset: 3 layers (1 dense + 2 MoE), 16 routed experts of which this
chip holds the second share of 4 (ids 4-7), top-3, 2 shared experts,
latent 32 + rope 16, 4 heads, vocabulary 256, float32 throughout."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.models import moe_transformer
from repro.models.layers import moe as moe_mod
from repro.models.layers.attention import mla_softmax_scale, yarn_inv_freq
from repro.models.registry import get_model

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench", "reference", "deepseek_v2.py")
_spec = importlib.util.spec_from_file_location("deepseek_v2_reference", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

ROPE = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}


def preset(offset=4, held=4):
    return ModelConfig(
        name="dsv2-test", family="moe", num_layers=3, d_model=64,
        num_heads=4, num_kv_heads=4, head_dim=32, d_ff=96, vocab_size=256,
        norm_eps=1e-6, rope_theta=10000.0, param_dtype="float32",
        compute_dtype="float32",
        moe=MoEConfig(num_experts=16, top_k=3, num_shared_experts=2,
                      expert_d_ff=32, norm_topk_prob=False,
                      expert_offset=offset, experts_held=held,
                      first_dense_layers=1),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=16, v_head_dim=16, rope_factor=40.0,
                      original_max_position=64, mscale=0.707,
                      mscale_all_dim=0.707))


def hf_keys(cfg):
    """The same model in the reference's (Hugging Face) keys."""
    m, a = cfg.moe, cfg.mla
    return {"num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim, "kv_lora_rank": a.kv_lora_rank,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": m.top_k,
            "norm_topk_prob": m.norm_topk_prob, "routed_scaling_factor": 1,
            "rope_scaling": ROPE,
            "expert_parallel": {"expert_offset": m.expert_offset},
            # the flat form the reference's blocks read
            "expert_offset": m.expert_offset}


def params_for(cfg, seed=0):
    """Seeded weights, norm scales drawn near 1 so that a skipped norm
    shows."""
    p = get_model(cfg).init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def scale(path, x):
        if getattr(path[-1], "key", None) == "scale":
            return 1.0 + 0.1 * jax.random.normal(next(keys), x.shape)
        return x

    return jax.tree_util.tree_map_with_path(scale, p)


def ref_logits(params, cfg, seq, rows=64):
    """Reference logits at every row of ``seq`` (one sequence, at most
    ``rows`` long: one compiled program for every length)."""
    seq = np.asarray(seq, np.int32)
    lg = ref.logits_at(params, hf_keys(cfg), jnp.asarray(seq),
                       jnp.minimum(jnp.arange(rows), len(seq) - 1))
    return np.asarray(lg)[:len(seq)]


# ---------------------------------------------------------------------------
# engine: prefill, then paged decode through the latent pool
# ---------------------------------------------------------------------------

# Tolerance on logits (float32 on the CPU, |logits| ~ 1): the program and
# the reference differ in the order of float32 operations only (absorbed
# against non-absorbed attention, grouped against dense expert matmuls),
# which moves these logits by at most 6e-6; a latent pool kept in bfloat16
# moves them by 2.6e-2.
ATOL = 2e-4


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_engine_matches_reference(path, monkeypatch):
    """Requests through ``ForecastEngine`` on the paged latent pool: a
    fresh prompt, a block-aligned prefix-sharing hit, a whole-prompt hit
    (copy-on-write of its shared tail block) and a swap-out/swap-in round
    trip; every decode step's logits match the reference's full forward at
    that position.  ``kernel``: the Pallas paged kernel's latent mode in
    interpret mode."""
    from repro.kernels import ops
    from repro.serve.engine import ForecastEngine
    from repro.serve.request import Request
    if path == "kernel":
        monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    cfg = preset()
    params = params_for(cfg)
    eng = ForecastEngine(cfg, params, num_slots=3, cache_len=64,
                         block_size=8, prefill_bucket=16,
                         share_prefixes=True, swap_tier=True)
    assert eng.paged and eng._paged_kernel == (path == "kernel")
    assert ops.pallas_decode(eng.pool.ring_len) == (path == "kernel")
    seen = []

    def record(pos, logits):
        for i, s in enumerate(eng.slots):
            if s is not None and pos[i] >= 0:
                seen.append((s.request.id, int(pos[i]),
                             np.asarray(logits[i, -1])))

    orig = moe_transformer.decode_step

    def decode_step(*a, **kw):
        logits, cache = orig(*a, **kw)
        jax.debug.callback(record, a[4], logits)
        return logits, cache

    monkeypatch.setattr(moe_transformer, "decode_step", decode_step)
    rng = np.random.default_rng(0)
    pre = rng.integers(0, 256, 16)                   # two whole blocks
    prompts = {"a": np.concatenate([pre, rng.integers(0, 256, 5)]),
               "b": np.concatenate([pre, rng.integers(0, 256, 9)])}
    prompts["c"] = prompts["a"].copy()               # whole-prompt hit
    out = {"a": 6, "b": 8, "c": 5}

    def submit(rid):
        eng.submit(Request(id=rid, prompt=prompts[rid].astype(np.int32),
                           max_new_tokens=out[rid]))

    submit("a")
    eng.step()
    submit("b")
    submit("c")
    for _ in range(3):
        eng.step()
    # b shares a's preamble, c all of a's prompt (its write CoWs a block)
    assert eng.metrics.share_hits == 2 and eng.metrics.full_prompt_hits == 1
    assert eng.metrics.cow_copies >= 1
    # swap b out to the host tier, then let the engine swap it back in
    slot = next(i for i, s in enumerate(eng.slots)
                if s is not None and s.request.id == "b")
    eng.scheduler.requeue_front([eng._swap_out(slot)])
    eng.step()
    assert eng.metrics.swap_outs == 1 and eng.metrics.swap_ins == 1
    done = eng.run()
    assert set(done) == {"a", "b", "c"}
    want = {rid: ref_logits(params, cfg, np.concatenate(
        [prompts[rid], done[rid].tokens[:-1]])) for rid in done}
    checked = {rid: 0 for rid in done}
    for rid, pos, lg in seen:
        np.testing.assert_allclose(lg, want[rid][pos], atol=ATOL, rtol=0)
        checked[rid] += 1
    assert all(checked[r] == out[r] - 1 for r in done), checked


def test_engine_live_pages_count_the_latent_pool(monkeypatch):
    """``engine.decode_step``'s ``live_pages``: the table entries the
    latent kernel visits, 1 + each lane's last granted logical block."""
    from repro.serve.engine import ForecastEngine
    from repro.serve.request import Request
    monkeypatch.setenv("REPRO_FORCE_KERNELS", "1")
    cfg = preset()
    eng = ForecastEngine(cfg, params_for(cfg), num_slots=2, cache_len=64,
                         block_size=8, prefill_bucket=16,
                         share_prefixes=False, swap_tier=False)
    eng.submit(Request(id="x", prompt=np.arange(21, dtype=np.int32),
                       max_new_tokens=3))
    eng.step()             # a prefill bucket of 32 holds blocks 0-3
    assert eng.pool.live_pages() == 4
    assert eng.metrics.decode_pages_live == 4
    assert eng.metrics.decode_pages_grid == 2 * 8


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _moe_params(cfg, seed=3):
    lp = moe_transformer.init(cfg, jax.random.PRNGKey(seed))["layers"]
    return jax.tree.map(lambda x: x[0], lp["moe"])


def test_expert_shares_sum_to_the_uncut_layer():
    """Each of the 4 shares of 4 experts computes its routed part plus the
    shared experts; the routed parts summed, with the shared experts once,
    give the uncut layer's output, the reference's with all 16 held."""
    full = preset(offset=0, held=16)
    p = _moe_params(full)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    shared = moe_mod.mlp(p["shared"], x, "swiglu")
    total = shared
    for s in range(4):
        cfg = preset(offset=4 * s, held=4)
        ps = dict(p, **{k: p[k][4 * s:4 * s + 4]
                        for k in ("gate_proj", "up_proj", "down_proj")})
        total = total + moe_mod.moe_serve(ps, cfg, x) - shared
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x.reshape(-1, 64), p, hf_keys(full), False)
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64),
                               np.asarray(want), atol=1e-5, rtol=0)


def test_a_batch_routed_to_one_expert_loses_no_token():
    """Every token's largest router logit is expert 5's: the dropless path
    computes all 48 of them there (the reference's dense sum), where the
    training path's capacity dispatch drops those over its capacity."""
    cfg = preset(offset=0, held=16)
    p = _moe_params(cfg)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64)))
    w = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (64, 16))
    p = dict(p, router={"w": w.at[:, 5].set(1.0)})
    gates, idx = moe_mod._route(p, cfg, x.reshape(-1, 64))
    assert bool(jnp.all(idx[:, 0] == 5))
    y = moe_mod.moe_serve(p, cfg, x)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x.reshape(-1, 64), p, hf_keys(cfg), False)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64),
                               np.asarray(want), atol=1e-5, rtol=0)
    capped, _ = moe_mod.moe_block(p, cfg, x)
    assert not np.allclose(np.asarray(capped), np.asarray(y), atol=1e-3)


# ---------------------------------------------------------------------------
# YaRN and the softmax scale at DeepSeek-V2-Lite's published settings
# ---------------------------------------------------------------------------

def test_yarn_and_softmax_scale_match_the_published_formulas():
    lite = MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                     qk_rope_head_dim=64, v_head_dim=128, rope_factor=40.0,
                     original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    inv = yarn_inv_freq(64, 10000.0, lite)
    i = np.arange(32)
    extra = 1.0 / 10000.0 ** (2 * i / 64)
    # correction range: floor(64 ln(4096 / (32 2 pi)) / (2 ln 1e4)) = 10,
    # ceil(64 ln(4096 / (1 2 pi)) / (2 ln 1e4)) = 23
    low, high = 10, 23
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40.0 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    np.testing.assert_allclose(inv[:low], extra[:low], rtol=1e-6)
    np.testing.assert_allclose(inv[high:], extra[high:] / 40.0, rtol=1e-6)
    cfg = preset().replace(mla=lite, num_heads=16)
    scale = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert mla_softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert mla_softmax_scale(cfg) == pytest.approx(0.11472, abs=5e-6)


def test_latent_scopes_reach_hlo_metadata():
    """``obs.mla`` and ``obs.moe`` name the compiled paged serve step's
    operations (HLO ``op_name`` metadata), as ``obs.sample`` does."""
    from repro.launch.steps import make_serve_step
    from repro.obs import devmem
    cfg = preset()
    api = get_model(cfg)
    params = params_for(cfg)
    B, bs, T = 2, 8, 8
    cache = api.init_cache(cfg, B * T, bs, dtype=jnp.float32)
    batch = {"token": jnp.zeros((B, 1), jnp.int32),
             "pos": jnp.array([3, -1], jnp.int32),
             "block_tbl": jnp.arange(B * T, dtype=jnp.int32).reshape(B, T),
             "ring_len": jnp.asarray(T * bs, jnp.int32)}
    compiled = jax.jit(make_serve_step(cfg)).lower(params, cache,
                                                   batch).compile()
    costs = devmem.compiled_scope_costs(compiled)
    assert costs and "obs.mla" in costs and "obs.moe" in costs
