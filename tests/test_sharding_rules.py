"""Unit tests for the sharding rule tables (pure functions of shapes —
no multi-device runtime needed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import _spec_for_param, _div


MODEL = 16


def _spec(path, shape):
    return _spec_for_param(path, jax.ShapeDtypeStruct(shape, jnp.float32),
                           MODEL)


def test_attention_projections_shard_flat_head_dim():
    assert _spec("/layers/attn/wq/w", (28, 1024, 2048)) == P(None, None, "model")
    assert _spec("/layers/attn/wo/w", (28, 2048, 1024)) == P(None, "model", None)


def test_non_divisible_replicates():
    # 15-head smollm q proj: 960 divides, fine; a 15-dim leaf must replicate
    assert _spec("/layers/attn/wq/w", (32, 960, 960)) == P(None, None, "model")
    assert _spec("/layers/attn/wq/w", (32, 960, 15)) == P()


def test_mlp_shards_hidden():
    assert _spec("/layers/mlp/up/w", (28, 1024, 3072)) == P(None, None, "model")
    assert _spec("/layers/mlp/down/w", (28, 3072, 1024)) == P(None, "model", None)


def test_moe_experts_shard_ffn_not_expert_dim():
    # 60 experts don't divide 16; d_ff=1408 does
    assert _spec("/layers/moe/gate_proj", (24, 60, 2048, 1408)) == \
        P(None, None, None, "model")
    assert _spec("/layers/moe/down_proj", (24, 60, 1408, 2048)) == \
        P(None, None, "model", None)
    assert _spec("/layers/moe/router/w", (24, 2048, 60)) == P()


def test_lora_adapters_replicated():
    """The federated payload must be replicated — cluster aggregation is a
    pure psum (DESIGN.md §5)."""
    assert _spec("/layers/attn/wq/lora_a", (28, 1024, 8)) == P()
    assert _spec("/layers/attn/wq/lora_b", (28, 8, 2048)) == P()


def test_embed_shards_vocab():
    assert _spec("/embed/table", (151936, 1024)) == P("model", None)


def test_norms_replicated():
    assert _spec("/layers/attn_norm/scale", (28, 1024)) == P()


def test_cache_specs_seq_sharded(monkeypatch):
    from repro.dist import sharding as sh

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    cache = {"k": jax.ShapeDtypeStruct((28, 128, 32768, 8, 128),
                                       jnp.bfloat16),
             "kv_pos": jax.ShapeDtypeStruct((28, 128, 32768), jnp.int32)}
    monkeypatch.setenv("REPRO_CACHE_SHARD", "seq")
    specs = sh.cache_specs(cache, FakeMesh())
    # flash-decode layout: batch -> data, seq -> model
    assert specs["k"] == P(None, "data", "model", None, None)
    monkeypatch.setenv("REPRO_CACHE_SHARD", "heads")
    specs = sh.cache_specs(cache, FakeMesh())
    # head dim 8 doesn't divide 16 -> falls through to dh=128
    assert specs["k"] == P(None, "data", None, None, "model")


def test_opt_state_specs_zero1(monkeypatch):
    from repro.dist import sharding as sh

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    params = {"mlp": {"up": {"w": jax.ShapeDtypeStruct((28, 4608, 36864),
                                                       jnp.bfloat16)}}}
    specs = sh.opt_state_specs(params, FakeMesh())
    # base spec shards dim2 over model; ZeRO widens dim1 over data
    assert specs["mlp"]["up"]["w"] == P(None, "data", "model")


def test_data_specs_batch_divisibility():
    from repro.dist import sharding as sh

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = sh.data_specs(batch, FakeMesh())
    assert specs["tokens"] == P(("pod", "data"), None)
    assert specs["pos"] == P()
    # batch=1 (long_500k) cannot shard
    one = {"token": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    assert sh.data_specs(one, FakeMesh())["token"] == P()


_PLACE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.core.lora import attach_lora, lora_tree
from repro.data.tokens import lm_batches, markov_tokens
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_fed_train_step
from repro.launch.train import place_batch, place_state, synth_batch
from repro.models.registry import get_model
from repro.optim.adamw import adamw_init

cfg = get_smoke_config("qwen3-0.6b")
params = attach_lora(get_model(cfg).init(cfg, jax.random.PRNGKey(0)),
                     jax.random.PRNGKey(1), rank=4, alpha=8.0)
opt = adamw_init(lora_tree(params))
it = lm_batches(markov_tokens(5000, cfg.vocab_size), 8, 33)
batch = synth_batch(cfg, 8, 32, it)
step = make_fed_train_step(cfg)
_, _, want = jax.jit(step)(params, opt, batch, np.int32(0))

mesh = make_host_mesh(model=2)
mp, mo, (psh, osh) = place_state(params, opt, mesh, fed=True)
total = sum(x.nbytes for x in jax.tree.leaves(mp))
per_dev = {}
for x in jax.tree.leaves(mp):
    for s in x.addressable_shards:
        per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
assert len(per_dev) == 4 and max(per_dev.values()) < total, per_dev
mb = place_batch(batch, mesh)
assert mb["tokens"].sharding.spec[0] == "data"
with mesh:
    _, _, got = jax.jit(step, out_shardings=(psh, osh, None))(
        mp, mo, mb, np.int32(0))
np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
print("PLACE_OK")
"""


def test_train_launcher_places_state_on_mesh():
    """launch/train.place_state/place_batch spread parameters over a
    (data=2, model=2) mesh — no device holds the whole tree — and the fed
    step's loss on the mesh equals the one-device loss."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _PLACE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "PLACE_OK" in r.stdout, r.stdout + r.stderr
