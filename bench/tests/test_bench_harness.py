"""The benchmark's arithmetic, its generator, its files and its refusal to
run without a chip — all on the CPU, without the program's hot path."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import common
import flops
from traffic import gen

BENCH = common.BENCH
NAME_OK = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
              "0123456789_.-")


def test_rate_is_all_work_over_the_whole_window():
    # three bursts of work in a 10 s window: the rate counts all of it over
    # all 10 s, not the busy part only
    assert common.rate(30 + 20 + 50, 10.0) == 10.0
    with pytest.raises(ValueError):
        common.rate(1, 0.0)


def test_percentile_is_over_all_requests():
    xs = list(range(1, 101))                       # 100 requests
    assert common.percentile(xs, 90) == pytest.approx(np.percentile(xs, 90))
    assert common.percentile(xs, 95) == pytest.approx(np.percentile(xs, 95))
    # one more slow request moves the tail: every request counts
    assert common.percentile(xs + [10_000], 95) > common.percentile(xs, 95)
    assert common.percentile([], 90) != common.percentile([], 90)   # nan


@pytest.mark.parametrize("mix", ["shared_prefix", "short_closed"])
def test_generator_is_deterministic_for_a_seed(mix):
    m = common.load_json("traffic", mix)
    big = 3_000_000_019                            # past 32 signed bits
    a = gen.serve_requests(m, big, 151_936, 30.0)
    b = gen.serve_requests(m, big, 151_936, 30.0)
    c = gen.serve_requests(m, 7, 151_936, 30.0)
    assert [r["id"] for r in a] == [r["id"] for r in b]
    for x, y in zip(a, b):
        assert np.array_equal(x["prompt"], y["prompt"])
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert x.get("due_s") == y.get("due_s")
    # another seed: other tokens, the same sizes in the same order
    assert not all(np.array_equal(x["prompt"], y["prompt"])
                   for x, y in zip(a, c))
    assert [r["max_new_tokens"] for r in a] == \
        [r["max_new_tokens"] for r in c]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in c]
    assert [r.get("repeat_of") for r in a] == [r.get("repeat_of") for r in c]
    if m["loop"] == "open":
        assert len(a) == round(m["rate_per_s"] * 30.0)
        assert [r["due_s"] for r in a] == [r["due_s"] for r in c]
        assert 0 <= a[0]["due_s"] and a[-1]["due_s"] < 30.0
    else:
        block = m["size_block"]
        assert sorted(r["max_new_tokens"] for r in a[:block]) == \
            sorted(r["max_new_tokens"] for r in a[block:2 * block])
    for r in a:
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 151_936


def test_shared_prefix_mix_shares_and_repeats():
    m = common.load_json("traffic", "shared_prefix")
    reqs = gen.serve_requests(m, 5, 1000, 400.0)
    pre = m["shared_prefix"]["preamble_tokens"]
    rep = [r for r in reqs if r["repeat_of"] is not None]
    assert 0.15 < len(rep) / len(reqs) < 0.35
    for r in rep:
        assert np.array_equal(r["prompt"], reqs[r["repeat_of"]]["prompt"])
    by_t = {}
    for r in reqs:
        by_t.setdefault(r["tenant"], []).append(r["prompt"][:pre])
    for ps in by_t.values():
        assert all(np.array_equal(p, ps[0]) for p in ps)
    lens = [len(r["prompt"]) for r in reqs]
    assert min(lens) >= pre + m["prompt"]["min"]
    assert max(lens) <= pre + m["prompt"]["max"]
    buckets = gen.prefill_buckets(m, 256)
    assert {-(-n // 256) * 256 for n in lens} <= set(buckets)


def test_every_cell_file_loads():
    spec = common.benchmark_spec()
    assert spec["paths"] == ["bench"]
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        cell = common.load_workload(w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert os.path.exists(os.path.join(BENCH, "kinds",
                                           f"{cell['kind']}.py"))
        assert cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(common.ROOT,
                                           configs[w["config"]]["file"]))
        assert cell["limits"]
        e2e = common.end_to_end_names(spec, w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = common.per_layer_names(spec, w["name"])
        assert layer
        for n in layer:
            assert callable(common.metric_reader(n))
            moves = next(m["moves"] for m in spec["per_layer"]
                         if m["name"] == n)
            assert moves in e2e
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m["name"]) <= NAME_OK
    for c in spec["configs"]:
        data = json.load(open(os.path.join(common.ROOT, c["file"])))
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]


def test_every_configuration_serves_a_cell():
    spec = common.benchmark_spec()
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)


def test_sample_covers_the_tokens_and_holds_the_longest():
    from kinds import serve

    class F:
        def __init__(self, n):
            self.tokens = [0] * n
    by_id = {f"r{i}": {"prompt": [0] * (10 + i)} for i in range(8)}
    finished = {f"r{i}": F(5) for i in range(8)}
    picked = serve.sample(finished, by_id, 3_000_000_019, 12)
    assert picked[0] == "r7"                       # longest prompt + output
    assert sum(len(finished[i].tokens) for i in picked) >= 12
    assert len(picked) == 3
    assert picked == serve.sample(finished, by_id, 3_000_000_019, 12)
    assert serve.sample(finished, by_id, 1, 10**6) != []


def _bench_copy(tmp_path, monkeypatch):
    """A copy of bench/ under ``tmp_path`` that the harness reads in place
    of this one: (root, bench, spec), ``spec`` this BENCHMARK.json for the
    caller to extend and write to ``root``."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = common.benchmark_spec()
    monkeypatch.setattr(common, "BENCH", str(root / "bench"))
    monkeypatch.setattr(common, "ROOT", str(root))
    return root, root / "bench", spec


def _digests(top):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in top.rglob("*") if p.is_file()}


def test_harness_finds_added_files_by_name(tmp_path, monkeypatch):
    """A new configuration, traffic mix, cell and per-layer metric are
    files under bench/ and nothing else."""
    root, b, spec = _bench_copy(tmp_path, monkeypatch)
    cfg = json.load(open(b / "configs" / "qwen3-0.6b.json"))
    json.dump(dict(cfg, engine=dict(cfg["engine"], num_slots=8)),
              open(b / "configs" / "qwen3-0.6b-8slot.json", "w"))
    mix = json.load(open(b / "traffic" / "short_closed.json"))
    json.dump(dict(mix, clients=4), open(b / "traffic" / "tiny_closed.json",
                                         "w"))
    json.dump({"config": "qwen3-0.6b-8slot", "traffic": "tiny_closed",
               "kind": "serve", "chips": 1,
               "limits": {"max_logit_gap": 1.0, "sample_tokens": 10}},
              open(b / "workloads" / "serve.added.json", "w"))
    (b / "metrics" / "serve.added_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec["workloads"].append({"name": "serve.added",
                              "config": "qwen3-0.6b-8slot",
                              "traffic": "tiny_closed", "chips": 1,
                              "why": "added"})
    spec["per_layer"].append({"name": "serve.added_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "itl_p95_ms",
                              "workloads": ["serve.added"]})
    json.dump(spec, open(root / "BENCHMARK.json", "w"))
    cell = common.load_workload("serve.added")
    assert cell["config_data"]["engine"]["num_slots"] == 8
    assert cell["traffic_data"]["clients"] == 4
    assert common.per_layer_names(common.benchmark_spec(), "serve.added") \
        == ["serve.added_metric"]
    assert common.metric_reader("serve.added_metric")({}) == 42.0


# A second architecture, Qwen3 with an untied output head: the program
# serves it (``lm_head``), the qwen3 module cannot make its weights.
UNTIED_MODEL = '''"""Qwen3 with an untied output head ``lm_head``."""
import jax

import common
from reference import qwen3_untied as ref
from weights import _dense, seed_key

base = common.model_module({"model_type": "qwen3"})
model_config = base.model_config
prefill_flops, decode_flops = base.prefill_flops, base.decode_flops
decode_attention_flops = base.decode_attention_flops
paged_decode_bytes = base.paged_decode_bytes


def params(cfg, seed):
    @jax.jit
    def make_head(key):
        return {"w": _dense(jax.random.fold_in(key, 1),
                            (cfg.d_model, cfg.vocab_size), cfg.param_dtype)}
    return dict(base.params(cfg, seed), lm_head=make_head(seed_key(seed)))


def logits_at(params, data, tokens, rows, *, int8=False):
    return ref.logits_at(params, data, tokens, rows, int8=int8)
'''

UNTIED_REFERENCE = '''"""Plain float32 Qwen3 with an untied output head."""
import functools

import jax
import jax.numpy as jnp

from reference import qwen3 as q


@functools.partial(jax.jit, static_argnames=("cfg_items", "int8"))
def _logits_at(params, tokens, rows, cfg_items, int8):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = f32["embed"]["table"][tokens]
        x, _ = jax.lax.scan(
            lambda h, lp: (q.block(h, lp, cfg, int8), None), x, f32["layers"])
        x = q.rms(x[rows], f32["final_norm"]["scale"], cfg["rms_norm_eps"])
        return q.matmul(x, HEAD, int8)


def logits_at(params, cfg, tokens, rows, *, int8=False):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return _logits_at(params, tokens, rows,
                      tuple((k, cfg[k]) for k in keys), int8)
'''


def test_an_architecture_enters_by_files_alone(tmp_path, monkeypatch,
                                               capsys):
    """A model_type the harness had no module for: its module, its
    reference, a configuration and a cell, all new files, serve to
    ``correct`` on the CPU; the same run against a reference that ties the
    head reads not correct; no file that was there changes."""
    import types

    import jax

    from kinds import serve
    root, b, spec = _bench_copy(tmp_path, monkeypatch)
    before = _digests(root)
    data = json.load(open(os.path.join(BENCH, "tests", "data",
                                       "tiny-lm.json")))
    json.dump(dict(data, model_type="qwen3_untied",
                   tie_word_embeddings=False,
                   reduced=data["reduced"] + ["tie_word_embeddings"]),
              open(b / "configs" / "tiny-untied.json", "w"))
    shutil.copy(os.path.join(BENCH, "tests", "data", "tiny-closed.json"),
                b / "traffic" / "tiny_closed.json")
    json.dump({"config": "tiny-untied", "traffic": "tiny_closed",
               "kind": "serve", "chips": 1,
               "limits": {"max_logit_gap": 1e-3, "sample_tokens": 40}},
              open(b / "workloads" / "serve.untied.json", "w"))
    spec["workloads"].append({"name": "serve.untied",
                              "config": "tiny-untied",
                              "traffic": "tiny_closed", "chips": 1,
                              "why": "untied head"})
    json.dump(spec, open(root / "BENCHMARK.json", "w"))
    (b / "models" / "qwen3_untied.py").write_text(UNTIED_MODEL)
    ref = b / "reference" / "qwen3_untied.py"
    monkeypatch.syspath_prepend(str(b))

    def run(head):
        ref.write_text(UNTIED_REFERENCE.replace("HEAD", head))
        # the next import reads the file as it now is
        monkeypatch.delitem(sys.modules, "reference.qwen3_untied",
                            raising=False)
        if "reference" in sys.modules:
            monkeypatch.delattr(sys.modules["reference"], "qwen3_untied",
                                raising=False)
        cell = common.load_workload("serve.untied")
        args = types.SimpleNamespace(workload=cell["name"], seed=2**33 + 7,
                                     seconds=1.5, trace=0)
        capsys.readouterr()
        assert serve.run(cell, args, jax, common.Clock(),
                         {"platform": "cpu", "kind": "TPU v5 lite",
                          "count": 1}) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    sound = run('f32["lm_head"]["w"]')
    assert sound["correct"] is True
    assert sound["checks"]["tokens_compared"]["value"] >= 40
    tied = run('f32["embed"]["table"].T')
    assert tied["correct"] is False
    assert tied["checks"]["max_logit_gap"]["value"] > 1e-3
    after = _digests(root)
    assert {p: after.get(p) for p in before} == before


def test_a_model_type_without_a_module_is_an_error():
    with pytest.raises(FileNotFoundError) as e:
        common.model_module({"model_type": "deepseek_v2"})
    assert os.path.join(BENCH, "models", "deepseek_v2.py") in str(e.value)
    assert "qwen3" in str(e.value)


def test_a_run_without_a_tpu_fails_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "serve.short_closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr


def test_peaks_are_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def qwen3():
    return common.model_module({"model_type": "qwen3"})


class _Cfg:
    """Shapes of a small decoder for hand counts."""
    num_layers, d_model, num_heads, num_kv_heads = 2, 8, 2, 1
    d_ff, vocab_size, activation = 16, 10, "swiglu"

    @staticmethod
    def resolved_head_dim():
        return 4


def test_layer_params_hand_count():
    # q 8x8, k 8x4, v 8x4, o 8x8; gate/up 8x16, down 16x8
    assert qwen3().layer_matmul_params(_Cfg) == (64 + 32 + 32 + 64, 384)


def test_serve_flops_hand_count():
    m = qwen3()
    # prompt of 3: 2 FLOPs per weight per token, 6 causal pairs, one logit row
    assert m.prefill_flops(_Cfg, 3) == \
        2 * 2 * 576 * 3 + 4 * 2 * 4 * 2 * 6 + 2 * 8 * 10
    # token at position 4 sees 5 keys
    assert m.decode_flops(_Cfg, 4) == \
        2 * 2 * 576 + 2 * 8 * 10 + 4 * 2 * 4 * 2 * 5
    assert m.decode_attention_flops(_Cfg, 4) == 4 * 2 * 4 * 2 * 5


def test_paged_decode_bytes_hand_count():
    # block 4; lanes at positions 0 and 5 need 1 and 2 blocks; a block is
    # 4 slots x (K and V: 1 head x 4 dims x 2 B, and a 4 B position)
    per_block = 4 * (2 * 1 * 4 * 2 + 4)
    q_out = 2 * 2 * 4 * 2
    want = 2 * ((1 + 2) * per_block + 2 * q_out)
    assert qwen3().paged_decode_bytes(_Cfg, [0, 5], 4) == want


def _config(name, path=None):
    path = path or os.path.join(BENCH, "configs", f"{name}.json")
    with open(path) as f:
        return dict(json.load(f), name=name)


# The counts at Qwen3-0.6B's own widths as bench/flops.py gave them before
# they moved into bench/models/qwen3.py.
@pytest.mark.parametrize("count,args,want", [
    ("prefill_flops", (2048,), 2285468647424.0),
    ("decode_flops", (4095,), 2131492864.0),
    ("decode_attention_flops", (4095,), 939524096.0),
    ("paged_decode_bytes", ([0, 1000, 4095], 16), 588464128.0),
])
def test_qwen3_counts_at_published_widths(count, args, want):
    m = qwen3()
    cfg = m.model_config(_config("qwen3-0.6b"))
    assert getattr(m, count)(cfg, *args) == want


class _Trace:
    def op_seconds(self, lo, hi, match=None):
        ops = {"flash_decode_paged": 0.004, "fusion.3": 1.0}
        return {k: v for k, v in ops.items() if match is None or match(k)}


# Readings of a synthetic window at Qwen3-0.6B's widths, as the readers
# gave them when they counted through bench/flops.py.
@pytest.mark.parametrize("metric,want", [
    ("serve.mfu", 0.8347070651126904),
    ("flash_decode_roofline", 21.565264957264954),
])
def test_count_readers_read_as_before(metric, want):
    m = qwen3()
    run = {"model": m, "cfg": m.model_config(_config("qwen3-0.6b")),
           "trace": _Trace(), "lo": 0, "hi": 1, "window_s": 2.0,
           "peak": flops.peaks("TPU v5 lite"), "block_size": 16,
           "ticks": [(0.1, [0, 1000, 4095]), (0.2, []), (0.3, [1, 1001])],
           "admits": [(0.05, 2048, 2048, 0), (0.15, 1024, 0, 1024),
                      (0.25, 3000, 1000, 2000)]}
    assert common.metric_reader(metric)(run) == want


# Each leaf's exact sum of tiny-lm's weights at one seed, as
# bench/weights.py made them before they moved into bench/models/qwen3.py.
TINY_LM_SUMS = {
    "['embed']['table']": ((512, 64), 3.2400034738811314),
    "['final_norm']['scale']": ((64,), 64.33971786499023),
    "['layers']['attn']['k_norm']['scale']": ((2, 16), 31.64985716342926),
    "['layers']['attn']['q_norm']['scale']": ((2, 16), 31.762085139751434),
    "['layers']['attn']['wk']['w']": ((2, 64, 32), -0.1306711313627602),
    "['layers']['attn']['wo']['w']": ((2, 64, 64), 26.963043638881118),
    "['layers']['attn']['wq']['w']": ((2, 64, 64), -9.683272932973523),
    "['layers']['attn']['wv']['w']": ((2, 64, 32), -4.449179310358886),
    "['layers']['attn_norm']['scale']": ((2, 64), 130.02460032701492),
    "['layers']['mlp']['down']['w']": ((2, 128, 64), -6.538345231214407),
    "['layers']['mlp']['gate']['w']": ((2, 64, 128), 10.201201989643778),
    "['layers']['mlp']['up']['w']": ((2, 64, 128), -19.207872173670694),
    "['layers']['mlp_norm']['scale']": ((2, 64), 128.24915379285812),
}


def test_qwen3_params_fingerprint():
    import math

    import jax
    m = qwen3()
    cfg = m.model_config(_config(
        "tiny-lm", os.path.join(BENCH, "tests", "data", "tiny-lm.json")))
    leaves = jax.tree_util.tree_flatten_with_path(m.params(cfg, 2**33 + 5))[0]
    got = {jax.tree_util.keystr(path): (leaf.shape, math.fsum(
               np.asarray(leaf, np.float64).ravel()))
           for path, leaf in leaves}
    assert {k: s for k, (s, _) in got.items()} == \
        {k: s for k, (s, _) in TINY_LM_SUMS.items()}
    for k, (_, total) in TINY_LM_SUMS.items():
        assert got[k][1] == pytest.approx(total, rel=0, abs=1e-6), k


def test_roofline_share_never_reads_zero_for_want_of_time():
    assert flops.share(1.0, 0.0) is None
    t, bound = flops.roofline_seconds(0.0, 819e9, flops.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(1.0)
    assert flops.share(t, 2.0) == pytest.approx(50.0)
