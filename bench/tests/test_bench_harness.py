"""The benchmark's arithmetic, its generator, its files and its refusal to
run without a chip — all on the CPU, without the program's hot path."""

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


def test_harness_finds_added_files_by_name(tmp_path, monkeypatch):
    """A new configuration, traffic mix, cell and per-layer metric are
    files under bench/ and nothing else."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = common.benchmark_spec()
    b = root / "bench"
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
    monkeypatch.setattr(common, "BENCH", str(b))
    monkeypatch.setattr(common, "ROOT", str(root))
    cell = common.load_workload("serve.added")
    assert cell["config_data"]["engine"]["num_slots"] == 8
    assert cell["traffic_data"]["clients"] == 4
    assert common.per_layer_names(common.benchmark_spec(), "serve.added") \
        == ["serve.added_metric"]
    assert common.metric_reader("serve.added_metric")({}) == 42.0


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


class _Cfg:
    """Shapes of a small decoder for hand counts."""
    num_layers, d_model, num_heads, num_kv_heads = 2, 8, 2, 1
    d_ff, vocab_size, activation = 16, 10, "swiglu"

    @staticmethod
    def resolved_head_dim():
        return 4


def test_layer_params_hand_count():
    # q 8x8, k 8x4, v 8x4, o 8x8; gate/up 8x16, down 16x8
    assert flops.layer_matmul_params(_Cfg) == (64 + 32 + 32 + 64, 384)


def test_serve_flops_hand_count():
    # prompt of 3: 2 FLOPs per weight per token, 6 causal pairs, one logit row
    assert flops.prefill_flops(_Cfg, 3) == \
        2 * 2 * 576 * 3 + 4 * 2 * 4 * 2 * 6 + 2 * 8 * 10
    # token at position 4 sees 5 keys
    assert flops.decode_flops(_Cfg, 4) == \
        2 * 2 * 576 + 2 * 8 * 10 + 4 * 2 * 4 * 2 * 5


def test_paged_decode_bytes_hand_count():
    # block 4; lanes at positions 0 and 5 need 1 and 2 blocks; a block is
    # 4 slots x (K and V: 1 head x 4 dims x 2 B, and a 4 B position)
    per_block = 4 * (2 * 1 * 4 * 2 + 4)
    q_out = 2 * 2 * 4 * 2
    want = 2 * ((1 + 2) * per_block + 2 * q_out)
    assert flops.paged_decode_bytes(_Cfg, [0, 5], 4) == want


def test_roofline_share_never_reads_zero_for_want_of_time():
    assert flops.share(1.0, 0.0) is None
    t, bound = flops.roofline_seconds(0.0, 819e9, flops.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(1.0)
    assert flops.share(t, 2.0) == pytest.approx(50.0)
