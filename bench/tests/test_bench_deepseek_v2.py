"""DeepSeek-V2 in the benchmark, on the CPU: a tiny ``deepseek_v2_lite``
configuration serves through ``kinds/serve.py`` to ``correct``; a
reference that renormalises the top-k gates, or skips ``kv_a_layernorm``,
reads not correct; the architecture module's counts at the published
widths; and the two per-layer readers it brings on a synthetic trace."""

import json
import os
import types

import pytest

import common
import flops
from reference import deepseek_v2 as ref

BENCH = common.BENCH
DATA = os.path.join(BENCH, "tests", "data")
SEED = 2**33 + 11


def module():
    return common.model_module({"model_type": "deepseek_v2_lite"})


def tiny_cell():
    with open(os.path.join(DATA, "tiny-dsv2.json")) as f:
        cfg = dict(json.load(f), name="tiny-dsv2")
    with open(os.path.join(DATA, "tiny-closed.json")) as f:
        mix = json.load(f)
    return {"name": "serve.tiny_dsv2", "kind": "serve", "chips": 1,
            "config_data": cfg, "traffic_data": mix,
            "limits": {"max_logit_gap": 1e-3, "sample_tokens": 40}}


def run_tiny(capsys):
    import jax

    from kinds import serve
    cell = tiny_cell()
    args = types.SimpleNamespace(workload=cell["name"], seed=SEED,
                                 seconds=1.5, trace=0)
    capsys.readouterr()
    assert serve.run(cell, args, jax, common.Clock(),
                     {"platform": "cpu", "kind": "TPU v5 lite",
                      "count": 1}) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_tiny_deepseek_v2_serves_correct(capsys):
    res = run_tiny(capsys)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert res["checks"]["tokens_compared"]["value"] >= 40
    assert res["checks"]["max_logit_gap"]["value"] <= 1e-3


def _renormalised(topk_gates):
    return lambda probs, k, norm: topk_gates(probs, k, True)


@pytest.mark.parametrize("fault", ["renormalised_top_k", "no_latent_norm"])
def test_a_reference_that_departs_reads_not_correct(fault, capsys,
                                                    monkeypatch):
    import jax
    if fault == "renormalised_top_k":
        monkeypatch.setattr(ref, "topk_gates", _renormalised(ref.topk_gates))
    else:
        monkeypatch.setattr(ref, "latent_norm", lambda c, scale, eps: c)
    jax.clear_caches()          # the reference's program is traced anew
    try:
        res = run_tiny(capsys)
    finally:
        jax.clear_caches()
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > 1e-3


def _lite():
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v2-lite-8of64.json")) as f:
        data = dict(json.load(f), name="deepseek-v2-lite-8of64")
    return module().model_config(data)


def test_params_are_one_chips_share_at_published_widths():
    """3.11 B parameters: attention 13.76 M a layer, the dense layer's
    67.2 M, 8 held experts of 8.65 M and 2 shared (17.3 M) in each of 26
    MoE layers, the router's 64 outputs, embeddings and head 419 M."""
    import jax
    cfg = _lite()
    shapes = jax.eval_shape(lambda: module().params(cfg, SEED))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    moe = 8 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64
    want = (27 * (attn + 2 * 2048) + 3 * 2048 * 10944 + 26 * moe
            + 2 * 102400 * 2048 + 2048)
    assert n == want
    assert 3.10e9 < n < 3.12e9
    gate = shapes["layers"]["moe"]["gate_proj"]
    assert gate.shape == (26, 8, 2048, 1408)
    assert shapes["layers"]["moe"]["router"]["w"].shape == (26, 2048, 64)


def test_counts_at_published_widths():
    m, cfg = module(), _lite()
    # absorbed decode: 16 heads x (2 x 576 + 2 x 512) a visible position
    assert m.decode_attention_flops(cfg, 99) == 16 * 2176 * 27 * 100
    # routed experts at 6 x 8 / 64 = 0.75 assignments a token
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + 2 * expert + 0.75 * expert
    body = 27 * attn + 3 * 2048 * 10944 + 26 * moe
    assert m.token_matmul_params(cfg) == body + 2048 * 102400
    assert m.decode_flops(cfg, 99) == 2 * (body + 2048 * 102400) \
        + 16 * 2176 * 27 * 100
    assert m.prefill_flops(cfg, 3) == 2 * body * 3 \
        + 2 * 16 * (192 + 128) * 27 * 6 + 2 * 2048 * 102400
    # a lane at position 17 with 16-slot blocks: 2 pages of 576-wide bf16
    # entries and 4 B positions, the 16 x 576 query and 16 x 512 output
    page = 16 * (576 * 2 + 4)
    assert m.paged_decode_bytes(cfg, [17], 16) == \
        27 * (2 * page + 2 * 16 * (576 + 512))


class _Trace:
    """Op events on one device, two programs: the serve step and a
    prefill."""
    devices = {"/device:TPU:0": [
        ("%mla_decode.7 = ... custom-call(...)", 0, 2_000_000),
        ("%fusion.4 = ... fusion(%mla_decode.7)", 2_000_000, 3_000_000),
        ("%ragged-dot-none.1 = ...", 3_000_000, 7_000_000),
        ("%ragged-dot-metadata = ...", 7_000_000, 8_000_000),
        ("%ragged-dot-none.2 = ...", 20_000_000, 30_000_000),
    ]}
    modules = {"/device:TPU:0": [
        ("jit_serve_step(12)", 0, 10_000_000),
        ("jit__prefill(13)", 15_000_000, 40_000_000),
    ]}

    def op_seconds(self, lo, hi, match=None):
        out = {}
        for n, s, e in self.devices["/device:TPU:0"]:
            if match is None or match(n):
                out[n] = out.get(n, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        return out


def _run():
    m = module()
    return {"model": m, "cfg": _lite(), "trace": _Trace(), "lo": 0,
            "hi": 50_000_000, "peak": flops.peaks("TPU v5 lite"),
            "block_size": 16,
            "ticks": [(0.1, [1000, 2000]), (0.2, []), (0.3, [3000])]}


def test_mla_roofline_reads_the_latent_kernel_only():
    """The kernel's own event (2 ms), not the fusion that reads its
    output; memory bound."""
    m, run = module(), _run()
    need = (m.paged_decode_bytes(run["cfg"], [1000, 2000], 16)
            + m.paged_decode_bytes(run["cfg"], [3000], 16)) / 819e9
    got = common.metric_reader("mla_decode_roofline")(run)
    assert got == pytest.approx(100.0 * need / 0.002)


def test_expert_ms_reads_the_serve_steps_grouped_matmuls():
    """5 ms of ``ragged-dot`` inside the serve step over 2 decoding ticks;
    the prefill's 10 ms are left out."""
    got = common.metric_reader("moe.expert_ms_per_tick")(_run())
    assert got == pytest.approx(2.5)
