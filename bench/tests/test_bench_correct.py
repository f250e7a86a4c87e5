"""``correct`` end to end at a size a test run can hold, on the CPU: the
rest of a run (the harness's look for a chip skipped) with a sound timed
path reads correct; with the timed path broken underneath, or with the
int8 control in the program's place, it does not.  The tiny
configurations run in float32, so their limits are float32 rounding."""

import io
import json
import os
import types

import pytest

import calibrate
import common
from kinds import serve

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**33 + 5


def _json(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def serve_cell(mix="tiny-open"):
    return {"name": "serve.shared_prefix", "kind": "serve", "chips": 1,
            "config_data": dict(_json("tiny-lm.json"), name="tiny-lm"),
            "traffic_data": _json(f"{mix}.json"),
            "limits": {"max_logit_gap": 1e-3, "sample_tokens": 40}}


@pytest.fixture(scope="module")
def jax():
    import jax as j
    return j


def run_cell(kind, cell, jax, capsys, seconds):
    args = types.SimpleNamespace(workload=cell["name"], seed=SEED,
                                 seconds=seconds, trace=0)
    capsys.readouterr()
    assert kind.run(cell, args, jax, common.Clock(), dict(DEVICE)) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-closed"])
def test_serve_sound_run_is_correct(jax, capsys, mix):
    res = run_cell(serve, serve_cell(mix), jax, capsys, 1.5)
    assert res["correct"] is True
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "itl_p95_ms"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_logit_gap"]["limit"] == 1e-3
    assert res["checks"]["tokens_compared"]["value"] >= 40
    dev = res["device"]                 # the pool's fill beside its memory
    assert dev["kv_pool_blocks"] == 4 * 128 // 16
    assert 0 < dev["kv_pool_fill_mean"] <= dev["kv_pool_fill_max"] <= 1


def test_too_few_tokens_compared_is_not_correct(jax, capsys):
    """The sample has to cover ``sample_tokens``: a run that cannot finish
    that many reads not correct, whatever its gap."""
    cell = serve_cell()
    cell["limits"] = dict(cell["limits"], sample_tokens=10**6)
    res = run_cell(serve, cell, jax, capsys, 1.0)
    assert res["correct"] is False
    assert res["checks"]["tokens_compared"]["value"] < 10**6
    assert res["checks"]["max_logit_gap"]["value"] <= 1e-3


def test_serve_token_altered_where_produced_is_not_correct(jax, capsys,
                                                           monkeypatch):
    import repro.serve.engine as engine_mod
    make = engine_mod.make_serve_step

    def broken(cfg, **kw):
        step = make(cfg, **kw)

        def serve_step(params, cache, batch):
            tok, ok, cache = step(params, cache, batch)
            return (tok + 1) % cfg.vocab_size, ok, cache
        return serve_step

    monkeypatch.setattr(engine_mod, "make_serve_step", broken)
    res = run_cell(serve, serve_cell(), jax, capsys, 1.5)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > 1e-3


def test_serve_control_is_not_correct(jax):
    out = io.StringIO()
    calibrate.serve_seed(serve_cell(), SEED, 1.0, True, out)
    rows = {r["side"]: r for r in map(json.loads, out.getvalue().splitlines())}
    assert rows["program"]["max_logit_gap"] <= 1e-3
    assert rows["control"]["max_logit_gap"] > 1e-3
