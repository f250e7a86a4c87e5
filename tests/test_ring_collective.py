"""Federated communication fast path (repro.kernels.ring_allreduce +
repro.dist.fedcomm): psum parity, wire formats, error feedback, and the
three-way byte agreement.

Multi-device cases run in subprocesses (like test_paged_pool) because the
emulated device count must be set before jax initializes; the scripts
inherit REPRO_FORCE_KERNELS so the CI interpret job drives the Pallas
fused-hop kernel, not just its jnp oracle.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm
from repro.dist import fed, fedcomm

_ENV_KEYS = ("REPRO_FED_WIRE", "REPRO_FED_QBLOCK", "REPRO_FED_RING",
             "REPRO_CACHE_SHARD")


def _run_sub(script: str, timeout: int = 900, **env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    for k in _ENV_KEYS:
        env.pop(k, None)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


# ---------------------------------------------------------------------------
# byte accounting: one number, three ways
# ---------------------------------------------------------------------------

def test_ring_wire_plan_f32_matches_classic_formula():
    """On a divisible f32 payload the exact chunk plan reduces to the
    textbook 2·P·(n-1)/n."""
    # 1024 elems over n=4: 2n=8 chunks of 128, no padding
    P = 1024 * 4
    assert comm.ring_wire_bytes(1024, 4, "f32") == int(2 * P * 3 / 4)
    assert comm.ring_wire_bytes(1024, 1, "f32") == 0


def test_ring_wire_plan_padding_is_counted():
    """Non-divisible payloads pay their real padding — no silent float
    truncation (the old int(2·P·(n-1)/n) would round DOWN)."""
    plan = comm.ring_wire_plan(1000, 16, "f32")
    assert plan.chunk_elems == 32          # ceil(1000 / 32)
    assert plan.per_device_bytes == 60 * 32 * 4
    assert plan.per_device_bytes >= int(2 * 4000 * 15 / 16)


def test_ring_wire_plan_int8_scale_bytes():
    plan = comm.ring_wire_plan(1 << 20, 8, "int8", qblock=128)
    c = plan.chunk_elems
    assert c % 128 == 0
    assert plan.scale_bytes == 4 * (c // 128)
    assert plan.code_bytes == c
    # scale overhead keeps the int8 wire under the 0.27x acceptance bound
    f32 = comm.ring_wire_bytes(1 << 20, 8, "f32")
    assert plan.per_device_bytes / f32 <= 0.27


def test_fed_ring_allreduce_bytes_wraps_plan():
    # payload_bytes -> f32 elems -> exact plan
    assert fed.ring_allreduce_bytes(4096, 4) == \
        comm.ring_wire_bytes(1024, 4, "f32")
    assert fed.ring_allreduce_bytes(4096, 4, wire="int8") == \
        comm.ring_wire_bytes(1024, 4, "int8")
    assert fed.ring_allreduce_bytes(1000, 1) == 0


def test_wire_payload_bytes():
    assert comm.wire_payload_bytes(1000, "f32") == 4000
    assert comm.wire_payload_bytes(1000, "bf16") == 2000
    assert comm.wire_payload_bytes(1000, "int8", qblock=128) == \
        1000 + 4 * 8   # ceil(1000/128) = 8 scale blocks
    with pytest.raises(ValueError):
        comm.wire_payload_bytes(10, "fp4")


@pytest.mark.parametrize("wire", comm.WIRE_FORMATS)
def test_expected_equals_accounted_per_wire(wire):
    """fed.expected_collective_bytes == comm.collective_bytes_per_round for
    every wire format (ways one and two of the three-way agreement; the
    kernel ledger is way three, measured on the emulated mesh below)."""
    from repro.configs import get_smoke_config
    from repro.core.lora import attach_lora
    from repro.models.registry import get_model

    cfg = get_smoke_config("qwen3-0.6b")

    def build(key):
        p = get_model(cfg).init(cfg, key)
        return attach_lora(p, key, rank=cfg.fedtime.lora_rank,
                           alpha=cfg.fedtime.lora_alpha)

    params = jax.eval_shape(build, jax.ShapeDtypeStruct((2,), jnp.uint32))
    shape = {"pod": 2, "data": 16, "model": 16}
    assert fed.expected_collective_bytes(params, shape, wire=wire) == \
        comm.collective_bytes_per_round(params, shape, wire=wire)


def test_fedtime_round_int8_shrinks(monkeypatch):
    from repro.configs import get_smoke_config
    from repro.core import fedtime
    from repro.core.lora import attach_lora

    cfg = get_smoke_config("fedtime-llama2-7b")
    p = fedtime.init(cfg, jax.random.PRNGKey(0), num_channels=3)
    p = attach_lora(p, jax.random.PRNGKey(1), rank=4, alpha=8.0)
    f32 = comm.fedtime_round(p, clients_per_round=4, num_clusters=2)
    i8 = comm.fedtime_round(p, clients_per_round=4, num_clusters=2,
                            wire="int8")
    assert i8.megabytes < 0.27 * f32.megabytes
    # env-driven default
    monkeypatch.setenv("REPRO_FED_WIRE", "int8")
    assert comm.fedtime_round(p, clients_per_round=4,
                              num_clusters=2).bytes_up == i8.bytes_up


# ---------------------------------------------------------------------------
# the ring itself (emulated meshes, subprocess)
# ---------------------------------------------------------------------------

_RING_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.comm import ring_wire_plan
from repro.dist import fed, fedcomm

MESHES = [jax.make_mesh((8, 1), ("data", "model")),
          jax.make_mesh((2, 2, 2), ("pod", "data", "model"))]
rng = np.random.default_rng(0)
for mesh in MESHES:
    axes = fed.aggregation_axes(mesh)
    n = 16                                         # members (divides both)
    # E = 610 elems / member: not divisible by 2n for any fed axis size
    members = {"wq": {"lora_a": None, "lora_b": None}}
    ints = rng.integers(-8, 9, (n, 5, 61, 2)).astype(np.float32)
    members["wq"]["lora_a"] = jnp.asarray(ints)
    members["wq"]["lora_b"] = jnp.asarray(
        rng.integers(-8, 9, (n,**SHAPE_B**)).astype(np.float32))
    w_int = jnp.ones((n,), jnp.float32)            # integer-exact weights
    exact = jax.tree.map(
        lambda a: np.tensordot(np.ones(n, np.float32), np.asarray(a),
                               axes=1), members)
    with mesh:
        # f32 wire: BIT-EXACT against psum (integer payload: any summation
        # order is exact in f32, so equality is robust)
        ring = fedcomm.ring_aggregate(members, w_int, mesh, wire="f32")
        os.environ["REPRO_FED_RING"] = "0"
        psum = fed.aggregate_adapters(members, w_int, mesh)
        del os.environ["REPRO_FED_RING"]
        for k in ("lora_a", "lora_b"):
            assert np.array_equal(np.asarray(ring["wq"][k]),
                                  np.asarray(psum["wq"][k])), (mesh, k)
            assert np.array_equal(np.asarray(ring["wq"][k]),
                                  exact["wq"][k]), (mesh, k)

        # weighted float aggregation, every wire
        wf = jnp.asarray(rng.random(n).astype(np.float32))
        wf = wf / wf.sum()
        want = jax.tree.map(
            lambda a: np.tensordot(np.asarray(wf), np.asarray(a), axes=1),
            members)
        for wire, tol in (("f32", 1e-6), ("bf16", 5e-2), ("int8", 0.3)):
            ledger = []
            out = fedcomm.ring_aggregate(members, wf, mesh, wire=wire,
                                         byte_ledger=ledger)
            for k in ("lora_a", "lora_b"):
                np.testing.assert_allclose(np.asarray(out["wq"][k]),
                                           want["wq"][k], atol=tol,
                                           err_msg=f"{wire} {k}")
            # way three of the byte agreement: the ledger records the
            # actual nbytes of every ppermute'd buffer at trace time
            E = sum(l.size // n for l in jax.tree.leaves(members))
            per_axis = {}
            for ax, b in ledger:
                per_axis[ax] = per_axis.get(ax, 0) + b
            shape = dict(mesh.shape)
            expected = fed.expected_collective_bytes(
                {"wq": {k: jax.ShapeDtypeStruct((E // 2,), jnp.float32)
                        for k in ("lora_a", "lora_b")}}, mesh, wire=wire)
            for ax in axes:
                plan = ring_wire_plan(E, shape[ax], wire)
                assert per_axis[ax] == plan.per_device_bytes, (wire, ax)
                assert per_axis[ax] == expected[ax], (wire, ax)
print("RING_PARITY_OK")
"""


def test_ring_psum_parity_and_byte_ledger():
    """f32 ring == psum bit-exact; weighted aggregation on every wire; the
    kernel's measured per-hop bytes == plan == expected_collective_bytes,
    per axis, on single- and multi-axis (pod) meshes."""
    out = _run_sub(_RING_PARITY.replace("**SHAPE_B**", "2, 61, 5"))
    assert "RING_PARITY_OK" in out


_RING_EF = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.dist import fedcomm

mesh = jax.make_mesh((4, 1), ("data", "model"))
rng = np.random.default_rng(3)
n = 4
members = {"a": jnp.asarray(rng.normal(size=(n, 777)).astype(np.float32))}
w = jnp.full((n,), 1.0 / n)
exact = np.asarray(members["a"]).mean(axis=0)

with mesh:
    # one-shot (no residual): a fixed quantization bias
    one = fedcomm.ring_aggregate(members, w, mesh, wire="int8")
    bias_one = float(np.abs(np.asarray(one["a"]) - exact).mean())

    # carried error feedback: the time-average converges to the true mean
    st = fedcomm.init_state(members, mesh, wire="int8")
    acc = np.zeros_like(exact)
    R = 24
    for r in range(R):
        out, st = fedcomm.ring_aggregate(members, w, mesh, wire="int8",
                                         state=st)
        acc += np.asarray(out["a"])
bias_ef = float(np.abs(acc / R - exact).mean())
print("bias one-shot", bias_one, "bias EF", bias_ef)
assert bias_ef < 0.35 * bias_one, (bias_ef, bias_one)
print("RING_EF_OK")
"""


def test_error_feedback_debiases_ring_rounds():
    """Carried EF residual: the running average of int8-wire rounds
    converges to the exact aggregate, while one-shot quantization keeps a
    fixed bias — Algorithm 1 stays unbiased on the quantized wire."""
    out = _run_sub(_RING_EF)
    assert "RING_EF_OK" in out


def test_quantize_update_host_path():
    """The host-loop wire emulation (fed_trainer's client upload): f32 is
    the identity, int8 round-trips within absmax precision, and the carried
    residual drives the time-averaged delivery to the true delta."""
    rng = np.random.default_rng(1)
    tree = {"x": jnp.asarray(rng.normal(size=(13, 7)).astype(np.float32)),
            "y": jnp.asarray(rng.normal(size=(5,)).astype(np.float32))}

    same, res = fedcomm.quantize_update(tree, None, wire="f32")
    assert same is tree and res is None

    dq, res = fedcomm.quantize_update(tree, None, wire="int8")
    for k in tree:
        np.testing.assert_allclose(np.asarray(dq[k]), np.asarray(tree[k]),
                                   atol=0.05)
    one_bias = max(float(np.abs(np.asarray(dq[k]) -
                                np.asarray(tree[k])).mean()) for k in tree)

    acc = {k: np.zeros(tree[k].shape, np.float32) for k in tree}
    res, R = None, 16
    for _ in range(R):
        dq, res = fedcomm.quantize_update(tree, res, wire="int8")
        for k in tree:
            acc[k] += np.asarray(dq[k])
    ef_bias = max(float(np.abs(acc[k] / R - np.asarray(tree[k])).mean())
                  for k in tree)
    assert ef_bias < 0.5 * one_bias, (ef_bias, one_bias)


def test_fed_trainer_int8_wire_runs():
    """federated_fit on the int8 wire: losses stay finite, comm is metered
    at wire prices (< 0.27x the f32 meter), residuals are carried."""
    from repro.configs import get_smoke_config
    from repro.data.federated import client_windows, partition_clients
    from repro.data.timeseries import (DATASETS, generate, train_test_split)
    from repro.train.fed_trainer import federated_fit

    cfg = get_smoke_config("fedtime-llama2-7b")
    series = generate(DATASETS["etth1"], timesteps=1200, seed=0)
    train, _ = train_test_split(series)
    clients = partition_clients(train, cfg.fedtime.num_clients, seed=0,
                                channels_per_client=2)
    cdata = client_windows(clients, cfg.fedtime.lookback,
                           cfg.fedtime.horizon, max_windows=24)
    res32 = federated_fit(cfg, cdata, rounds=1, batch_size=4)
    res8 = federated_fit(cfg, cdata, rounds=1, batch_size=4, wire="int8")
    assert all(np.isfinite(l.train_loss) for l in res8.logs)
    assert res8.total_megabytes() < 0.27 * res32.total_megabytes()
