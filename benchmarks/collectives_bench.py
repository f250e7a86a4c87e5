"""Collectives microbench — the federated communication fast path.

Ring vs XLA psum at matched payload, per wire format: per-device bytes per
aggregation round (the kernel's measured byte ledger — identical to the
``ring_wire_plan`` accounting) and wall time per round on an emulated
8-way CPU data mesh.  The headline number: the int8 wire moves <= 0.27x
the bytes of the f32 psum baseline.  It runs in a CPU-pinned subprocess
(the emulated device count must be set before jax initializes, and the
child must never compete with its parent for an accelerator).

``benchmarks/run.py --only collectives`` writes the rows to
``BENCH_collectives.json`` (the per-PR comm-perf trajectory artifact).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_SUB = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.pop("REPRO_FED_WIRE", None)
os.environ.pop("REPRO_FED_RING", None)
import jax, jax.numpy as jnp, numpy as np
from repro.core.comm import ring_wire_plan
from repro.dist import fed, fedcomm

FULL = __FULL__
E = (1 << 22) if FULL else (1 << 20)          # payload elems per member
ITERS = 5
mesh = jax.make_mesh((8, 1), ("data", "model"))
ndev = 8
rng = np.random.default_rng(0)
n = 8
members = {"lora_a": jnp.asarray(rng.normal(size=(n, E)).astype(np.float32))}
w = jnp.full((n,), 1.0 / n)
exact = np.asarray(members["lora_a"]).mean(axis=0)


def timed(f):
    f()                                        # compile
    ts = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6          # us


rows = []
with mesh:
    # --- XLA psum baseline (f32; assumed ring lowering => classic bytes)
    os.environ["REPRO_FED_RING"] = "0"
    us = timed(lambda: fed.aggregate_adapters(members, w, mesh))
    del os.environ["REPRO_FED_RING"]
    f32_psum_bytes = ring_wire_plan(E, ndev, "f32").per_device_bytes
    rows.append({"case": "psum_xla", "wire": "f32",
                 "bytes_per_round": f32_psum_bytes, "us_per_round": us,
                 "bytes_vs_f32_psum": 1.0})

    # --- hand-rolled bidirectional ring, every wire format
    for wire in ("f32", "bf16", "int8"):
        ledger = []
        out = fedcomm.ring_aggregate(members, w, mesh, wire=wire,
                                     byte_ledger=ledger)
        measured = sum(b for _, b in ledger)
        plan = ring_wire_plan(E, ndev, wire)
        assert measured == plan.per_device_bytes, (wire, measured, plan)
        err = float(np.abs(np.asarray(out["lora_a"]) - exact).max())
        us = timed(lambda: fedcomm.ring_aggregate(members, w, mesh,
                                                  wire=wire))
        rows.append({"case": "ring", "wire": wire,
                     "bytes_per_round": measured, "us_per_round": us,
                     "bytes_vs_f32_psum": measured / f32_psum_bytes,
                     "max_abs_err": err})

for r in rows:
    print("ROW " + json.dumps(r), flush=True)
"""


def run(full: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SUB.replace("__FULL__", str(full))],
        env=env, capture_output=True, text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"collectives subprocess failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("ROW "):
            rows.append(emit("collectives", **json.loads(line[4:])))
    int8 = next(x for x in rows if x.get("case") == "ring"
                and x.get("wire") == "int8")
    rows.append(emit(
        "collectives_summary",
        int8_vs_f32_psum=round(int8["bytes_vs_f32_psum"], 4),
        int8_under_027=int8["bytes_vs_f32_psum"] <= 0.27))
    return rows


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    run(ap.parse_args().full)


if __name__ == "__main__":
    main()
