"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 20 --batch 8 --seq 256 [--full-config] [--fed]

It runs the reduced (smoke) config by default; ``--full-config`` takes the
published widths.  The step runs on a (data, model) mesh over whatever
devices JAX finds (``--model-parallel`` sets the model axis): parameters
are tensor-parallel over ``model``, the AdamW moments ZeRO-1-sharded over
``data`` and each batch split over ``data`` — the partition rules of
``repro.dist.sharding``, the same step functions the dry-run lowers.

``--trace-out PATH`` dumps the ``repro.obs`` timeline (per-step
``train.step`` spans via ``jax.profiler.StepTraceAnnotation``, loss gauge,
device-memory watermarks) as Chrome trace-event JSON for Perfetto /
chrome://tracing.  ``--scope-costs`` prints the per-``obs.*``-named-scope
FLOP/byte attribution of the compiled step (``repro.obs.devmem``) — which
kernel owns the step's cost, straight from the HLO.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.core.lora import FAMILY_TARGETS, attach_lora, lora_tree
from repro.data.tokens import lm_batches, markov_tokens
from repro.dist.sharding import (data_specs, opt_state_specs, param_specs,
                                 to_shardings)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_fed_train_step, make_train_step
from repro.models.registry import get_model, train_batch_shapes
from repro.optim.adamw import adamw_init


def synth_batch(cfg, batch, seq, it):
    shapes = train_batch_shapes(cfg, batch, seq)
    out = {}
    b = next(it)
    for k, (shp, dt) in shapes.items():
        if k == "tokens":
            out[k] = jnp.asarray(b["tokens"][:, :shp[1]])
        elif k == "labels":
            out[k] = jnp.asarray(b["labels"][:, :shp[1]])
        else:
            out[k] = jnp.zeros(shp, dt)
    return out


def place_state(params, opt, mesh, *, fed: bool):
    """Put the parameters (tensor-parallel over ``model``) and the AdamW
    moments (ZeRO-1 over ``data``; adapter-shaped for the fed step) on
    ``mesh``.  Returns (params, opt, (param_shardings, opt_shardings))."""
    psh = to_shardings(param_specs(params, mesh), mesh)
    moments = lora_tree(params) if fed else params
    o = to_shardings(opt_state_specs(moments, mesh), mesh)
    osh = {"mu": o, "nu": o}
    return jax.device_put(params, psh), jax.device_put(opt, osh), (psh, osh)


def place_batch(batch, mesh):
    """Split each batch leaf's leading dim over the data axes."""
    return jax.device_put(batch, to_shardings(data_specs(batch, mesh), mesh))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="smollm-360m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full-config", action="store_true",
                    help="published widths instead of the smoke config")
    ap.add_argument("--fed", action="store_true",
                    help="LoRA-federated step (the paper's training mode)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--trace-out", default="",
                    help="write the repro.obs span timeline as Chrome "
                         "trace-event JSON (Perfetto / chrome://tracing)")
    ap.add_argument("--scope-costs", action="store_true",
                    help="print per-obs.* named-scope FLOP/byte attribution "
                         "of the compiled train step")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (get_config if args.full_config else get_smoke_config)(args.arch)
    api = get_model(cfg)
    mesh = make_host_mesh(model=args.model_parallel)
    print(f"arch={cfg.name} devices={mesh.size} mesh={dict(mesh.shape)}")

    params = api.init(cfg, jax.random.PRNGKey(0))
    if args.fed:
        params = attach_lora(params, jax.random.PRNGKey(1), rank=4,
                             alpha=8.0, targets=FAMILY_TARGETS[cfg.family])
        step_fn = make_fed_train_step(cfg, lr=args.lr)
    else:
        step_fn = make_train_step(cfg, lr=args.lr)
    opt = adamw_init(lora_tree(params) if args.fed else params)
    params, opt, (psh, osh) = place_state(params, opt, mesh, fed=args.fed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M")

    toks = markov_tokens(200_000, cfg.vocab_size, seed=0)
    it = lm_batches(toks, args.batch, args.seq + 1, seed=0)

    jitted = jax.jit(step_fn, donate_argnums=(0, 1),
                     out_shardings=(psh, osh, None))
    with mesh:
        if args.scope_costs:
            # undonated lower: attribution only, params survive for the loop
            batch = place_batch(synth_batch(cfg, args.batch, args.seq, it),
                                mesh)
            compiled = jax.jit(step_fn).lower(
                params, opt, batch, jnp.asarray(0, jnp.int32)).compile()
            costs = obs.devmem.compiled_scope_costs(compiled)
            if costs:
                total_f = sum(v["flops"] for v in costs.values()) or 1.0
                print("per-scope HLO cost attribution (compiled step):")
                for scope, v in sorted(costs.items(),
                                       key=lambda kv: -kv[1]["flops"]):
                    print(f"  {scope:<28} flops={v['flops']:.3e} "
                          f"({v['flops'] / total_f:5.1%})  "
                          f"bytes={v['bytes']:.3e}")
        t0 = time.time()
        for i in range(args.steps):
            batch = place_batch(synth_batch(cfg, args.batch, args.seq, it),
                                mesh)
            with obs.step_span("train.step", i, batch=args.batch,
                               seq=args.seq):
                params, opt, loss = jitted(params, opt, batch,
                                           jnp.asarray(i, jnp.int32))
                loss = float(loss)      # device sync inside the span
            obs.gauge("train.loss", loss)
            if i < 3 or (i + 1) % 5 == 0:
                dt = time.time() - t0
                tok_s = args.batch * args.seq * (i + 1) / dt
                print(f"step {i + 1}/{args.steps} loss={loss:.4f} "
                      f"({tok_s:.0f} tok/s)", flush=True)
                if obs.enabled():
                    obs.watermark("train.step")   # devmem track, sampled
    print("done")
    if args.trace_out:
        from repro.obs import bench_gate
        path = obs.dump(args.trace_out, provenance=bench_gate.provenance())
        print(f"trace: wrote {path} "
              f"(open at https://ui.perfetto.dev or chrome://tracing)")


if __name__ == "__main__":
    main()
