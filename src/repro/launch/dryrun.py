import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"     # the mesh is emulated on host CPUs

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, and extract the roofline raw terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Results land in experiments/dryrun/<arch>__<shape>__<mesh>[__fed].json;
benchmarks/roofline.py turns them into EXPERIMENTS.md §Roofline.

NOTE: the XLA_FLAGS / JAX_PLATFORMS lines above MUST run before any other
import (jax locks the device count and backend at first init); do not move
them.  The dry-run never takes an accelerator: it compiles for 512 emulated
CPU devices even on a machine that has a TPU.
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import dryrun_args
from repro.launch.steps import (decode_force_window, make_fed_train_step,
                                make_prefill_step, make_serve_step,
                                make_train_step)


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            fed: bool = False, outdir: str = "experiments/dryrun") -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    t0 = time.time()

    kind, args, in_sh, out_sh = dryrun_args(cfg, shape_name, mesh, fed=fed)
    # gradient-accumulation factor: large models microbatch train_4k
    # (§Perf memory lever; EXPERIMENTS.md records before/after)
    accum = int(os.environ.get("REPRO_ACCUM", "0")) or         (8 if cfg.d_model >= 4096 else 4 if cfg.d_model >= 1024 else 1)
    if kind == "train":
        fn = make_train_step(cfg, accum=accum)
        donate = (0, 1)
    elif kind == "fed_train":
        fn = make_fed_train_step(cfg)
        donate = (0, 1)
    elif kind == "prefill":
        fn = make_prefill_step(cfg)
        donate = ()
    else:
        fw = decode_force_window(cfg, [s for s in INPUT_SHAPES
                                       if s.name == shape_name][0].seq_len)
        fn = make_serve_step(cfg, force_window=fw)
        donate = (1,)

    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        # scan-aware accounting (XLA cost_analysis counts while bodies once)
        parsed = hlo_analyze(hlo)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "step_kind": kind, "fed": fed,
        "accum": accum if kind in ("train", "fed_train") else 1,
        "num_devices": mesh.size,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        # per-device numbers (post-SPMD module, trip-count corrected)
        "flops_per_device": parsed["flops_per_device"],
        "bytes_accessed_per_device": parsed["bytes_per_device"],
        "collectives": {"bytes": parsed["collective_bytes"],
                        "counts": parsed["collective_counts"],
                        "total_bytes": parsed["collective_total_bytes"]},
        # raw XLA module-level numbers (uncorrected), for reference
        "xla_flops_per_device": cost.get("flops", 0.0),
        "xla_bytes_per_device": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
    }
    os.makedirs(outdir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_name}" + ("__fed" if fed else "")
    with open(os.path.join(outdir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=[s.name for s in INPUT_SHAPES])
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--fed", action="store_true",
                    help="lower the paper's LoRA-federated train step")
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs x shapes")
    ap.add_argument("--outdir", default="experiments/dryrun")
    args = ap.parse_args()

    pairs = []
    archs = ASSIGNED_ARCHS if args.all else [args.arch]
    shapes = [s.name for s in INPUT_SHAPES] if args.all else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    ok, fail = 0, 0
    for a, s in pairs:
        for mp in meshes:
            tag = f"{a} x {s} x {'multi' if mp else 'single'}" + \
                (" [fed]" if args.fed else "")
            try:
                r = run_one(a, s, multi_pod=mp, fed=args.fed,
                            outdir=args.outdir)
                print(f"OK   {tag}: compile={r['compile_s']}s "
                      f"flops/dev={r['flops_per_device']:.3e} "
                      f"coll={r['collectives']['total_bytes']:.3e}B "
                      f"temp={r['memory']['temp_bytes']/2**30:.2f}GiB",
                      flush=True)
                ok += 1
            except Exception as e:
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                fail += 1
    print(f"dryrun: {ok} ok, {fail} failed", flush=True)
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
