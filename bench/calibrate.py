"""Readings that the limits of ``correct`` are set from, on the chip at a
serving cell's own size, many seeds in one process:

    python bench/calibrate.py --workload <name> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--seconds 20]

Each seed runs the cell's timed path as a benchmark run does
(``kinds.serve.Session``: window, drain, finishing of the sample) and
reads the numbers the cell compares, from the program (the lower
readings); on the control seeds, also the same numbers with the plain
reference computed in int8 put in the program's place (the control).  One
JSON line per reading on standard output and in
``chiprun_out/calibrate-<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def emit(out, **kw):
    line = json.dumps(kw)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def serve_seed(cell, seed, seconds, control, out):
    from kinds import serve
    s = serve.Session(cell, seed, seconds)
    _, _, sent = s.window(seconds)
    s.drain(sent)
    finished = s.finish(cell["limits"]["sample_tokens"])
    s.close()
    for int8 in ([False, True] if control else [False]):
        checks = serve.check_outputs(s.model, s.cfg, cell, s.reqs, finished,
                                     seed, int8=int8)
        emit(out, workload=cell["name"], seed=seed,
             side="control" if int8 else "program",
             finished=len(finished),
             **{c["name"]: c["value"] for c in checks})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = common.load_workload(args.workload)
    jax = common.start_jax(False)
    common.check_device(jax, cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(os.path.join(common.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(common.ROOT, "chiprun_out",
                        f"calibrate-{args.workload}.jsonl")
    with open(path, "a") as out:
        for seed in seeds:
            t = time.perf_counter()
            serve_seed(cell, seed, args.seconds, seed in control, out)
            print(f"seed {seed}: {time.perf_counter() - t:.1f}s",
                  file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
