"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics with the program's
own tracing off (``REPRO_TRACE=0``); ``--trace 1`` is a run of its own
that records a profiler trace of the window and reports the cell's
per-layer metrics from it.  Either way the outputs of the timed path are
compared with the plain reference after the window, and the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
the compared numbers with their limits under ``checks``.

The run exits non-zero, with no result line, when JAX finds no
accelerator or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    clock = common.Clock()
    args = parse(argv)
    cell = common.load_workload(args.workload)
    jax = common.start_jax(bool(args.trace))
    try:
        device = common.check_device(jax, cell["chips"])
    except common.NoChip as e:
        print(f"bench: {e}; this benchmark measures nothing without its "
              f"chips", file=sys.stderr)
        return 2
    kind = importlib.import_module(f"kinds.{cell['kind']}")
    return kind.run(cell, args, jax, clock, device)


if __name__ == "__main__":
    sys.exit(main())
