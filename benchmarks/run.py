"""Benchmark runner — one harness per paper table/figure (+ kernels +
roofline).  Prints ``name,key=value,...`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # fast (CPU-minutes)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale settings
  PYTHONPATH=src python -m benchmarks.run --only table2,fig5
  PYTHONPATH=src python -m benchmarks.run --only kernels --gate

The three perf suites (kernels / serving / collectives) persist their rows
into ``BENCH_<suite>.json`` through ``repro.obs.bench_gate.write_bench``:
rows MERGE by identity key into whatever the file already holds (so
``--only serving`` refreshes the serving rows without clobbering the other
file's history — each suite owns its own file — and partial reruns within a
suite keep unmatched old rows), and every write stamps provenance (git SHA,
jax/jaxlib versions, device kind, REPRO_* env) next to the data.

``--gate`` turns the runner into a regression gate: the committed
``BENCH_*.json`` are loaded as BASELINE before the suites overwrite them,
the fresh rows are compared metric-by-metric against
``repro.obs.bench_gate.GATES`` (relative tolerance for wall-clock ratios,
exact for deterministic byte/count invariants, absolute floors
independent of baseline), and any regression fails the process — this is
what CI runs.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated subset (table2,table3,fig2,fig3,"
                         "fig5,fig6,kernels,serving,collectives,roofline)")
    ap.add_argument("--gate", action="store_true",
                    help="compare fresh perf rows against the committed "
                         "BENCH_*.json baselines and exit 1 on regression")
    args = ap.parse_args()

    from benchmarks import (collectives_bench, fig2_lookback,
                            fig3_convergence, fig5_comm_overhead,
                            fig6_ablation, kernels_bench, serving_bench,
                            table2_forecasting, table3_federated)
    from repro.obs import bench_gate

    suites = {
        "table2": table2_forecasting.run,      # Table 2: MSE/MAE grid
        "table3": table3_federated.run,        # Table 3: federated compare
        "fig2": fig2_lookback.run,             # Fig 2: look-back sweep
        "fig3": fig3_convergence.run,          # Fig 3: convergence
        "fig5": fig5_comm_overhead.run,        # Fig 5: comm overhead
        "fig6": fig6_ablation.run,             # Fig 6: ablation
        "kernels": kernels_bench.run,          # kernel microbench
        "serving": serving_bench.run,          # engine + paged-pool A/Bs
        "collectives": collectives_bench.run,  # ring vs psum A/B per wire
    }
    only = set(filter(None, args.only.split(",")))
    unknown = only - set(suites) - {"roofline"}
    if unknown:
        ap.error(f"unknown suite(s) {sorted(unknown)}; choose from "
                 f"{sorted(suites) + ['roofline']}")

    # gate baselines must be read BEFORE the suites rewrite the files
    baselines = {}
    if args.gate:
        current_prov = bench_gate.provenance()
        for suite in bench_gate.BENCH_SUITES:
            base = bench_gate.load_bench(suite)
            if base is None:
                print(f"# gate: no committed BENCH_{suite}.json — "
                      f"absolute bounds only", flush=True)
            baselines[suite] = base
            # cross-backend baselines make relative gates bogus: warn,
            # don't fail (absolute bounds still hold)
            for warning in bench_gate.provenance_drift(
                    bench_gate.load_provenance(suite), current_prov):
                print(f"# gate WARNING [{suite}]: {warning}", flush=True)

    failures = 0
    gate_results: dict = {}
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            rows = fn(full=args.full)
            if name in bench_gate.BENCH_SUITES and rows:
                # perf trajectory artifacts (merged, provenance-stamped)
                path = bench_gate.write_bench(name, rows, full=args.full)
                print(f"# wrote {path}", flush=True)
                if args.gate:
                    gate_results[name] = bench_gate.check_suite(
                        name, rows, baselines.get(name))
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()

    if not only or "roofline" in only:
        print("# === roofline (from dry-run artifacts) ===", flush=True)
        try:
            import benchmarks.roofline as roofline
            sys.argv = ["roofline"]
            roofline.main()
        except Exception as e:
            print(f"# roofline skipped: {e}", flush=True)

    if args.gate and gate_results:
        report = bench_gate.gate_report(gate_results)
        print(report, flush=True)
        if any(gate_results.values()):
            failures += 1

    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
