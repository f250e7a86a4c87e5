"""Bench provenance + regression gates over the committed ``BENCH_*.json``.

The ``BENCH_*`` files are the repo's per-PR perf trajectory: every claim in
the ROADMAP (flash-decode speedup, paged concurrency, int8 wire fraction)
lives in one of them.  This module makes them load-bearing:

  * :func:`provenance` — what produced a bench run: git SHA, jax/jaxlib
    versions, backend + device kind, and every ``REPRO_*`` env knob.
    ``benchmarks/run.py`` stamps it into each file it writes, so a number
    can always be traced back to the toolchain that measured it.
  * :func:`merge_rows` — row-level merge keyed on row identity, so
    ``benchmarks/run.py --only kernels`` refreshes exactly the rows it
    re-measured and leaves the rest of the file intact (no more
    whole-file clobbering on partial runs).
  * :data:`GATES` / :func:`check_suite` — the regression gate.  Each gated
    metric compares a fresh measurement against the committed baseline
    with a per-metric relative tolerance (generous for wall-clock-derived
    ratios, zero for deterministic byte/count invariants) plus an optional
    absolute floor/ceiling that must hold regardless of the baseline.
    ``benchmarks/run.py --gate`` fails CI when any gate trips.
"""

from __future__ import annotations

import dataclasses
import json
import os
import platform
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

# the suites whose rows persist to BENCH_<suite>.json
BENCH_SUITES = ("kernels", "serving", "collectives")

# fields identifying a row across runs (subset present per suite)
_ROW_KEY_FIELDS = ("row", "name", "case", "wire")


def bench_path(suite: str, root: str = ".") -> str:
    return os.path.join(root, f"BENCH_{suite}.json")


def row_key(row: dict) -> tuple:
    return tuple(row.get(k) for k in _ROW_KEY_FIELDS)


def provenance() -> dict:
    """Environment stamp for a bench run.  Never raises: every field
    degrades to ``"unknown"`` so the stamp works in stripped containers."""
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    try:
        import jax
        import jaxlib
        jax_v, jaxlib_v = jax.__version__, jaxlib.__version__
        backend = jax.default_backend()
        device_kind = jax.devices()[0].device_kind
    except Exception:                           # pragma: no cover
        jax_v = jaxlib_v = backend = device_kind = "unknown"
    try:
        from repro.obs import devmem
        peak = devmem.peak_bytes()
    except Exception:                           # pragma: no cover
        peak = 0
    return {
        "git_sha": sha,
        "jax": jax_v,
        "jaxlib": jaxlib_v,
        "backend": backend,
        "device_kind": device_kind,
        # allocator peak where the backend tracks it, live-buffer footprint
        # otherwise — BENCH speedups carry their memory watermark
        "device_peak_bytes": peak,
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith("REPRO_") or k == "XLA_FLAGS"},
    }


# fields whose baseline/current mismatch makes gate comparisons bogus
_DRIFT_FIELDS = ("backend", "device_kind")


def load_provenance(suite: str, root: str = ".") -> Optional[dict]:
    path = bench_path(suite, root)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            return json.load(f).get("provenance")
    except (OSError, ValueError):
        return None


def provenance_drift(baseline: Optional[dict],
                     current: Optional[dict] = None) -> List[str]:
    """Warnings (NOT failures) when a committed baseline was measured on a
    different backend/device than the current run — a CPU baseline gated
    against a GPU run produces bogus "regressions", and vice versa.  The
    gate still runs (absolute bounds stay meaningful); the warnings tell
    the reader which relative comparisons to distrust."""
    if not baseline:
        return []
    current = current or provenance()
    out = []
    for f in _DRIFT_FIELDS:
        b, c = baseline.get(f, "unknown"), current.get(f, "unknown")
        if b != c and "unknown" not in (b, c):
            out.append(f"provenance drift: baseline {f}={b!r} but this "
                       f"run has {f}={c!r} — relative gates are "
                       f"cross-{f} and may be bogus")
    return out


def merge_rows(old_rows: Sequence[dict],
               new_rows: Sequence[dict]) -> List[dict]:
    """Fresh rows replace same-identity old rows in place (stable order);
    old rows the run didn't re-measure survive; genuinely new rows
    append."""
    fresh = {row_key(r): r for r in new_rows}
    out: List[dict] = []
    for r in old_rows:
        out.append(fresh.pop(row_key(r), r))
    out.extend(fresh.values())
    return out


def write_bench(suite: str, rows: Sequence[dict], *, full: bool,
                root: str = ".") -> str:
    """Merge ``rows`` into ``BENCH_<suite>.json`` (provenance-stamped)."""
    path = bench_path(suite, root)
    old: List[dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f).get("rows", [])
        except (OSError, ValueError):
            old = []
    with open(path, "w") as f:
        json.dump({"full": full, "rows": merge_rows(old, rows),
                   "provenance": provenance()}, f, indent=2)
    return path


def load_bench(suite: str, root: str = ".") -> Optional[List[dict]]:
    path = bench_path(suite, root)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f).get("rows", [])


# ---------------------------------------------------------------------------
# Gates
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GateSpec:
    """One gated metric.

    ``direction``:
      * ``"higher"`` — bigger is better; fail if current <
        baseline·(1−rel_tol) or current < ``bound``.
      * ``"lower"``  — smaller is better; fail if current >
        baseline·(1+rel_tol) or current > ``bound``.
      * ``"exact"``  — must equal the baseline exactly (determinism
        invariants: greedy mismatches, compiled-signature counts).

    ``rel_tol`` absorbs machine-to-machine wall-clock noise; byte ratios
    and counts are deterministic and gate at 0.  ``bound`` is the absolute
    floor (higher) / ceiling (lower) that holds even against a degraded
    baseline."""
    match: Dict[str, object]
    key: str
    direction: str
    rel_tol: float = 0.0
    bound: Optional[float] = None

    def describe(self) -> str:
        sel = ",".join(f"{k}={v}" for k, v in self.match.items())
        return f"[{sel}].{self.key}"


GATES: Dict[str, List[GateSpec]] = {
    "kernels": [
        # fused decode must stay ahead of the naive full-dequant sdpa at
        # both cache lengths; wall-clock ratio, so tolerance is generous
        GateSpec({"name": "flash_decode_4k"}, "speedup", "higher",
                 rel_tol=0.40, bound=1.0),
        GateSpec({"name": "flash_decode_32k"}, "speedup", "higher",
                 rel_tol=0.40, bound=1.0),
    ],
    "serving": [
        GateSpec({"name": "serving_engine_vs_sequential"}, "speedup",
                 "higher", rel_tol=0.60, bound=2.0),
        GateSpec({"name": "serving_engine_vs_sequential"},
                 "greedy_mismatches", "exact"),
        GateSpec({"name": "serving_engine_vs_sequential"},
                 "serve_step_signatures", "exact"),
        # the paged pool's headline: strictly more requests in flight at
        # equal pool bytes — scheduling-deterministic, zero tolerance
        GateSpec({"name": "serving_paged_vs_contiguous"},
                 "concurrency_ratio", "higher", rel_tol=0.0, bound=1.5),
        GateSpec({"name": "serving_paged_vs_contiguous"},
                 "greedy_mismatches", "exact"),
        # CoW prefix sharing: cluster-skewed traffic must sustain at least
        # 2x the non-shared paged pool's peak concurrency at equal pool
        # bytes, bit-identically — scheduling-deterministic, zero tolerance
        GateSpec({"name": "serving_shared_prefix"},
                 "concurrency_ratio", "higher", rel_tol=0.0, bound=2.0),
        GateSpec({"name": "serving_shared_prefix"},
                 "greedy_mismatches", "exact"),
        GateSpec({"name": "serving_shared_prefix"},
                 "serve_step_signatures", "exact"),
        # Zipf fleet trace: admission outcomes are scheduling-deterministic
        # — the head cluster's replays must keep sharing, every request
        # must finish
        GateSpec({"name": "serving_zipf_trace"},
                 "share_hit_rate", "higher", rel_tol=0.0, bound=0.5),
        GateSpec({"name": "serving_zipf_trace"}, "unfinished", "exact"),
        # Serving chaos: under injected request faults (malformed prompts,
        # poisoned logits, unmeetable deadlines, arrival bursts) every
        # survivor must stay bit-identical to its solo greedy reference,
        # the journal must replay to zero unfinished requests, and the
        # fault paths must not add jit signatures. Absolute zero bounds —
        # "exact" would only compare against a (possibly wrong) baseline.
        GateSpec({"name": "serving_chaos"}, "greedy_mismatches", "lower",
                 rel_tol=0.0, bound=0.0),
        GateSpec({"name": "serving_chaos"}, "unfinished", "lower",
                 rel_tol=0.0, bound=0.0),
        GateSpec({"name": "serving_chaos"}, "unaccounted", "lower",
                 rel_tol=0.0, bound=0.0),
        GateSpec({"name": "serving_chaos"}, "serve_step_signatures",
                 "exact"),
        # load shedding must actually engage under the burst (observed
        # shed_rate 0.625 at seed 26; generous floor)
        GateSpec({"name": "serving_chaos"}, "shed_rate", "higher",
                 rel_tol=0.0, bound=0.25),
    ],
    "collectives": [
        # wire-byte fractions are exact chunk-plan arithmetic: zero tol
        GateSpec({"case": "ring", "wire": "int8"}, "bytes_vs_f32_psum",
                 "lower", rel_tol=0.0, bound=0.27),
        GateSpec({"case": "ring", "wire": "bf16"}, "bytes_vs_f32_psum",
                 "lower", rel_tol=0.0, bound=0.51),
        GateSpec({"row": "collectives_summary"}, "int8_under_027", "exact"),
    ],
}


def _find_row(rows: Sequence[dict], match: Dict[str, object]) -> Optional[dict]:
    for r in rows:
        if all(r.get(k) == v for k, v in match.items()):
            return r
    return None


def check_suite(suite: str, current_rows: Sequence[dict],
                baseline_rows: Optional[Sequence[dict]]) -> List[str]:
    """Gate ``current_rows`` against ``baseline_rows``; returns failure
    strings (empty == pass).  A missing baseline file/row only enforces the
    absolute bounds (first run of a new metric)."""
    failures: List[str] = []
    for g in GATES.get(suite, ()):
        row = _find_row(current_rows, g.match)
        if row is None or g.key not in row:
            failures.append(f"{suite}:{g.describe()}: metric missing "
                            f"from current run")
            continue
        cur = row[g.key]
        base_row = (_find_row(baseline_rows, g.match)
                    if baseline_rows is not None else None)
        base = base_row.get(g.key) if base_row else None
        if g.direction == "exact":
            if base is not None and cur != base:
                failures.append(f"{suite}:{g.describe()}: {cur!r} != "
                                f"baseline {base!r}")
            continue
        cur = float(cur)
        if g.direction == "higher":
            if g.bound is not None and cur < g.bound:
                failures.append(f"{suite}:{g.describe()}: {cur:.4g} below "
                                f"absolute floor {g.bound:.4g}")
            elif base is not None and cur < float(base) * (1 - g.rel_tol):
                failures.append(
                    f"{suite}:{g.describe()}: {cur:.4g} regressed vs "
                    f"baseline {float(base):.4g} (tol {g.rel_tol:.0%})")
        elif g.direction == "lower":
            if g.bound is not None and cur > g.bound:
                failures.append(f"{suite}:{g.describe()}: {cur:.4g} above "
                                f"absolute ceiling {g.bound:.4g}")
            elif base is not None and cur > float(base) * (1 + g.rel_tol):
                failures.append(
                    f"{suite}:{g.describe()}: {cur:.4g} regressed vs "
                    f"baseline {float(base):.4g} (tol {g.rel_tol:.0%})")
        else:
            raise ValueError(f"unknown gate direction {g.direction!r}")
    return failures


def gate_report(results: Dict[str, List[str]]) -> str:
    """Human-readable gate outcome (printed by ``benchmarks/run.py``)."""
    lines = []
    for suite in sorted(results):
        fails = results[suite]
        n = len(GATES.get(suite, ()))
        if fails:
            lines.append(f"# GATE {suite}: FAIL ({len(fails)}/{n} metrics)")
            lines.extend(f"#   {f}" for f in fails)
        else:
            lines.append(f"# GATE {suite}: ok ({n} metrics)")
    return "\n".join(lines)
