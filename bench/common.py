"""Shared pieces of the benchmark: start-up, the device check, files found
by name, percentiles, and the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own under ``bench/`` and is found
here by the name ``BENCHMARK.json`` gives it.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from typing import Dict, Iterable, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json`` — a config, traffic mix or cell."""
    path = os.path.join(BENCH, kind, f"{name}.json")
    with open(path) as f:
        out = json.load(f)
    out.setdefault("name", name)
    return out


def load_workload(name: str) -> dict:
    """The cell with its configuration and traffic mix resolved by name."""
    cell = load_json("workloads", name)
    cell["config_data"] = load_json("configs", cell["config"])
    cell["traffic_data"] = load_json("traffic", cell["traffic"])
    return cell


def _load_file(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module of its own."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _load_file("metrics", name).read


def model_module(config_data: dict):
    """``bench/models/<model_type>.py``, the module of the configuration's
    architecture: ``model_config``, ``params``, ``logits_at`` and the FLOP
    and byte counts.  A ``model_type`` with no module is an error, never
    another architecture's module."""
    name = config_data["model_type"]
    models = os.path.join(BENCH, "models")
    path = os.path.join(models, f"{name}.py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(models) if f.endswith(".py"))
        raise FileNotFoundError(
            f"no module for model_type {name!r}: {path} is missing "
            f"(bench/models has {known})")
    return _load_file("models", name)


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def per_layer_names(spec: dict, workload: str) -> List[str]:
    """Per-layer metrics that name this cell (or name no cells and so
    belong to every cell that reports the end-to-end metric they move)."""
    e2e = end_to_end_names(spec, workload)
    out = []
    for m in spec["per_layer"]:
        cells = m.get("workloads")
        if (workload in cells) if cells is not None else m["moves"] in e2e:
            out.append(m["name"])
    return out


def end_to_end_names(spec: dict, workload: str) -> List[str]:
    return [m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


def metric_units(spec: dict) -> Dict[str, str]:
    return {m["name"]: m["unit"]
            for m in spec["end_to_end"] + spec["per_layer"]}


# ---------------------------------------------------------------------------
# start-up and device
# ---------------------------------------------------------------------------

def start_jax(trace: bool):
    """Environment first, then JAX: the program reads ``REPRO_TRACE`` when
    it is imported, and the compile cache lives at the checkout's fixed
    ``.jax_cache/`` unless ``JAX_COMPILATION_CACHE_DIR`` names another."""
    os.environ["REPRO_TRACE"] = "1" if trace else "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_device(jax, chips: int) -> dict:
    """The device as JAX reports it; raises ``NoChip`` off an accelerator
    or with fewer chips than the cell needs.  There is no CPU fallback."""
    devs = jax.devices()
    if not devs or devs[0].platform == "cpu":
        raise NoChip(f"no accelerator: JAX reports {devs[0].platform if devs else 'nothing'}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(jax, chips: int) -> Optional[int]:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA compilations (cache loads included) through JAX's
    monitoring events, so a run can show that none fell in its window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values: Iterable[float], q: float) -> float:
    """The ``q``-th percentile of all values, by linear interpolation
    between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    if len(xs) == 1:
        return xs[0]
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def rate(work: float, seconds: float) -> float:
    """All the work of the window over all of its time."""
    if seconds <= 0:
        raise ValueError("a window has positive length")
    return work / seconds


# ---------------------------------------------------------------------------
# result
# ---------------------------------------------------------------------------

class Clock:
    """Process start to now, on the host's monotonic clock."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


def print_result(*, correct: bool, attempted: int, failed: int,
                 metrics: Dict[str, float], units: Dict[str, str],
                 device: dict, checks: List[dict],
                 breakdown: Optional[dict] = None) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line on standard output, with the
    checks under the key that comes last."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}, "
              f"{'ok' if c['ok'] else 'FAIL'})", file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in metrics.items() if v is not None},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(json.dumps(line), flush=True)
