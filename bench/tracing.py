"""The profiler trace of a window and its reduction to numbers.

A traced run records the window with ``jax.profiler`` and reads the
``.xplane.pb`` it writes with ``jax.profiler.ProfileData`` (nothing but
JAX).  From the device planes: the busy time (the union of the intervals in
which an XLA operation ran), the time per operation and per XLA program,
and the idle gaps.  From the host plane: the benchmark's own
``TraceAnnotation`` spans around the calls into each layer (and the
program's ``device=True`` spans), which name what the host was doing in
each idle gap.  Every time here is a device or host time from the trace,
in seconds.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import shutil
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# host spans that name what the host was doing: the benchmark's own, and
# the program's ``device=True`` spans (``engine.decode_step``,
# ``req.prefill``)
HOST_PREFIXES = ("bench.", "engine.", "req.")


@contextlib.contextmanager
def profile(jax, out_dir: str):
    """Trace what runs inside the block into ``out_dir``."""
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # no per-call Python events
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_and_remove(out_dir: str) -> "Trace":
    """Reduce the trace written under ``out_dir``, then delete it: a
    window's trace is hundreds of MB and every number is taken from it
    here."""
    try:
        return Trace.load(find_xplane(out_dir))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def find_xplane(out_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return found[-1]


def merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, lo: int, hi: int) -> int:
    """Nanoseconds of the merged ``intervals`` inside [lo, hi)."""
    return sum(e - s for s, e in clip(intervals, lo, hi))


# event stats that carry an operation's framework name (the jitted
# function and ``jax.named_scope`` path, e.g. ``.../obs.flash_decode/...``)
SCOPE_STATS = ("tf_op", "long_name", "hlo_op")


def op_label(event) -> str:
    """An XLA operation's name with its framework path, when the trace
    gives one: ``fusion.3 jit(serve_step)/.../obs.flash_decode/...``."""
    for k, v in event.stats:
        if k in SCOPE_STATS and v is not None and str(v) != event.name:
            return f"{event.name} {v}"
    return event.name


class Trace:
    """One reduced trace.  ``devices`` maps a device plane name to its op
    events ``(label, start_ns, end_ns)`` (label: ``op_label``);
    ``modules`` to its XLA program events; ``host`` holds host spans
    ``(name, start_ns, end_ns)``."""

    def __init__(self, devices: Dict[str, list], modules: Dict[str, list],
                 host: list):
        self.devices = devices
        self.modules = modules
        self.host = sorted(host, key=lambda e: (e[1], -e[2]))
        self._starts = [s for _, s, _ in self.host]
        self.busy = {d: merge([(s, e) for _, s, e in evs])
                     for d, evs in devices.items()}

    @classmethod
    def load(cls, path: str) -> "Trace":
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        devices, modules, host = {}, {}, []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices[plane.name] = [
                            (op_label(e), int(e.start_ns), int(e.end_ns))
                            for e in line.events]
                    elif line.name == MODULES_LINE:
                        modules[plane.name] = [
                            (e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.duration_ns > 0 and \
                                e.name.startswith(HOST_PREFIXES):
                            host.append((e.name, int(e.start_ns),
                                         int(e.end_ns)))
        return cls(devices, modules, host)

    # -- the window ----------------------------------------------------------

    def window(self) -> Tuple[int, int]:
        """The benchmark's ``bench.window`` span, or the whole trace."""
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if spans:
            return spans[0]
        ends = [e for evs in self.devices.values() for _, _, e in evs]
        starts = [s for evs in self.devices.values() for _, s, _ in evs]
        return (min(starts), max(ends)) if starts else (0, 0)

    def spans(self, name: str) -> List[Tuple[int, int]]:
        return [(s, e) for n, s, e in self.host if n == name]

    # -- device --------------------------------------------------------------

    def busy_s(self, lo: int, hi: int) -> float:
        """Busy seconds in [lo, hi), averaged over the traced devices."""
        if not self.busy:
            return 0.0
        return sum(covered(b, lo, hi) for b in self.busy.values()) \
            / len(self.busy) / 1e9

    def op_seconds(self, lo: int, hi: int, match=None) -> Dict[str, float]:
        """Device seconds per operation name in [lo, hi) (summed over
        devices), optionally only names for which ``match`` is true."""
        out: Dict[str, float] = collections.defaultdict(float)
        for evs in self.devices.values():
            for n, s, e in evs:
                if (match is None or match(n)) and e > lo and s < hi:
                    out[n] += (min(e, hi) - max(s, lo)) / 1e9
        return dict(out)

    def module_seconds(self, lo: int, hi: int) -> Dict[str, float]:
        """Device seconds per XLA program (name without its id suffix)."""
        out: Dict[str, float] = collections.defaultdict(float)
        for evs in self.modules.values():
            for n, s, e in evs:
                if e > lo and s < hi:
                    out[program_name(n)] += (min(e, hi) - max(s, lo)) / 1e9
        return dict(out)

    # -- idle gaps -----------------------------------------------------------

    def gaps(self, lo: int, hi: int, min_ns: int = 0) -> List[Tuple[int, int]]:
        """Intervals in [lo, hi) in which the first device ran nothing."""
        if not self.busy:
            return [(lo, hi)]
        b = clip(next(iter(self.busy.values())), lo, hi)
        out, t = [], lo
        for s, e in b:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < hi:
            out.append((t, hi))
        return [(s, e) for s, e in out if e - s > min_ns]

    def host_doing(self, t: int) -> str:
        """Innermost host span covering instant ``t`` (not the window):
        of nested spans, the one that started last."""
        i = bisect.bisect_right(self._starts, t)
        for n, s, e in reversed(self.host[max(0, i - 256):i]):
            if e > t and n != WINDOW_SPAN:
                return n
        return "untraced host"

    def idle_by_host(self, lo: int, hi: int) -> Dict[str, float]:
        """Idle seconds in [lo, hi) attributed to what the host was doing
        at each gap's midpoint."""
        out: Dict[str, float] = collections.defaultdict(float)
        for s, e in self.gaps(lo, hi):
            out[self.host_doing((s + e) // 2)] += (e - s) / 1e9
        return dict(out)


def program_name(module_event: str) -> str:
    """``jit_serve_step(1234)`` -> ``jit_serve_step``."""
    return module_event.split("(")[0].strip()


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def short_name(label: str) -> str:
    """An operation's HLO name without its text (``%fusion.3``), marked
    when it is a Pallas kernel."""
    name = label.split(" = ", 1)[0].split(" ", 1)[0]
    return name + (" (tpu_custom_call)" if "tpu_custom_call" in label
                   else "")


def breakdown(tr: Trace, lo: int, hi: int) -> dict:
    ops: Dict[str, float] = collections.defaultdict(float)
    for label, secs in tr.op_seconds(lo, hi).items():
        ops[short_name(label)] += secs
    return {"device_ops": top(ops), "idle_gaps": top(tr.idle_by_host(lo, hi))}


def summary(tr: Trace) -> dict:
    """The numbers every traced run reports: busy and window seconds of
    the ``bench.window`` span, and the breakdown."""
    lo, hi = tr.window()
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
            "busy_s": tr.busy_s(lo, hi), "breakdown": breakdown(tr, lo, hi)}
