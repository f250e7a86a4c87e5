"""The table of peaks that the utilization and roofline metrics divide
by, and the arithmetic they share.  The operations and bytes an
architecture needs, counted from its shapes, are its module's
(``bench/models/<model_type>.py``: ``prefill_flops``, ``decode_flops``,
``decode_attention_flops``, ``paged_decode_bytes``).
"""

from __future__ import annotations

import json
import math
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that is
    not in ``peaks.json`` is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def causal_pairs(s: int) -> int:
    """(query, key) pairs a causal sequence of length ``s`` attends."""
    return s * (s + 1) // 2


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def share(numerator_s: float, measured_s: float):
    """A share of a roofline or peak in percent, or None with nothing
    measured (never 0 for want of a reading)."""
    if not measured_s or measured_s <= 0 or not math.isfinite(numerator_s):
        return None
    return 100.0 * numerator_s / measured_s
