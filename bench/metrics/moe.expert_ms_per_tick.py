"""Device time of the held experts' grouped matmuls in the serve-step
program per tick that decoded, in ms: the operations named
``ragged-dot`` (``jax.lax.ragged_dot`` in ``models/layers/moe.py``'s
dropless dispatch, with its group metadata) that run inside the serve
step's XLA program events (prefill's are left out).  Moves
``itl_p95_ms``."""

import tracing

PROGRAM = "serve_step"
OPS = "ragged-dot"


def read(run):
    tr, lo, hi = run["trace"], run["lo"], run["hi"]
    secs = 0.0
    for plane, evs in tr.devices.items():
        steps = tracing.merge([(s, e) for n, s, e in tr.modules.get(plane, [])
                               if PROGRAM in n and e > lo and s < hi])
        ops = [(s, e) for n, s, e in evs
               if OPS in tracing.short_name(n) and e > lo and s < hi]
        secs += sum(tracing.covered(steps, max(s, lo), min(e, hi))
                    for s, e in ops) / 1e9
    ticks = sum(1 for _, ps in run["ticks"] if ps)
    if not secs or not ticks:
        return None
    return 1e3 * secs / ticks
