"""Device time of the serve-step program (``launch.steps`` serve step, one
decode token for every lane) per tick that decoded, in ms (profiler
trace, XLA program events).  Moves ``itl_p95_ms``."""

PROGRAM = "serve_step"


def read(run):
    tr = run["trace"]
    secs = sum(v for k, v in tr.module_seconds(run["lo"], run["hi"]).items()
               if PROGRAM in k)
    ticks = sum(1 for _, ps in run["ticks"] if ps)
    if not secs or not ticks:
        return None
    return 1e3 * secs / ticks
