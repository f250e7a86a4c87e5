"""Device time of the prefill program (``engine._admit`` -> the model's
bucketed prefill) per prompt prefilled in the window, in ms (profiler
trace, XLA program events).  Moves ``ttft_p90_ms``."""

PROGRAM = "_prefill"


def read(run):
    tr = run["trace"]
    secs = sum(v for k, v in tr.module_seconds(run["lo"], run["hi"]).items()
               if PROGRAM in k)
    n = sum(1 for _, _, pre, _ in run["admits"] if pre > 0)
    if not secs or not n:
        return None
    return 1e3 * secs / n
