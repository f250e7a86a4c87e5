"""Share of the serving window in which the chip ran no XLA operation
(profiler trace: 1 - busy / window).  Moves ``itl_p95_ms``."""


def read(run):
    if run["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])
