"""Host time per engine tick, in ms: the harness's span around each
``step()`` minus the device busy time inside it (profiler trace), mean
over the ticks of the window — the engine's host loop (scheduling,
admission, block grants, batch building, dispatch, token bookkeeping).
Moves ``itl_p95_ms``."""


def read(run):
    tr = run["trace"]
    spans = [(s, e) for s, e in run["step_spans"]
             if s >= run["lo"] and e <= run["hi"]]
    if not spans:
        return None
    host = [(e - s) / 1e9 - tr.busy_s(s, e) for s, e in spans]
    return 1e3 * sum(host) / len(host)
