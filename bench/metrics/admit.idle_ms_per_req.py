"""Device idle time inside each admission, in ms: the length of each
``engine.admit`` span (the engine's admission of one request, through its
last host sync) that starts in the window, minus the device busy time
inside it (profiler trace), mean over those admissions.  While an
admission waits on the host, no lane decodes.  Moves ``itl_p95_ms``."""

SPAN = "engine.admit"


def read(run):
    tr = run["trace"]
    spans = [(s, e) for s, e in tr.spans(SPAN)
             if run["lo"] <= s < run["hi"]]
    if not spans:
        return None
    idle = [(e - s) / 1e9 - tr.busy_s(s, e) for s, e in spans]
    return 1e3 * sum(idle) / len(idle)
