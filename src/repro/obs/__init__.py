"""``repro.obs`` — the unified observability layer.

One process-global structured tracer (``repro.obs.trace``) threads through
the serving engine, the federated trainer, and the launchers; mergeable
quantile sketches live in ``repro.obs.sketch``, the per-client federated
round ledger in ``repro.obs.fleet``, device-memory / HLO-cost attribution
in ``repro.obs.devmem``, the crash-dump flight recorder in
``repro.obs.flight``, and bench provenance + regression gates in
``repro.obs.bench_gate``.  Import this package, not the submodules, from
instrumented code::

    from repro import obs

    with obs.span("engine.decode_step", step=i, active=n):
        ...                       # under jax.profiler: an annotation too
    obs.counter("ring.wire_bytes.data", nbytes)
    obs.hist("fed.fit_wall_s", dt, sketch=True)   # mergeable percentiles
    obs.dump("trace.json")        # -> chrome://tracing / Perfetto UI

``REPRO_TRACE=0`` turns every call into a no-op; ``REPRO_TRACE_OUT=f.json``
dumps the trace at exit.  Even with the tracer off, the flight recorder
keeps the last ``REPRO_FLIGHT_CAP`` events and ``REPRO_FLIGHT_OUT=f.json``
arms post-mortem dumps (atexit / unhandled exception / engine distress);
``REPRO_FLIGHT=0`` disables that last layer too.
"""

from repro.obs import devmem, fleet
from repro.obs.devmem import memory_snapshot, scope_costs, watermark
from repro.obs.fleet import ClientRecord, FleetLedger
from repro.obs.flight import (FlightRecorder, flight_enabled, get_flight,
                              maybe_dump as flight_maybe_dump)
from repro.obs.sketch import QuantileSketch, merge_all
from repro.obs.trace import (Histogram, Tracer, add_span, counter,
                             counter_track, dump, gauge, get_tracer, hist,
                             instant, reset, span, span_count, step_span,
                             trace_enabled)

enabled = trace_enabled

__all__ = [
    "ClientRecord", "FleetLedger", "FlightRecorder", "Histogram",
    "QuantileSketch", "Tracer", "add_span", "counter", "counter_track",
    "devmem", "dump", "enabled", "fleet", "flight_enabled",
    "flight_maybe_dump", "gauge", "get_flight", "get_tracer", "hist",
    "instant", "memory_snapshot", "merge_all", "reset", "scope_costs",
    "span", "span_count", "step_span", "trace_enabled", "watermark",
]
