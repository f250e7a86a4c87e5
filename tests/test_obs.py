"""repro.obs tracer: span nesting under threads, histogram percentiles vs
numpy, no-op overhead, Chrome trace-event schema round-trip, engine trace
validity, metrics fixes, and the federated ring-telemetry byte agreement
("one number, now four ways")."""

import collections
import json
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.models.registry import get_model
from repro.obs import bench_gate
from repro.obs.trace import _NULL_SPAN, Histogram, Tracer
from repro.serve import ForecastEngine, Request
from repro.serve.metrics import EngineMetrics


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy_below_capacity():
    rng = np.random.default_rng(0)
    xs = rng.random(1000) * 10.0
    h = Histogram(capacity=4096)
    for x in xs:
        h.add(x)
    assert h.count == 1000
    assert h.min == xs.min() and h.max == xs.max()
    assert h.mean == pytest.approx(xs.mean(), rel=1e-12)
    for q in (0, 10, 50, 95, 99, 100):
        assert h.percentile(q) == pytest.approx(
            np.percentile(xs, q, method="linear"), rel=1e-12), q
    s = h.summary()
    assert s["p50"] == h.percentile(50) and s["p99"] == h.percentile(99)


def test_histogram_reservoir_bounded_and_sane_past_capacity():
    h = Histogram(capacity=128)
    rng = np.random.default_rng(1)
    for x in rng.random(10_000):
        h.add(x)
    assert h.count == 10_000
    assert len(h._res) == 128                 # bounded memory
    assert 0.0 <= h.min and h.max <= 1.0
    # uniform[0,1): the reservoir median is a coarse but unbiased estimate
    assert abs(h.percentile(50) - 0.5) < 0.15


def test_empty_histogram():
    h = Histogram()
    assert h.summary() == {"count": 0}
    assert h.percentile(50) == 0.0
    assert h.mean == 0.0


# ---------------------------------------------------------------------------
# Spans: nesting, threads, tracks
# ---------------------------------------------------------------------------

def test_span_nesting_and_thread_tracks():
    tr = Tracer()

    def work(tag):
        with tr.span(f"outer.{tag}", depth=0):
            time.sleep(0.002)
            with tr.span(f"inner.{tag}", depth=1):
                time.sleep(0.002)

    threads = [threading.Thread(target=work, args=(i,), name=f"wk{i}")
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    spans = {e["name"]: e for e in tr.events() if e["ph"] == "X"}
    assert len(spans) == 6
    tids = set()
    for i in range(3):
        outer, inner = spans[f"outer.{i}"], spans[f"inner.{i}"]
        # same thread -> same tid; inner nests strictly inside outer
        assert outer["tid"] == inner["tid"]
        tids.add(outer["tid"])
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert len(tids) == 3                     # one track per thread
    meta = [e for e in tr.events() if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} >= {"wk0", "wk1", "wk2"}


def test_threads_run_one_after_another_get_their_own_tracks():
    """An exited thread's ``threading.get_ident()`` is reused by the next
    thread; each thread still gets a track of its own, with its name."""
    tr = Tracer()

    def work():
        with tr.span("work"):
            pass

    for name in ("first", "second"):
        t = threading.Thread(target=work, name=name)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    work = [e for e in tr.events("work") if e["ph"] == "X"]
    assert len(work) == 2
    track_name = {e["tid"]: e["args"]["name"]
                  for e in tr.events("thread_name")}
    assert [track_name[e["tid"]] for e in work] == ["first", "second"]


def test_virtual_tracks_and_span_count():
    tr = Tracer()
    tr.add_span("req.lifecycle", 0.0, 1.0, track="req:a", id="a")
    tr.add_span("req.lifecycle", 0.0, 2.0, track="req:b", id="b")
    tr.instant("req.retire", track="req:a", id="a")
    assert tr.span_count("req.lifecycle") == 2
    assert tr.span_count("req.retire") == 0   # instants are not spans
    evs = [e for e in tr.events() if e.get("args", {}).get("id") == "a"]
    assert len({e["tid"] for e in evs}) == 1  # one virtual track per request


# ---------------------------------------------------------------------------
# No-op mode
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_noop_and_cheap(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "0")
    monkeypatch.setenv("REPRO_FLIGHT", "0")   # pure no-op: flight off too
    tr = Tracer()
    assert tr.span("x") is _NULL_SPAN         # shared singleton, no alloc
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with tr.span("hot", step=i):
            pass
        tr.instant("i")
        tr.counter("c", 1)
        tr.hist("h", 0.5)
    per_call = (time.perf_counter() - t0) / (4 * n)
    assert tr.events() == []
    assert tr.counters == {} and tr.hists == {}
    # generous CI bound; typical is well under 1us
    assert per_call < 20e-6, f"{per_call * 1e6:.2f}us per disabled call"
    monkeypatch.setenv("REPRO_TRACE", "1")
    with tr.span("on"):
        pass
    assert tr.span_count("on") == 1           # re-enables without restart


# ---------------------------------------------------------------------------
# Chrome trace-event schema round-trip
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("a", k=1):
        tr.instant("evt", track="t1", x=2)
    tr.counter_track("pool", blocks_in_use=3, active_lanes=1)
    tr.counter("bytes", 42)
    tr.gauge("norm", 0.5)
    tr.hist("lat", 0.01)
    path = tr.dump(str(tmp_path / "trace.json"),
                   provenance=bench_gate.provenance())
    doc = json.load(open(path))

    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "tid" in e
        elif e["ph"] == "i":
            assert e["s"] == "t" and "ts" in e
        elif e["ph"] == "C":
            assert all(isinstance(v, float) for v in e["args"].values())
    by_ph = {e["ph"] for e in evs}
    assert {"X", "i", "C", "M"} <= by_ph
    md = doc["metadata"]
    assert md["tool"] == "repro.obs"
    assert md["summary"]["counters"]["bytes"] == 42
    assert md["summary"]["gauges"]["norm"] == 0.5
    assert md["summary"]["hists"]["lat"]["count"] == 1
    prov = md["provenance"]
    assert {"git_sha", "jax", "backend", "device_kind", "env"} <= set(prov)


# ---------------------------------------------------------------------------
# EngineMetrics fixes
# ---------------------------------------------------------------------------

def test_metrics_wall_clock_spans_to_last_event():
    m = EngineMetrics(2)
    m.record_decode_step(2, 2, 0.001)
    m.record_finish(0.01)
    t_finish = m.last_event_at
    time.sleep(0.02)
    # decode work AFTER the last finish must advance the clock
    m.record_decode_step(1, 1, 0.001)
    assert m.last_event_at > t_finish
    s = m.summary()
    assert s["wall_s"] >= (m.last_event_at - m.started) * 0.99
    assert s["tok_per_s"] == pytest.approx(3 / s["wall_s"])


def test_metrics_steady_rate_guards_single_step():
    m = EngineMetrics(1)
    m.record_decode_step(1, 1, 5.0)           # compile-laden only step
    assert m.summary()["steady_tok_per_s"] == 0.0
    # second step: steady excludes the first step's tokens and time
    m.record_decode_step(1, 1, 0.5)
    s = m.summary()
    assert s["steady_tok_per_s"] == pytest.approx((2 * 0.5) / 0.5)


def test_metrics_latency_percentiles():
    m = EngineMetrics(4)
    m.record_decode_step(4, 4, 3.0)           # first step: excluded from ITL
    for _ in range(10):
        m.record_decode_step(4, 4, 0.01)
    for i in range(5):
        m.record_finish(0.1 * (i + 1))
    s = m.summary()
    assert m.itl_hist.count == 10             # compile step not recorded
    assert s["itl_p50_s"] == pytest.approx(0.01)
    assert s["itl_p99_s"] == pytest.approx(0.01)
    assert s["ttft_p50_s"] == pytest.approx(0.3)
    assert s["ttft_p99_s"] == pytest.approx(np.percentile(
        [0.1, 0.2, 0.3, 0.4, 0.5], 99, method="linear"), rel=1e-12)


# ---------------------------------------------------------------------------
# Engine trace validity (integration)
# ---------------------------------------------------------------------------

CACHE_LEN = 48
_LIFECYCLE = ["req.submit", "req.queued", "engine.admit", "req.first_token",
              "req.decode", "req.lifecycle", "req.retire"]


def test_engine_trace_two_request_lifecycle():
    """A 2-request staggered trace produces the exact per-request event
    sequence, one lifecycle span per finished request, and one
    engine.decode_step span per recorded decode step."""
    cfg = get_smoke_config("qwen3-0.6b")
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(id=f"r{i}",
                    prompt=rng.integers(0, cfg.vocab_size, 6 + 3 * i)
                    .astype(np.int32),
                    max_new_tokens=4 + i, arrival_step=2 * i)
            for i in range(2)]

    obs.reset()
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN)
    for r in reqs:
        eng.submit(r)
    done = eng.run(max_steps=200)
    assert set(done) == {"r0", "r1"}

    tr = obs.get_tracer()
    events = tr.events()
    for rid in ("r0", "r1"):
        names = [e["name"] for e in events
                 if e.get("args", {}).get("id") == rid]
        assert names == _LIFECYCLE, (rid, names)
        # the whole lifecycle rides ONE virtual track
        tids = {e["tid"] for e in events
                if e.get("args", {}).get("id") == rid}
        assert len(tids) == 1, rid
    assert tr.span_count("req.lifecycle") == eng.metrics.requests_finished \
        == 2
    assert tr.span_count("engine.decode_step") == eng.metrics.decode_steps
    # the pool counter track sampled every decode step
    pool_samples = [e for e in events
                    if e["ph"] == "C" and e["name"] == "pool"]
    assert len(pool_samples) == eng.metrics.decode_steps
    # lifecycle span duration covers queued + prefill + decode
    life = {e["args"]["id"]: e for e in events
            if e["name"] == "req.lifecycle"}
    dec = {e["args"]["id"]: e for e in events if e["name"] == "req.decode"}
    for rid in ("r0", "r1"):
        assert life[rid]["dur"] >= dec[rid]["dur"]
        assert life[rid]["args"]["tokens"] == len(done[rid].tokens)
        assert life[rid]["args"]["ttft_s"] == pytest.approx(
            done[rid].ttft_s)


_PHASES = ("engine.slo_sweep", "engine.schedule", "engine.grant",
           "engine.batch", "engine.decode_step", "engine.emit")


def _inside(inner, outer) -> bool:
    return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1.0)


def _lm_engine():
    cfg = get_smoke_config("qwen3-0.6b")
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN)


def _three_requests(cfg):
    """Two lanes, three requests: the third waits in the queue, and the
    second repeats the first's prompt (a whole-prompt hit)."""
    rng = np.random.default_rng(2)
    p0 = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    return [Request(id="a", prompt=p0, max_new_tokens=5),
            Request(id="b", prompt=p0.copy(), max_new_tokens=3),
            Request(id="c", prompt=p2, max_new_tokens=3)]


def test_engine_tick_phase_and_admission_spans():
    """One engine.tick per step(), the tick's phases nested inside it,
    one engine.admit per admission with its sync points nested inside it,
    and the admission args (queue wait, prefilled and shared tokens)."""
    cfg, eng = _lm_engine()
    obs.reset()
    for r in _three_requests(cfg):
        eng.submit(r)
    eng.run(max_steps=200)
    tr = obs.get_tracer()
    ticks = [e for e in tr.events("engine.tick") if e["ph"] == "X"]
    assert len(ticks) == eng.step_count
    assert [e["args"]["step"] for e in ticks] == list(range(eng.step_count))
    for name in _PHASES:
        evs = tr.events(name)
        assert evs, name
        assert all(any(_inside(e, t) for t in ticks) for e in evs), name
    dec = tr.events("engine.decode_step")
    assert len(dec) == eng.metrics.decode_steps
    assert all(e["args"]["lanes"] == 2 and 1 <= e["args"]["active"] <= 2
               for e in dec)

    admits = {e["args"]["id"]: e for e in tr.events("engine.admit")}
    assert len(tr.events("engine.admit")) == \
        eng.metrics.requests_admitted == 3
    assert all(any(_inside(a, t) for t in ticks) for a in admits.values())
    for name in ("engine.admit.prefill", "engine.admit.first_token",
                 "engine.admit.index"):
        evs = tr.events(name)
        assert evs, name
        assert all(any(_inside(e, a) for a in admits.values())
                   for e in evs), name
    a, b, c = admits["a"]["args"], admits["b"]["args"], admits["c"]["args"]
    assert (a["prefilled"], a["shared_tokens"]) == (16, 0)
    assert (b["prefilled"], b["shared_tokens"]) == (0, 16)    # whole hit
    assert (c["prefilled"], c["shared_tokens"]) == (7, 0)
    assert not any(x["resumed"] for x in (a, b, c))
    assert a["prompt_len"] == 16 and c["prompt_len"] == 7
    # "c" waited in the queue for a lane through at least one decode step
    assert c["queued_ms"] > max(a["queued_ms"], b["queued_ms"])
    assert c["queued_ms"] >= 1e-3 * min(e["dur"] for e in dec)


def test_engine_spans_on_the_profiler_timeline(tmp_path):
    """Under jax.profiler the engine's spans are host events of the trace,
    named as the tracer names them and carrying its args as stats."""
    cfg, eng = _lm_engine()
    reqs = _three_requests(cfg)
    eng.submit(Request(id="warm", prompt=reqs[2].prompt, max_new_tokens=2))
    eng.run(max_steps=200)                    # compile outside the trace
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=200)
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    host = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        host[e.name].append((e.start_ns, dict(list(e.stats))))
    prof = {n: [st for _, st in sorted(evs, key=lambda x: x[0])]
            for n, evs in host.items()}
    tr = obs.get_tracer()
    admits = tr.events("engine.admit")
    assert [(st["queued_ms"], st["prefilled"], st["id"])
            for st in prof["engine.admit"]] == \
        [(e["args"]["queued_ms"], e["args"]["prefilled"], e["args"]["id"])
         for e in admits]
    assert [(st["active"], st["lanes"], st["step"])
            for st in prof["engine.decode_step"]] == \
        [(e["args"]["active"], e["args"]["lanes"], e["args"]["step"])
         for e in tr.events("engine.decode_step")]
    assert len(prof["engine.tick"]) == tr.span_count("engine.tick")
    for name in _PHASES + ("engine.admit.prefill",
                           "engine.admit.first_token"):
        assert len(prof[name]) == tr.span_count(name), name


def test_serve_programs_named_for_the_trace():
    """The trace's readers find sampling by its named scope and the serve
    step and prefill by their program names."""
    cfg, eng = _lm_engine()
    step = eng._step_fn.lower(eng.params, eng.pool.cache,
                              eng.decode_batch()).compile().as_text()
    assert "serve_step" in step.splitlines()[0]
    assert re.search(r'op_name="[^"]*obs\.sample/[^"]*sort', step)
    toks = jnp.zeros((1, 8), jnp.int32)
    prefill = eng._prefill_fn.lower(eng.params, toks, None).as_text(
        dialect="hlo")
    assert "_prefill" in prefill.splitlines()[0]


def test_engine_trace_valid_chrome_json(tmp_path):
    """The dump of an engine run is valid Chrome trace JSON whose
    lifecycle-span count equals requests_finished (the --trace-out
    acceptance check, in-process)."""
    cfg = get_smoke_config("qwen3-0.6b")
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    obs.reset()
    eng = ForecastEngine(cfg, params, num_slots=2, cache_len=CACHE_LEN)
    for i in range(3):
        eng.submit(Request(
            id=f"q{i}",
            prompt=rng.integers(0, cfg.vocab_size, 5 + i).astype(np.int32),
            max_new_tokens=3))
    eng.run(max_steps=200)
    path = obs.dump(str(tmp_path / "serve_trace.json"),
                    provenance=bench_gate.provenance())
    doc = json.load(open(path))
    lifecycles = [e for e in doc["traceEvents"]
                  if e["name"] == "req.lifecycle" and e["ph"] == "X"]
    assert len(lifecycles) == eng.metrics.requests_finished == 3


# ---------------------------------------------------------------------------
# Federated ring telemetry: one number, now four ways (subprocess — the
# emulated device count must be set before jax initializes)
# ---------------------------------------------------------------------------

def _run_sub(script: str, timeout: int = 900, **env_extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_TRACE", None)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


_RING_OBS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import obs
from repro.dist import fed, fedcomm

mesh = jax.make_mesh((8, 1), ("data", "model"))
axes = fed.aggregation_axes(mesh)
n = 8
rng = np.random.default_rng(0)
members = {"wq": {
    "lora_a": jnp.asarray(rng.random((n, 4, 33)).astype(np.float32)),
    "lora_b": jnp.asarray(rng.random((n, 33, 4)).astype(np.float32))}}
w = jnp.ones((n,), jnp.float32) / n
expected = fed.expected_collective_bytes(
    {"wq": {"lora_a": jax.ShapeDtypeStruct((4, 33), jnp.float32),
            "lora_b": jax.ShapeDtypeStruct((33, 4), jnp.float32)}},
    mesh, wire="int8")
ROUNDS = 3
with mesh:
    for _ in range(ROUNDS):
        fedcomm.ring_aggregate(members, w, mesh, wire="int8")
tr = obs.get_tracer()
# rounds 2..N hit the compiled-executable cache: the cached ledger must
# keep the telemetry flowing (counters scale linearly with rounds)
assert tr.counters["ring.rounds"] == ROUNDS, tr.counters
assert tr.span_count("fedcomm.ring_aggregate") == ROUNDS
for ax in axes:
    got = tr.counters[f"ring.wire_bytes.{ax}"]
    assert got == ROUNDS * expected[ax], (ax, got, expected[ax])
hops = tr.events("ring.hop")
assert hops and all(e["ph"] == "i" for e in hops)
assert sum(e["args"]["nbytes"] for e in hops) == \
    ROUNDS * sum(expected[ax] for ax in axes)
print("RING_OBS_OK")
"""


def test_ring_telemetry_matches_expected_collective_bytes():
    """The obs counter per federation axis equals
    fed.expected_collective_bytes EXACTLY, every round, including rounds
    served from the compiled-aggregation cache."""
    out = _run_sub(_RING_OBS)
    assert "RING_OBS_OK" in out


# ---------------------------------------------------------------------------
# fed_trainer round telemetry (host loop — no mesh needed)
# ---------------------------------------------------------------------------

def test_fed_trainer_round_telemetry():
    from repro.train.fed_trainer import federated_fit
    cfg = get_smoke_config("fedtime-llama2-7b")
    rng = np.random.default_rng(0)
    L, T, M, n_clients = cfg.fedtime.lookback, cfg.fedtime.horizon, 2, 4
    data = [(rng.standard_normal((6, L, M)).astype(np.float32),
             rng.standard_normal((6, T, M)).astype(np.float32))
            for _ in range(n_clients)]
    obs.reset()
    res = federated_fit(cfg, data, rounds=2, batch_size=2,
                        key=jax.random.PRNGKey(0), wire="int8")
    tr = obs.get_tracer()
    n_rounds = len(res.logs)
    assert tr.span_count("fed.round") == n_rounds
    assert tr.span_count("fed.aggregate") == n_rounds
    assert tr.span_count("fed.client_fit") >= n_rounds  # >=1 client/round
    # wire accounting mirrors the logs' metered comm exactly
    assert tr.counters["fed.wire_bytes"] == sum(
        l.comm.bytes_up + l.comm.bytes_down for l in res.logs)
    # int8 wire: every participating client carried an EF residual
    assert tr.hists["fed.ef_residual_norm"].count == \
        tr.span_count("fed.client_fit")
    # per-cluster adapter movement gauges exist for every cluster seen
    for l in res.logs:
        assert f"fed.adapter_delta_norm.cluster{l.cluster}" in tr.gauges
        assert f"fed.round_loss.cluster{l.cluster}" in tr.gauges


class _LateLoss:
    """A loss that reaches the host ``delay_s`` after the fit returns, as
    a dispatched computation's does."""

    def __init__(self, loss, delay_s):
        self.loss, self.delay_s = loss, delay_s

    def __float__(self):
        time.sleep(self.delay_s)
        return float(self.loss)


def test_client_fit_time_covers_the_fit(monkeypatch):
    """A client's recorded fit time (ledger wall time, the span) runs
    until its loss is on the host, not until the fit was dispatched."""
    from repro.train import fed_trainer
    delay_s = 0.2
    fit = fed_trainer.local_update

    def slow_fit(*a, **kw):
        ad, loss = fit(*a, **kw)
        return ad, _LateLoss(loss, delay_s)

    monkeypatch.setattr(fed_trainer, "local_update", slow_fit)
    cfg = get_smoke_config("fedtime-llama2-7b")
    rng = np.random.default_rng(0)
    L, T, M = cfg.fedtime.lookback, cfg.fedtime.horizon, 2
    data = [(rng.standard_normal((6, L, M)).astype(np.float32),
             rng.standard_normal((6, T, M)).astype(np.float32))
            for _ in range(3)]
    obs.reset()
    res = fed_trainer.federated_fit(cfg, data, rounds=1, batch_size=2,
                                    key=jax.random.PRNGKey(0))
    walls = [rec.wall_s for rec in res.fleet.records if rec.participated]
    assert walls and min(walls) >= delay_s
    fits = [e for e in obs.get_tracer().events("fed.client_fit")
            if e["ph"] == "X"]
    assert fits and min(e["dur"] for e in fits) >= 1e6 * delay_s
