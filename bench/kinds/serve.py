"""Serving cells: the continuous-batching engine (``serve.ForecastEngine``,
the engine behind ``launch.serve --engine``) driven through ``submit()`` and
``step()`` by an open- or closed-loop load generator.

Each token is timed when ``step()`` hands it to the harness: TTFT runs from
when the request was due (open loop) or sent (closed loop) to its first
token, and every gap between consecutive tokens of a request is an
inter-token latency.  After the window the engine steps on until every
request sent in it has its first token and until the finished requests
hold the cell's ``sample_tokens``; then its memory is read and freed, and
a sample of the finished requests, drawn from the seed with the longest
among them and covering ``sample_tokens`` served tokens, goes through the
plain float32 reference of the configuration's architecture (its module's
``logits_at``, ``bench/models/<model_type>.py``, which hands on to
``bench/reference/``): each served token's logit has to lie within the
cell's limit of the reference's best at that position.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List

import numpy as np

import common
import flops
from traffic import gen

DRAIN_S = 60.0          # how long a request due in the window may wait


class Harness:
    """Drives one engine and keeps the harness's own records: per request
    its due/sent time and token stamps, per tick the decode positions, per
    admission the prompt tokens prefilled and shared."""

    def __init__(self, engine, annotate):
        self.engine = engine
        self.ann = annotate
        self.stamps: Dict[str, List[float]] = {}
        self.due: Dict[str, float] = {}
        self.late: List[float] = []
        self.prompt_len: Dict[str, int] = {}
        self.ticks: List[tuple] = []        # (t_end, decode positions)
        self.fill: List[tuple] = []         # (t_end, pool blocks in use)
        self.admits: List[tuple] = []       # (t, prompt_len, prefilled, shared)
        self._pending: List[str] = []
        self.failed: set = set()
        orig_admit = engine._admit
        bs = engine.pool.block_size

        def admit(req):
            m = engine.metrics
            p0, s0 = m.prefill_tokens, m.shared_blocks
            with self.ann("bench.admit"):
                orig_admit(req)
            self.admits.append((time.perf_counter(), req.prompt_len,
                                m.prefill_tokens - p0,
                                min((m.shared_blocks - s0) * bs,
                                    req.prompt_len)))

        engine._admit = admit

    def _stream(self, rid, tok, last):
        self._pending.append(rid)

    def submit(self, r: dict, due: float) -> None:
        from repro.serve.request import Request
        now = time.perf_counter()
        self.due[r["id"]] = due
        self.late.append(now - due)
        self.prompt_len[r["id"]] = len(r["prompt"])
        self.stamps[r["id"]] = []
        with self.ann("bench.submit"):
            v = self.engine.submit(Request(
                id=r["id"], prompt=r["prompt"],
                max_new_tokens=r["max_new_tokens"], stream=self._stream))
        if not v.ok:
            self.failed.add(r["id"])

    def step(self) -> None:
        with self.ann("bench.step"):
            self.engine.step()
        t = time.perf_counter()
        positions = []
        for rid in self._pending:
            st = self.stamps[rid]
            if st:                       # produced by the serve step
                positions.append(self.prompt_len[rid] + len(st) - 1)
            st.append(t)
        self._pending.clear()
        self.ticks.append((t, positions))
        self.fill.append((t, self.engine.pool.blocks_in_use))

    def busy(self) -> bool:
        e = self.engine
        return bool(e.scheduler.pending or e.active_requests)

    def done(self, rid: str) -> bool:
        return rid in self.engine.finished or rid in self.failed


def _noop(name):
    return contextlib.nullcontext()


class OpenLoop:
    """Requests submitted when due, their instants counted from when the
    loop was made."""

    def __init__(self, h: Harness, reqs, annotate):
        self.h, self.reqs, self.ann = h, reqs, annotate
        self.start = time.perf_counter()
        self.i = 0

    def run(self, end: float) -> list:
        """Serve until ``end``; returns the requests submitted meanwhile."""
        h, reqs, sent = self.h, self.reqs, []
        while True:
            now = time.perf_counter()
            if now >= end:
                return sent
            while (self.i < len(reqs)
                   and self.start + reqs[self.i]["due_s"] <= now):
                r = reqs[self.i]
                h.submit(r, self.start + r["due_s"])
                sent.append(r)
                self.i += 1
            if h.busy():
                h.step()
            else:
                nxt = (self.start + reqs[self.i]["due_s"]
                       if self.i < len(reqs) else end)
                with self.ann("bench.wait"):
                    time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))


class ClosedLoop:
    """``clients`` callers; each sends its next request when its last one
    has all its tokens."""

    def __init__(self, h: Harness, reqs, clients: int):
        self.h, self.clients = h, clients
        self.queue = iter(reqs)
        self.live: Dict[int, dict] = {}

    def _send(self, c: int, sent: list) -> None:
        r = next(self.queue, None)
        if r is None:
            raise RuntimeError("the closed-loop mix ran out of requests: "
                               "raise its 'requests'")
        self.h.submit(r, time.perf_counter())
        sent.append(r)
        self.live[c] = r

    def run(self, end: float) -> list:
        """Serve until ``end``; returns the requests sent meanwhile."""
        sent: list = []
        if not self.live:
            for c in range(self.clients):
                self._send(c, sent)
        while time.perf_counter() < end:
            self.h.step()
            for c, r in list(self.live.items()):
                if self.h.done(r["id"]):
                    self._send(c, sent)
        return sent


def make_loop(h: Harness, reqs, mix: dict, annotate=None):
    """The load generator the mix names, ready to run."""
    if mix["loop"] == "open":
        return OpenLoop(h, reqs, annotate or _noop)
    return ClosedLoop(h, reqs, mix["clients"])


def warm_up(engine, mix, bucket, vocab, seed):
    """One request per prefill length the mix can produce, one repeat of a
    whole prompt (the full-hit path and a copy-on-write block copy), one
    that shares a block-aligned prefix, and enough decode steps."""
    from repro.serve.request import Request
    rng = np.random.default_rng(seed ^ 0xA11CE)
    prompts = []
    for P in gen.prefill_buckets(mix, bucket):
        prompts.append(rng.integers(0, vocab, P - 1, dtype=np.int32))
    prompts.append(prompts[0].copy())                      # whole-prompt hit
    bs = engine.pool.block_size
    prompts.append(np.concatenate([prompts[-1][:bs],      # shared prefix
                                   rng.integers(0, vocab, 3, dtype=np.int32)]))
    for k, p in enumerate(prompts):
        engine.submit(Request(id=f"warm{k}", prompt=p, max_new_tokens=4))
    engine.run()


def make_engine(cfg, ecfg: dict, params):
    """The engine as the configuration file sets it up."""
    from repro.serve.engine import ForecastEngine
    return ForecastEngine(
        cfg, params, num_slots=ecfg["num_slots"],
        cache_len=ecfg["cache_len"], paged=True,
        block_size=ecfg["block_size"], prefill_bucket=ecfg["prefill_bucket"],
        share_prefixes=ecfg["share_prefixes"], swap_tier=ecfg["swap_tier"])


class Session:
    """The timed path of one serving run, shared by the benchmark run, the
    calibration of the limits and the knee sweep: weights and requests
    from the seed, the engine as the configuration sets it up, warmed on
    every shape the mix uses, and the mix's load offered for its
    ``preroll_s`` so that the window opens on lanes and a pool in their
    steady state; then a window of load, the drain of the requests it
    sent, and the finishing of enough requests for the check."""

    def __init__(self, cell: dict, seed: int, seconds: float,
                 annotate=_noop):
        cdata, self.mix = cell["config_data"], cell["traffic_data"]
        self.model = common.model_module(cdata)
        self.cfg = self.model.model_config(cdata)
        ecfg = cdata["engine"]
        self.annotate = annotate
        preroll = self.mix.get("preroll_s", 0.0)
        self.params = self.model.params(self.cfg, seed)
        self.reqs = gen.serve_requests(self.mix, seed, self.cfg.vocab_size,
                                       preroll + seconds)
        self.engine = make_engine(self.cfg, ecfg, self.params)
        warm_up(self.engine, self.mix, ecfg["prefill_bucket"],
                self.cfg.vocab_size, seed)
        self.h = Harness(self.engine, annotate)
        self.loop = make_loop(self.h, self.reqs, self.mix, annotate)
        if preroll:
            self.loop.run(time.perf_counter() + preroll)

    def window(self, seconds: float):
        """Offer the mix's load for ``seconds``: (t0, end, requests sent)."""
        t0 = time.perf_counter()
        end = t0 + seconds
        return t0, end, self.loop.run(end)

    def drain(self, sent) -> None:
        """Step on, with no new requests, until every request sent in the
        window has its first token (or ``DRAIN_S`` has passed)."""
        h, cap = self.h, time.perf_counter() + DRAIN_S
        while any(not h.stamps[r["id"]] and r["id"] not in h.failed
                  for r in sent) and time.perf_counter() < cap and h.busy():
            h.step()

    def finished(self) -> dict:
        """The harness's requests that ran to their length."""
        return {rid: f for rid, f in self.engine.finished.items()
                if rid in self.h.stamps and f.reason == "length"}

    def finish(self, tokens: int) -> dict:
        """Step on until the finished requests hold ``tokens`` served
        tokens, enough for the check's sample (or ``DRAIN_S`` has passed),
        and return them."""
        cap = time.perf_counter() + DRAIN_S
        while (sum(len(f.tokens) for f in self.finished().values()) < tokens
               and self.h.busy() and time.perf_counter() < cap):
            self.h.step()
        return self.finished()

    def close(self) -> None:
        """Free the engine's pool and the weights before the reference."""
        del self.engine, self.h.engine, self.params
        gc.collect()


def run(cell: dict, args, jax, clock, device) -> int:
    spec = common.benchmark_spec()
    annotate = (jax.profiler.TraceAnnotation if args.trace else _noop)
    s = Session(cell, args.seed, args.seconds, annotate)
    h, engine, cfg = s.h, s.engine, s.cfg
    compiles = common.CompileCounter(jax)

    setup_s = clock.since_start()
    trace_dir = None
    prof = contextlib.nullcontext()
    if args.trace:
        import tracing
        trace_dir = f"{common.ROOT}/.bench_trace/{cell['name']}"
        prof = tracing.profile(jax, trace_dir)
    with prof:
        with annotate("bench.window"):
            t0, end, sent = s.window(args.seconds)
    compiles_in_window = compiles.n
    s.drain(sent)

    # ---- end-to-end numbers ------------------------------------------------
    ttft, gaps, out_tok = [], [], 0
    failed = set(h.failed)
    for r in sent:
        st = h.stamps[r["id"]]
        if st:
            ttft.append(st[0] - h.due[r["id"]])
        else:
            failed.add(r["id"])
    for st in h.stamps.values():         # pre-roll requests' tokens too
        out_tok += sum(1 for t in st if t0 < t <= end)
        gaps += [b - a for a, b in zip(st, st[1:]) if t0 < b <= end]
    e2e = {"setup_s": setup_s,
           "ttft_p90_ms": 1e3 * common.percentile(ttft, 90),
           "itl_p95_ms": 1e3 * common.percentile(gaps, 95),
           "serve_out_tokens_per_s": common.rate(out_tok, end - t0)}
    fill = [n for t, n in h.fill if t0 <= t <= end]
    pool_blocks = engine.pool.pool_blocks
    device["kv_pool_blocks"] = pool_blocks
    device["kv_pool_fill_mean"] = sum(fill) / len(fill) / pool_blocks
    device["kv_pool_fill_max"] = max(fill) / pool_blocks
    print(f"window {end - t0:.3f}s: {len(sent)} requests sent, "
          f"{len(ttft)} with a first token ({len(ttft) - int(0.9 * len(ttft))}"
          f" beyond p90), {len(gaps)} token gaps "
          f"({len(gaps) - int(0.95 * len(gaps))} beyond p95), "
          f"{out_tok} tokens, {compiles_in_window} compilations in the "
          f"window; KV pool blocks in use: mean "
          f"{device['kv_pool_fill_mean']:.4f}, max "
          f"{device['kv_pool_fill_max']:.4f} of {pool_blocks}", flush=True)

    layer = None
    breakdown = None
    if args.trace:
        import tracing
        tr = tracing.load_and_remove(trace_dir)
        summ = tracing.summary(tr)
        breakdown = summ["breakdown"]
        lo, hi = summ["lo"], summ["hi"]
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        ctx = {
            "kind": "serve", "model": s.model, "cfg": cfg, "trace": tr,
            "lo": lo, "hi": hi,
            "window_s": summ["window_s"], "busy_s": summ["busy_s"],
            "peak": flops.peaks(device["kind"]), "loop": s.mix["loop"],
            "block_size": engine.pool.block_size,
            "ticks": [(t, p) for t, p in h.ticks if t0 <= t <= end],
            "admits": [a for a in h.admits if t0 <= a[0] <= end],
            "late_s": h.late, "step_spans": tr.spans("bench.step"),
        }
        layer = {n: common.metric_reader(n)(ctx)
                 for n in common.per_layer_names(spec, cell["name"])}

    finished = s.finish(cell["limits"]["sample_tokens"])
    failed.update(engine.quarantined)
    for rid, f in engine.finished.items():
        if f.reason not in ("length", "eos"):
            failed.add(rid)
    device["memory_peak_bytes"] = common.memory_peak_bytes(jax,
                                                           cell["chips"])
    del engine
    s.close()

    # ---- correctness -------------------------------------------------------
    checks = check_outputs(s.model, cfg, cell, s.reqs, finished, args.seed)
    correct = all(c["ok"] for c in checks)
    metrics = (layer if args.trace else
               {k: e2e[k] for k in common.end_to_end_names(spec, cell["name"])})
    common.print_result(correct=correct, attempted=len(sent),
                        failed=len(failed), metrics=metrics,
                        units=common.metric_units(spec), device=device,
                        checks=checks, breakdown=breakdown)
    return 0


def sample(finished: dict, reqs_by_id: dict, seed: int, tokens: int) -> list:
    """Requests drawn from the seed, the longest first among them, until
    ``tokens`` served tokens are covered."""
    ids = sorted(finished)
    if not ids:
        return []
    longest = max(ids, key=lambda i: (len(reqs_by_id[i]["prompt"])
                                      + len(finished[i].tokens), i))
    rng = gen._rng(seed, 0xC4EC)
    order = [longest] + [i for i in rng.permutation(ids) if i != longest]
    out, n = [], 0
    for i in order:
        out.append(i)
        n += len(finished[i].tokens)
        if n >= tokens:
            break
    return out


def served_gap(model, params, cfg_file, prompt, served, length, rows_n,
               int8=False):
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position (or, for the control, the gap of the
    token the int8 reference puts first)."""
    import jax.numpy as jnp
    P, n = len(prompt), len(served)
    seq = np.zeros(length, np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:-1]
    rows = np.full(rows_n, P + n - 2, np.int32)
    rows[:n] = np.arange(P - 1, P + n - 1)
    lg = np.asarray(model.logits_at(params, cfg_file, jnp.asarray(seq),
                                    jnp.asarray(rows)))[:n]
    if int8:
        ctl = np.asarray(model.logits_at(params, cfg_file, jnp.asarray(seq),
                                         jnp.asarray(rows), int8=True))[:n]
        served = ctl.argmax(-1)
    best = lg.max(-1)
    return float(np.max(best - lg[np.arange(n), np.asarray(served)]))


def ref_shape(mix: dict) -> tuple:
    """(sequence length, rows) of the reference's one compiled program."""
    pre = mix.get("shared_prefix", {}).get("preamble_tokens", 0)
    S = pre + mix["prompt"]["max"] + mix["output"]["max"]
    return -(-S // 128) * 128, mix["output"]["max"]


def check_outputs(model, cfg, cell, reqs, finished, seed,
                  int8=False) -> List[dict]:
    cdata, mix = cell["config_data"], cell["traffic_data"]
    lim = cell["limits"]
    by_id = {r["id"]: r for r in reqs}
    wrong_len = sum(len(f.tokens) != by_id[i]["max_new_tokens"]
                    for i, f in finished.items())
    picked = sample(finished, by_id, seed, lim["sample_tokens"])
    params = model.params(cfg, seed)
    length, rows_n = ref_shape(mix)
    gap = 0.0
    for i in picked:
        gap = max(gap, served_gap(model, params, cdata,
                                  by_id[i]["prompt"],
                                  np.asarray(finished[i].tokens), length,
                                  rows_n, int8=int8))
    n_tok = sum(len(finished[i].tokens) for i in picked)
    print(f"reference: {len(picked)} requests, {n_tok} served tokens "
          f"compared", flush=True)
    return [
        {"name": "max_logit_gap", "value": gap,
         "limit": lim["max_logit_gap"], "ok": gap <= lim["max_logit_gap"]},
        {"name": "tokens_compared", "value": n_tok,
         "limit": lim["sample_tokens"], "ok": n_tok >= lim["sample_tokens"]},
        {"name": "wrong_lengths", "value": wrong_len, "limit": 0,
         "ok": wrong_len == 0},
    ]
