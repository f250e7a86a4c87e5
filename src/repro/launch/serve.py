"""Serving launcher: fixed-batch decode or the continuous-batching engine.

Fixed batch (the dry-run shape — one prefill, synchronous decode):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --batch 4 --prompt-len 64 --gen 32

Engine (request-level continuous batching over the same compiled step):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --engine \
      --slots 4 --trace 8 --arrival-rate 0.5 --gen 32

``--trace N`` synthesizes N requests with Poisson arrivals and mixed prompt
lengths; ``--requests FILE`` replays a JSON trace instead (a list of
objects with ``prompt`` or ``prompt_len``, ``max_new_tokens``, and optional
``arrival_step`` / ``temperature`` / ``top_k`` / ``top_p`` / ``seed``).

Fault tolerance (engine mode): ``--max-queue`` bounds the submit queue
with cost-aware load shedding, ``--deadline-s`` / ``--ttft-slo-s`` attach
default SLOs (cancelled mid-decode on miss), ``--journal PATH`` arms the
write-ahead request journal for crash recovery, and ``--virtual-clock`` /
``--step-time-s`` run the SLO clock deterministically.  Shed / quarantine
verdicts print per request; the summary grows a fault-tolerance line.

``--trace-out PATH`` dumps the run's ``repro.obs`` span timeline (request
lifecycles, engine decode steps, pool-utilization counters) as Chrome
trace-event JSON — open it at https://ui.perfetto.dev or chrome://tracing.
``--flight-out PATH`` arms the post-mortem flight recorder instead: the
last-N-events ring is written there at exit, on unhandled exception, and
on engine distress (park-storm, eviction) — cheap enough to leave on in
runs where the full tracer is off.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_serve_step
from repro.models.registry import get_model, train_batch_shapes


def make_trace(cfg, n: int, *, gen: int, max_prompt: int, rate: float,
               seed: int = 0):
    """Synthetic Poisson request trace (arrival steps, mixed prompt
    lengths) as plain dicts — shared with benchmarks/serving_bench.py."""
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(rng.exponential(1.0 / max(rate, 1e-6),
                                                  n))).astype(int)
    out = []
    for i in range(n):
        plen = int(rng.integers(max(4, max_prompt // 4), max_prompt + 1))
        out.append({
            "id": f"req{i}",
            "prompt": rng.integers(0, cfg.vocab_size, plen).tolist(),
            "max_new_tokens": gen,
            "arrival_step": int(arrivals[i]),
        })
    return out


def load_trace(path: str, cfg, *, gen: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for i, r in enumerate(json.load(open(path))):
        prompt = r.get("prompt")
        if prompt is None:
            prompt = rng.integers(0, cfg.vocab_size,
                                  int(r["prompt_len"])).tolist()
        out.append({**r, "id": r.get("id", f"req{i}"), "prompt": prompt,
                    "max_new_tokens": int(r.get("max_new_tokens", gen)),
                    "arrival_step": int(r.get("arrival_step", 0))})
    return out


def _to_request(r: dict):
    from repro.serve import Request, SamplingParams
    deadline = r.get("deadline_s")
    ttft_slo = r.get("ttft_slo_s")
    return Request(
        id=r["id"], prompt=np.asarray(r["prompt"], np.int32),
        max_new_tokens=r["max_new_tokens"],
        arrival_step=r.get("arrival_step", 0),
        eos_id=r.get("eos_id"),
        deadline_s=None if deadline is None else float(deadline),
        ttft_slo_s=None if ttft_slo is None else float(ttft_slo),
        sampling=SamplingParams(
            temperature=float(r.get("temperature", 0.0)),
            top_k=int(r.get("top_k", 0)),
            top_p=float(r.get("top_p", 0.0)),
            seed=int(r.get("seed", 0))))


def run_engine(cfg, params, trace, *, slots: int, cache_len: int,
               max_tokens_in_flight: int = 0, prefill_chunk: int = 0,
               prefill_bucket: int = 0, paged=None, block_size: int = 0,
               pool_blocks: int = 0, share_prefixes=None, swap_tier=None,
               max_queue=None, deadline_s=None, ttft_slo_s=None,
               journal=None, clock=None, step_time_s=None,
               quiet: bool = False):
    from repro.serve import ForecastEngine
    engine = ForecastEngine(cfg, params, num_slots=slots,
                            cache_len=cache_len,
                            max_tokens_in_flight=max_tokens_in_flight,
                            prefill_chunk=prefill_chunk,
                            prefill_bucket=prefill_bucket,
                            paged=paged, block_size=block_size,
                            pool_blocks=pool_blocks,
                            share_prefixes=share_prefixes,
                            swap_tier=swap_tier,
                            max_queue=max_queue,
                            default_deadline_s=deadline_s,
                            default_ttft_slo_s=ttft_slo_s,
                            journal=journal, clock=clock,
                            step_time_s=step_time_s)
    for r in trace:
        verdict = engine.submit(_to_request(r))
        if not verdict.ok and not quiet:
            # surface backpressure to the caller: a shed request should be
            # retried after retry_after_s, a quarantined one should not
            print(f"submit {verdict.id}: {verdict.verdict}"
                  + (f" (retry after {verdict.retry_after_s:.2f}s)"
                     if verdict.verdict == "shed" else "")
                  + (f" [{verdict.reason}]" if verdict.reason else ""))
    done = engine.run()
    summ = engine.metrics.summary()
    if not quiet:
        pool_kind = (f"paged ({engine.pool.pool_blocks} blocks x "
                     f"{engine.pool.block_size})" if engine.paged
                     else "contiguous lanes")
        print(f"engine: {summ['requests']} requests, "
              f"{summ['decode_tokens']} tokens in {summ['decode_steps']} "
              f"steps ({summ['tok_per_s']:.1f} tok/s aggregate, "
              f"{summ['steady_tok_per_s']:.1f} tok/s steady decode)")
        print(f"        mean TTFT {summ['mean_ttft_s'] * 1e3:.0f}ms, "
              f"occupancy {summ['mean_occupancy']:.2f}, block util "
              f"{summ['mean_block_utilization']:.2f} [{pool_kind}], "
              f"peak in-flight {summ['peak_in_flight']}, "
              f"parked {summ['parked_events']}, "
              f"evicted {summ['evictions']}, "
              f"fragmentation {summ['mean_fragmentation']:.2f} mean / "
              f"{summ['peak_fragmentation']:.2f} peak, "
              f"compiled serve_step signatures: "
              f"{engine.num_step_signatures()}")
        if (summ["shed"] or summ["deadline_misses"] or summ["quarantined"]
                or engine.journal is not None):
            print(f"        fault tolerance: {summ['shed']} shed, "
                  f"{summ['deadline_misses']} deadline-missed "
                  f"({summ['ttft_slo_misses']} TTFT-SLO), "
                  f"{summ['quarantined']} quarantined, "
                  f"deadline miss rate {summ['deadline_miss_rate']:.3f}"
                  + (f", journal {engine.journal.path}"
                     if engine.journal is not None else ""))
        if engine.paged and (engine.share_prefixes or engine.swap_tier):
            print(f"        prefix sharing: {summ['share_hits']} hits "
                  f"({summ['full_prompt_hits']} full-prompt, "
                  f"{summ['shared_blocks']} blocks shared, "
                  f"{summ['cow_copies']} CoW copies), swap tier: "
                  f"{summ['swap_outs']} out / {summ['swap_ins']} in "
                  f"({summ['swap_out_bytes']} B out)")
    return done, summ, engine


def run_fixed_batch(cfg, params, api, *, batch: int, prompt_len: int,
                    gen: int) -> None:
    B, P = batch, prompt_len
    total = P + gen

    rng = np.random.default_rng(0)
    fb = {}
    shapes = train_batch_shapes(cfg, B, P)
    shapes.pop("labels")
    for k, (shp, dt) in shapes.items():
        if dt == jnp.int32:
            fb[k] = jnp.asarray(rng.integers(0, cfg.vocab_size, shp),
                                jnp.int32)
        else:
            fb[k] = jnp.zeros(shp, dt)

    t0 = time.time()
    cache, logits = api.prefill(params, cfg, fb, cache_len=total)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    print(f"prefill: {B}x{P} in {t_prefill:.2f}s "
          f"({B * P / t_prefill:.0f} tok/s)")

    serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    tok = jnp.argmax(logits[:, -1:, :], -1).astype(jnp.int32)
    generated = [np.asarray(tok)]
    # prompt positions vary per family (vlm prepends image tokens)
    pos0 = P + (cfg.vlm.num_image_tokens if cfg.family == "vlm" else 0)

    # warmup: the first step carries jit compile time — time it apart so
    # the reported decode throughput is steady-state
    t0 = time.time()
    tok, cache = serve(params, cache,
                       {"token": tok, "pos": jnp.asarray(pos0, jnp.int32)})
    jax.block_until_ready(tok)
    t_warm = time.time() - t0
    generated.append(np.asarray(tok))

    t0 = time.time()
    for i in range(1, gen):
        tok, cache = serve(params, cache,
                           {"token": tok, "pos": jnp.asarray(pos0 + i,
                                                             jnp.int32)})
        generated.append(np.asarray(tok))
    jax.block_until_ready(tok)
    dt = time.time() - t0
    out = np.concatenate(generated, axis=1)
    steady = B * (gen - 1) / dt if gen > 1 else 0.0
    print(f"decode warmup (incl. jit): 1 step x {B} seqs in {t_warm:.2f}s")
    print(f"decode steady-state: {gen - 1} steps x {B} seqs in {dt:.2f}s "
          f"({steady:.1f} tok/s)")
    print(f"sample continuation (seq 0): {out[0][:16].tolist()}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--full-config", action="store_true",
                    help="published widths instead of the smoke config")
    # engine mode
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine instead of one fixed "
                         "batch")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=0,
                    help="per-slot ring length (default prompt+gen)")
    ap.add_argument("--trace", type=int, default=0,
                    help="synthesize N Poisson-arrival requests")
    ap.add_argument("--requests", default="",
                    help="JSON request trace file (see module docstring)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="mean arrivals per engine step (--trace)")
    ap.add_argument("--max-tokens-in-flight", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--prefill-bucket", type=int, default=0)
    ap.add_argument("--trace-seed", type=int, default=0)
    # paged block-KV pool (default: auto — on for uniform-ring dense/moe)
    ap.add_argument("--paged", dest="paged", action="store_const", const=True,
                    default=None, help="force the paged block-KV pool")
    ap.add_argument("--no-paged", dest="paged", action="store_const",
                    const=False, help="force contiguous per-slot lanes")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged pool block size (0 = divisor of the ring "
                         "nearest REPRO_PAGED_BLOCK, default 16)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="physical blocks in the paged pool (0 = full "
                         "capacity slots*blocks_per_slot; less "
                         "oversubscribes lanes against real footprints)")
    ap.add_argument("--share-prefixes", dest="share_prefixes",
                    action="store_const", const=True, default=None,
                    help="copy-on-write prefix sharing across lanes "
                         "(default on for paged pools; "
                         "REPRO_PREFIX_SHARE=0 disables)")
    ap.add_argument("--no-share-prefixes", dest="share_prefixes",
                    action="store_const", const=False,
                    help="disable prefix sharing (every lane owns private "
                         "blocks)")
    ap.add_argument("--swap-tier", dest="swap_tier", action="store_const",
                    const=True, default=None,
                    help="host-memory swap tier for displaced lanes "
                         "(default on for paged pools; REPRO_SWAP_TIER=0 "
                         "disables)")
    ap.add_argument("--no-swap-tier", dest="swap_tier", action="store_const",
                    const=False,
                    help="disable the swap tier (displaced lanes recompute)")
    # fault tolerance (engine mode; see repro.serve.engine docstring)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded submit queue: admission backpressure "
                         "sheds the cheapest-to-retry queued request when "
                         "full (0 = unbounded; REPRO_SERVE_MAX_QUEUE)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="default whole-request deadline in engine-clock "
                         "seconds (REPRO_SERVE_DEADLINE_S); per-request "
                         "deadline_s in a --requests trace overrides")
    ap.add_argument("--ttft-slo-s", type=float, default=None,
                    help="default first-token SLO in engine-clock seconds "
                         "(REPRO_SERVE_TTFT_SLO_S)")
    ap.add_argument("--journal", default="",
                    help="write-ahead request journal path: submits/tokens/"
                         "finishes are logged so a crashed engine replays "
                         "unfinished requests bit-identically "
                         "(REPRO_SERVE_JOURNAL)")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="run SLO deadlines on fault.clock.VirtualClock "
                         "(each engine step advances --step-time-s) instead "
                         "of wall time — deterministic deadline tests")
    ap.add_argument("--step-time-s", type=float, default=None,
                    help="virtual seconds per engine step under "
                         "--virtual-clock (REPRO_SERVE_STEP_S, default "
                         "0.05)")
    ap.add_argument("--trace-out", default="",
                    help="write the repro.obs span timeline as Chrome "
                         "trace-event JSON (Perfetto / chrome://tracing)")
    ap.add_argument("--flight-out", default="",
                    help="arm the crash-dump flight recorder: write the "
                         "last-N-events ring here at exit / on exception / "
                         "on engine distress (park-storm, evict) — works "
                         "with REPRO_TRACE=0")
    args = ap.parse_args()

    if args.flight_out:
        import os
        os.environ["REPRO_FLIGHT_OUT"] = args.flight_out

    enable_compile_cache()
    cfg = (get_config if args.full_config else get_smoke_config)(args.arch)
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0))

    if args.engine:
        if args.requests:
            trace = load_trace(args.requests, cfg, gen=args.gen,
                               seed=args.trace_seed)
        else:
            trace = make_trace(cfg, args.trace or 8, gen=args.gen,
                               max_prompt=args.prompt_len,
                               rate=args.arrival_rate, seed=args.trace_seed)
        cache_len = args.cache_len or max(
            len(r["prompt"]) + r["max_new_tokens"] for r in trace)
        print(f"decode path: {ops.decode_mode(cache_len)}")
        clock = None
        if args.virtual_clock:
            from repro.fault.clock import VirtualClock
            clock = VirtualClock()
        run_engine(cfg, params, trace, slots=args.slots, cache_len=cache_len,
                   max_tokens_in_flight=args.max_tokens_in_flight,
                   prefill_chunk=args.prefill_chunk,
                   prefill_bucket=args.prefill_bucket,
                   paged=args.paged, block_size=args.block_size,
                   pool_blocks=args.pool_blocks,
                   share_prefixes=args.share_prefixes,
                   swap_tier=args.swap_tier,
                   max_queue=args.max_queue or None,
                   deadline_s=args.deadline_s,
                   ttft_slo_s=args.ttft_slo_s,
                   journal=args.journal or None,
                   clock=clock, step_time_s=args.step_time_s)
    else:
        print(f"decode path: {ops.decode_mode(args.prompt_len + args.gen)}")
        run_fixed_batch(cfg, params, api, batch=args.batch,
                        prompt_len=args.prompt_len, gen=args.gen)

    if args.trace_out:
        from repro.obs import bench_gate
        path = obs.dump(args.trace_out, provenance=bench_gate.provenance())
        print(f"trace: wrote {path} "
              f"(open at https://ui.perfetto.dev or chrome://tracing)")


if __name__ == "__main__":
    main()
