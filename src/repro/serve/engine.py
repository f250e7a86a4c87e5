"""Continuous-batching forecast-serving engine over the sharded decode path.

The step loop the ROADMAP's top open item asks for: requests are admitted
FIFO under token budgets (``scheduler``), prefilled into a free lane of the
preallocated cache pool (``cache_pool``), then decoded *together* by the one
compiled ragged ``serve_step`` — per-slot positions, per-slot sampling
params, inactive lanes masked and frozen — until each request hits its
horizon or stop token and its lane is recycled.  Batch composition changes
every step; the compiled step signature never does (asserted by
``num_step_signatures``), which is what lets one jit serve an arbitrary
request trace.

Cache layout: uniform attention-ring families (dense/moe without
local/global alternation) default to the **paged block pool** — one shared
block pool plus per-lane block tables, so a lane only pins the blocks its
tokens occupy and short requests stop reserving full ``cache_len`` lanes
(REPRO_PAGED_KV=0 or ``paged=False`` restores contiguous lanes; SSM/hybrid
state lanes are always dense).  Paged decode grants blocks on demand as a
request's write position crosses a block boundary; on pool exhaustion the
request **parks** (its lane masked inactive, its blocks and neighbours
untouched) until frees arrive, and if *every* resident is parked the
youngest is moved out of the pool so the engine never livelocks while
holding blocks hostage.

Prefix sharing (``share_prefixes``, default on for paged pools /
REPRO_PREFIX_SHARE=0 disables): admission consults the pool's prefix-hash
index.  A whole-prompt hit maps every prefix block read-only (refcount
bump, zero new blocks) and skips prefill entirely — the chain's stored
last-token logits seed the first sample, so a cluster of users replaying
the same history costs one prefill total.  A partial block-aligned hit
shares the matched blocks and prefills as usual, with the shared blocks
masked out of the insert scatter (the donor's data is bit-identical —
deterministic prefill at equal positions).  The first write that would
land in a block with refcount > 1 copy-on-writes it in the grant pass:
fresh block, device tile copy, table remap, decref.  Admission pricing
(``blocks_needed``) counts only unshared blocks, so sharers admit even
when the free list alone couldn't cover them.

Swap tier (``swap_tier``, default on for paged pools / REPRO_SWAP_TIER=0
disables): the livelock-breaker snapshots the victim lane's logical ring
on device (async gather — it drains to host np arrays behind later decode
steps), frees its blocks, and requeues the request; on re-admission the
saved ring is re-inserted through the same compiled insert and decode
resumes bit-exactly where it left off — no recompute, TTFT keeps the
original submit time.  Evict-and-recompute (``_evict``) remains the final
fallback (swap tier off, or the handle is gone).  Same-tick victims are
requeued in one batch ordered by original submit order, so multi-eviction
ticks preserve FIFO.

Decode composes with the whole serving stack: fused flash-decode kernels
(``REPRO_FLASH_DECODE``; block tables ride a scalar-prefetch operand), int8
caches (``REPRO_KV_INT8``), and seq-sharded cache layouts
(``REPRO_CACHE_SHARD=seq`` under an active mesh — rings shard the slot
axis, paged pools the block axis, with the same pmax/psum combine).
Shared blocks change none of it: tables are read-only to the kernels, so a
physical block appearing in several tables just streams the same tile to
each sharer.

    engine = ForecastEngine(cfg, params, num_slots=8, cache_len=256)
    engine.submit(Request(id="r0", prompt=toks, max_new_tokens=32))
    done = engine.run()              # {id: FinishedRequest}

Fault tolerance (the serving mirror of ``repro.fault``'s training story):

  * **SLOs** — requests may carry ``deadline_s`` (whole-request) and
    ``ttft_slo_s`` (first-token) windows, measured on the engine clock
    from first submit.  With a ``fault.clock.VirtualClock`` the engine
    advances ``step_time_s`` virtual seconds per tick (no ``time.sleep``
    anywhere); without one it reads ``time.perf_counter()``.  A sweep at
    the top of every tick cancels expired queued AND resident requests
    mid-decode with full reclamation — lane batch rows zeroed, blocks
    released (refcounts/partition preserved), swap handles dropped — and
    audits each as a ``serve.deadline_miss`` instant + a finished record
    with reason ``"deadline"``/``"ttft_slo"`` carrying partial tokens.
  * **Backpressure** — ``max_queue`` bounds the submit queue; on overflow
    the engine sheds the cheapest-to-retry candidate (fewest total
    tokens, newest-first on ties, NEVER a request past first token —
    resumes are exempt) and ``submit`` returns a ``SubmitVerdict`` with a
    deterministic ``retry_after_s`` hint instead of raising.
  * **Quarantine** — ``submit`` screens prompts against the vocab
    (malformed requests quarantine before touching the device);
    ``fault.guard.logits_finite`` runs inside the compiled step on every
    decode slice, and a lane going non-finite is quarantined alone: no
    token emitted, blocks released, neighbours' lanes untouched, audit in
    ``engine.quarantined`` + a flight-recorder repro bundle.  The chaos
    NaN injector (``engine.poison(id)``) rides the same step via a
    ``poison`` batch row, so arming it never adds a jit signature.
  * **Journal** — ``journal=`` (or ``REPRO_SERVE_JOURNAL``) write-ahead
    logs submits/tokens/finishes (``serve/journal.py``, per-record CRC +
    fsync); after a crash ``replay_journal(path).unfinished_requests()``
    resubmits every incomplete request with its generated tokens as
    resume state — decode continues bit-identically (the fold_in sample
    counter continues), zero lost or duplicated requests.

Env knobs (each the default for the corresponding ctor arg):
``REPRO_SERVE_MAX_QUEUE`` (int, 0 = unbounded), ``REPRO_SERVE_DEADLINE_S``
/ ``REPRO_SERVE_TTFT_SLO_S`` (floats, applied to requests that don't set
their own), ``REPRO_SERVE_STEP_S`` (virtual seconds per tick under a
virtual clock, default 0.05), ``REPRO_SERVE_JOURNAL`` (journal path).

Observability (``repro.obs``, ``REPRO_TRACE=0`` disables): every request
gets its own Perfetto track carrying the lifecycle
``req.submit -> req.queued -> engine.admit -> req.first_token ->
req.decode -> req.lifecycle -> req.retire`` (park/evict as instant
events, plus ``pool.share_hit`` / ``pool.cow_copy`` / ``pool.swap_out`` /
``pool.swap_in`` instants with byte counts whenever sharing or the swap
tier fire).  ``engine.admit`` (args ``queued_ms`` — since the request last
entered the queue —, ``prompt_len``, ``prefilled``, ``shared_tokens``,
``resumed``) lasts until the admission's last host sync, with a child span
per sync point: ``engine.admit.prefill``, ``.first_token``, ``.index``
(or ``.swap_in``).  Each tick is an ``engine.tick`` span holding its
phases: ``engine.slo_sweep``, ``engine.schedule``, the admissions,
``engine.grant``, ``engine.batch``, ``engine.decode_step`` (``active`` of
``lanes``; on the paged Pallas flash-decode path also ``live_pages``, the
table entries the kernel visits), ``engine.emit`` and
``engine.swap_drain``; under the JAX profiler every one is also an
annotation with its args, so host phases and XLA device work share one
timeline.  A ``pool`` counter track samples
blocks in use and active lanes each decode.  Exactly one
``req.lifecycle`` span is emitted per FINISHED request — eviction and
recompute re-emit the per-residency phases, never the lifecycle — so a
trace's lifecycle-span count always equals ``requests_finished``.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.fault.clock import VirtualClock
from repro.kernels import ops
from repro.launch.steps import make_serve_step
from repro.models.registry import get_model
from repro.serve.cache_pool import (PAGED_FAMILIES, CachePool,
                                    PagedCachePool)
from repro.serve.journal import RequestJournal
from repro.serve.metrics import EngineMetrics
from repro.serve.request import (FinishedRequest, GenState,
                                 QuarantinedRequest, Request, SubmitVerdict)
from repro.serve.sampling import sample_vec
from repro.serve.scheduler import (FIFOScheduler, SchedulerConfig,
                                   bucket_len)

# families whose batch dict is {"tokens"} and whose decode path supports
# per-slot ragged positions (attention rings via attn_decode, SSM states
# via the serve-step freeze)
_SERVABLE = ("dense", "moe", "ssm", "hybrid")
_BUCKETABLE = ("dense", "moe")               # right-pad-safe prefill (causal
                                             # attention only, no recurrence)


class ForecastEngine:
    """Request-level serving engine: admit -> prefill-into-slot -> batched
    ragged decode -> retire."""

    def __init__(self, cfg: ModelConfig, params, *, num_slots: int = 4,
                 cache_len: int = 256, max_tokens_in_flight: int = 0,
                 prefill_chunk: int = 0, prefill_bucket: int = 0,
                 force_window: int = 0, paged: Optional[bool] = None,
                 block_size: int = 0, pool_blocks: int = 0,
                 share_prefixes: Optional[bool] = None,
                 swap_tier: Optional[bool] = None,
                 clock: Optional[VirtualClock] = None,
                 step_time_s: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 default_ttft_slo_s: Optional[float] = None,
                 journal=None):
        if cfg.family not in _SERVABLE:
            raise ValueError(f"family {cfg.family!r} not servable by the "
                             f"engine (supported: {_SERVABLE})")
        if prefill_bucket and cfg.family not in _BUCKETABLE:
            raise ValueError(f"prefill_bucket requires a causal-attention "
                             f"prefill (families {_BUCKETABLE}); "
                             f"{cfg.family!r} carries recurrent state "
                             f"through pad tokens")
        self.cfg = cfg
        self.params = params
        self.api = get_model(cfg)
        self.prefill_bucket = prefill_bucket
        self.force_window = force_window
        if paged is None:                     # default on where eligible
            paged = (os.environ.get("REPRO_PAGED_KV", "1") != "0"
                     and cfg.family in PAGED_FAMILIES
                     and not cfg.local_global_alternating)
        self.paged = paged
        if paged:
            self.pool = PagedCachePool(cfg, num_slots, cache_len,
                                       block_size=block_size,
                                       pool_blocks=pool_blocks,
                                       force_window=force_window)
        else:
            if block_size or pool_blocks:
                raise ValueError("block_size/pool_blocks require paged=True")
            if share_prefixes or swap_tier:
                raise ValueError("share_prefixes/swap_tier require the "
                                 "paged pool")
            self.pool = CachePool(self.api, cfg, num_slots, cache_len,
                                  force_window=force_window)
        # the paged Pallas flash-decode launch, which visits only each
        # lane's live table entries (the page-visit counter counts those)
        self._paged_kernel = paged and ops.pallas_decode(self.pool.ring_len)
        # CoW prefix sharing + host swap tier: paged-pool features, on by
        # default there (REPRO_PREFIX_SHARE=0 / REPRO_SWAP_TIER=0 or the
        # ctor args turn them off independently)
        self.share_prefixes = bool(paged and (
            share_prefixes if share_prefixes is not None
            else os.environ.get("REPRO_PREFIX_SHARE", "1") != "0"))
        self.swap_tier = bool(paged and (
            swap_tier if swap_tier is not None
            else os.environ.get("REPRO_SWAP_TIER", "1") != "0"))
        # swapped-out lanes: request id -> {"cache": leaves, "pos", "blocks"}
        # — leaves start as async device gathers and drain to host np arrays
        # behind later decode steps (see step())
        self.swap: Dict[str, dict] = {}
        self._swap_pending: List[str] = []
        # per-request submit sequence: multi-eviction ticks requeue in this
        # order, so FIFO survives same-tick victims (resumes keep the id)
        self._seq: Dict[str, int] = {}
        self.scheduler = FIFOScheduler(SchedulerConfig(
            max_tokens_in_flight=max_tokens_in_flight,
            prefill_chunk=prefill_chunk))
        self.metrics = EngineMetrics(num_slots,
                                     pool_blocks=self.pool.pool_blocks)
        self.step_count = 0
        self.finished: Dict[str, FinishedRequest] = {}
        self.slots: List[Optional[GenState]] = [None] * num_slots
        self._submit_time: Dict[str, float] = {}
        # when each queued request last entered the queue: its submit, or
        # its requeue after a park, eviction or swap-out
        self._queued_at: Dict[str, float] = {}

        # -- fault tolerance (SLOs / shedding / quarantine / journal) ----
        def _env_f(name):
            v = os.environ.get(name, "")
            return float(v) if v else None
        self.clock = clock
        # virtual seconds one engine tick costs on the SLO clock; only the
        # virtual clock advances by it (wall mode reads perf_counter)
        self.step_time_s = (step_time_s if step_time_s is not None
                            else _env_f("REPRO_SERVE_STEP_S") or 0.05)
        self.max_queue = (max_queue if max_queue is not None
                          else int(os.environ.get("REPRO_SERVE_MAX_QUEUE",
                                                  "0")))
        self._default_deadline_s = (default_deadline_s
                                    if default_deadline_s is not None
                                    else _env_f("REPRO_SERVE_DEADLINE_S"))
        self._default_ttft_slo_s = (default_ttft_slo_s
                                    if default_ttft_slo_s is not None
                                    else _env_f("REPRO_SERVE_TTFT_SLO_S"))
        if journal is None:
            journal = os.environ.get("REPRO_SERVE_JOURNAL") or None
        self.journal: Optional[RequestJournal] = (
            RequestJournal(journal) if isinstance(journal, str) else journal)
        self.quarantined: Dict[str, QuarantinedRequest] = {}
        self.shed_log: Dict[str, float] = {}   # id -> retry_after_s hint
        self._poison: set = set()              # chaos: ids to NaN-inject
        self._poison_row = np.zeros((num_slots,), bool)
        # SLO windows anchor at the FIRST submit (requeues/resumes keep
        # it); a shed request's re-submit starts a fresh window
        self._slo_submit: Dict[str, float] = {}
        # global-attention rings must hold the whole sequence: dense/moe
        # without a (forced) sliding window, and hybrid, whose attention
        # layers are always global.  Windowed archs wrap by design; pure
        # SSM state is O(1).
        self._ring_is_global = (
            cfg.family in _BUCKETABLE and cfg.sliding_window == 0
            and not force_window) or cfg.family == "hybrid"

        # fixed-shape per-slot batch arrays — the ONLY thing the compiled
        # step sees; host-side admission/eviction just rewrites rows
        self._tok = np.zeros((num_slots, 1), np.int32)
        self._pos = np.full((num_slots,), -1, np.int32)
        self._temp = np.zeros((num_slots,), np.float32)
        self._topk = np.zeros((num_slots,), np.int32)
        self._topp = np.zeros((num_slots,), np.float32)
        self._key = np.zeros((num_slots, 2), np.uint32)
        self._t = np.zeros((num_slots,), np.int32)

        self._step_fn = jax.jit(
            make_serve_step(cfg, force_window=force_window, sampling=True,
                            guard=True),
            donate_argnums=(1,))

        def _prefill(params, tokens, true_len):
            return self.api.prefill(params, cfg, {"tokens": tokens},
                                    cache_len=cache_len,
                                    force_window=force_window,
                                    true_len=true_len)

        self._prefill_fn = jax.jit(_prefill)

        def _first(logits, key, temp, top_k, top_p, t):
            # same finite screen the decode step runs: a prompt whose
            # prefill already went non-finite quarantines at admission
            lg = logits[:, -1, :]
            ok = jnp.all(jnp.isfinite(lg))
            keys = jax.random.fold_in(key, t)[None]
            return sample_vec(keys, lg, temperature=temp[None],
                              top_k=top_k[None], top_p=top_p[None])[0], ok

        self._first_fn = jax.jit(_first)

    # -- public surface ------------------------------------------------------

    def submit(self, request: Request) -> SubmitVerdict:
        """Queue a request.  Structural impossibilities (footprint that
        could never admit) still raise — they are caller bugs; traffic
        conditions return a verdict instead: ``"quarantined"`` for
        malformed prompts (audited, never queued) and ``"shed"`` under
        backpressure (bounded ``max_queue``, cheapest-to-retry
        newest-first victim, never a request past first token)."""
        budget = self.scheduler.config.max_tokens_in_flight
        if budget > 0 and request.total_tokens > budget:
            # would never admit: run() would spin on it forever
            raise ValueError(
                f"request {request.id}: total tokens "
                f"({request.total_tokens}) exceed max_tokens_in_flight "
                f"({budget}) — it could never be admitted")
        footprint = max(request.total_tokens,
                        bucket_len(request.prompt_len, self.prefill_bucket))
        if self._ring_is_global and footprint > self.pool.cache_len:
            raise ValueError(
                f"request {request.id}: prompt + horizon (bucketed: "
                f"{footprint}) exceeds cache_len ({self.pool.cache_len})")
        if self.paged:
            need = self.pool.blocks_for(footprint)
            if need > self.pool.pool_blocks:
                # even alone it would park forever: reject at submit
                raise ValueError(
                    f"request {request.id}: needs {need} blocks, pool has "
                    f"{self.pool.pool_blocks}")
        # malformed-prompt screen: out-of-vocab ids would index garbage
        # embeddings (or crash a gather) — quarantine before any device
        # work, audited like a mid-decode poison
        prompt = np.asarray(request.prompt)
        if int(prompt.min()) < 0 or int(prompt.max()) >= self.cfg.vocab_size:
            self._quarantine_submit(request, "malformed_prompt")
            return SubmitVerdict(request.id, "quarantined",
                                 reason="malformed_prompt")
        if request.deadline_s is None:
            request.deadline_s = self._default_deadline_s
        if request.ttft_slo_s is None:
            request.ttft_slo_s = self._default_ttft_slo_s
        self._seq.setdefault(request.id, len(self._seq))
        shed_id = None
        if self.max_queue > 0 and request.resume is None and \
                self.scheduler.pending >= self.max_queue:
            victim = self._shed_victim(request)
            if victim is request:
                self._record_shed(request, queued=False)
                return SubmitVerdict(request.id, "shed",
                                     retry_after_s=self._retry_after_s())
            self.scheduler.remove(victim)
            self._record_shed(victim, queued=True)
            shed_id = victim.id
        if request.resume is None:            # eviction re-queues internally
            obs.instant("req.submit", track=f"req:{request.id}",
                        id=request.id, prompt_len=request.prompt_len,
                        max_new_tokens=request.max_new_tokens)
            if self.journal is not None:
                self.journal.log_submit(request)
            self.metrics.record_submit()
        self._submit_time[request.id] = time.perf_counter()
        self._queued_at[request.id] = self._submit_time[request.id]
        # SLO anchor: resumes (journal replay, evict requeue) keep the
        # original window; a fresh submit — including a shed request's
        # retry — starts one
        res = request.resume or {}
        if request.resume is None:
            self._slo_submit[request.id] = self._now()
        else:
            self._slo_submit.setdefault(
                request.id,
                res.get("slo_submit") if res.get("slo_submit") is not None
                else self._now())
        self.scheduler.submit(request)
        return SubmitVerdict(request.id, "ok", shed_id=shed_id)

    def poison(self, request_id: str) -> None:
        """Chaos hook: NaN-inject this request's logits row on its next
        decode step (via the compiled step's ``poison`` batch input — no
        new jit signature).  The guard then quarantines the lane."""
        self._poison.add(request_id)

    @property
    def active_requests(self) -> int:
        return sum(s is not None for s in self.slots)

    # -- SLOs / shedding / quarantine ----------------------------------------

    def _now(self) -> float:
        """The engine's SLO clock: virtual when one was injected (chaos/
        CI — deadlines honored with zero ``time.sleep``), wall otherwise.
        Distinct from the wall-clock TTFT/throughput metrics."""
        return (self.clock.now() if self.clock is not None
                else time.perf_counter())

    def _retry_after_s(self) -> float:
        """Deterministic backoff hint for a shed request: roughly the
        engine-seconds needed to drain the current queue through the
        available lanes."""
        steps = self.scheduler.pending_tokens() / max(len(self.slots), 1)
        return self.step_time_s * (steps + 1.0)

    def _shed_victim(self, incoming: Request) -> Request:
        """Cheapest-to-retry, newest-first: fewest total tokens, ties to
        the latest submit sequence.  Only requests that have produced no
        token are candidates (queued resumes carry generated tokens and a
        paid-for TTFT — shedding them wastes finished work and breaks the
        'never past first token' contract), so the incoming request is
        always a candidate of last resort."""
        cands = [incoming] + [q for q in self.scheduler.queued()
                              if q.resume is None]
        return min(cands, key=lambda r: (r.total_tokens,
                                         -self._seq.get(r.id, 0)))

    def _record_shed(self, req: Request, *, queued: bool) -> None:
        retry = self._retry_after_s()
        self.metrics.record_shed()
        self.shed_log[req.id] = retry
        self._slo_submit.pop(req.id, None)
        self._queued_at.pop(req.id, None)
        obs.instant("serve.shed", track=f"req:{req.id}", id=req.id,
                    queued=queued, retry_after_s=retry,
                    queue_depth=self.scheduler.pending,
                    total_tokens=req.total_tokens)
        obs.counter("serve.shed", 1)
        if queued and self.journal is not None:
            # the victim's submit is journaled: close it so replay never
            # resurrects a request we told the client to retry
            self.journal.log_finish(req.id, "shed")

    def _quarantine_submit(self, req: Request, reason: str) -> None:
        """Park a request that failed the submit-time screen: audited,
        never queued, never touching the device."""
        self.quarantined[req.id] = QuarantinedRequest(
            req.id, reason, self.step_count, req.prompt_len, 0)
        self.metrics.record_quarantine(reason)
        self._audit_quarantine(req, reason, slot=-1, generated=0)

    def _quarantine_lane(self, st: GenState, reason: str) -> None:
        """Quarantine ONE resident lane mid-decode: no token emitted, the
        lane's batch row zeroed and its blocks released (refcounts and the
        partition invariant preserved — neighbours never notice), audit +
        flight-recorder repro bundle dumped."""
        req, slot = st.request, st.slot
        res = req.resume or {}
        self.quarantined[req.id] = QuarantinedRequest(
            req.id, reason, self.step_count,
            int(res.get("prompt_len", req.prompt_len)), len(st.generated))
        self.metrics.record_quarantine(reason)
        self._clear_lane_rows(slot)
        self._audit_quarantine(req, reason, slot=slot,
                               generated=len(st.generated))

    def _audit_quarantine(self, req: Request, reason: str, *, slot: int,
                          generated: int) -> None:
        self._poison.discard(req.id)
        self._slo_submit.pop(req.id, None)
        sp = req.sampling
        # the instant doubles as the repro bundle: enough of the request
        # (prompt head, sampling knobs, progress) rides into the flight
        # dump to replay the poisoned step offline
        obs.instant("serve.quarantine", track=f"req:{req.id}", id=req.id,
                    reason=reason, slot=slot, step=self.step_count,
                    prompt_len=req.prompt_len, generated=generated,
                    prompt_head=[int(t) for t in
                                 np.asarray(req.prompt)[:16]],
                    seed=sp.seed, temperature=sp.temperature)
        obs.counter(f"serve.quarantine.{reason}", 1)
        if self.journal is not None:
            self.journal.log_finish(req.id, f"quarantined:{reason}")
        obs.flight_maybe_dump("engine.quarantine")

    def _clear_lane_rows(self, slot: int) -> None:
        """Full lane reclamation: GenState gone, every batch row zeroed,
        blocks back to the pool (CoW refcounts handled by release)."""
        self.slots[slot] = None
        self._pos[slot] = -1
        self._tok[slot, 0] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 0.0
        self._key[slot] = 0
        self._t[slot] = 0
        self.pool.release(slot)

    def _expiry(self, req: Request, started: bool,
                now: float) -> Optional[str]:
        """Which SLO (if any) ``req`` has blown at ``now``.  Windows are
        measured from the FIRST submit; a request finishing exactly at
        its deadline is on time (strict >)."""
        t0 = self._slo_submit.get(req.id)
        if t0 is None:
            return None
        if req.deadline_s is not None and now - t0 > req.deadline_s:
            return "deadline"
        if req.ttft_slo_s is not None and not started \
                and now - t0 > req.ttft_slo_s:
            return "ttft_slo"
        return None

    def _slo_sweep(self) -> None:
        """Top of every tick: cancel expired queued and resident requests
        BEFORE admission, so the blocks and lanes a cancellation frees are
        grantable in the same tick (the grant pass hands them out in
        submit order — cancellation never reorders FIFO resumption)."""
        if not self._slo_submit:
            return
        now = self._now()

        def q_kind(req: Request) -> Optional[str]:
            started = bool((req.resume or {}).get("generated"))
            return self._expiry(req, started, now)

        for req in self.scheduler.cancel_where(
                lambda r: q_kind(r) is not None):
            self._cancel_queued(req, q_kind(req), now)
        for st in [s for s in self.slots if s is not None]:
            kind = self._expiry(st.request, bool(st.generated), now)
            if kind is not None:
                self._retire(st, kind)

    def _cancel_queued(self, req: Request, kind: str, now: float) -> None:
        """Deadline-cancel a request that is not resident: drop its swap
        handle (host tier reclamation), finish it with whatever it
        generated in prior residencies, audit the miss."""
        res = req.resume or {}
        if res.get("swap") in self.swap:
            self.swap.pop(res["swap"])
        gen = [int(t) for t in res.get("generated", [])]
        t0 = self._slo_submit.pop(req.id, None)
        self._queued_at.pop(req.id, None)
        self.metrics.record_deadline_miss(ttft=kind == "ttft_slo")
        first = res.get("first_token_time") or 0.0
        submit_t = (res.get("submitted")
                    or self._submit_time.get(req.id, now))
        ttft = (first - submit_t) if first else None
        self.metrics.record_finish(ttft)
        track = f"req:{req.id}"
        obs.instant("serve.deadline_miss", track=track, id=req.id,
                    kind=kind, queued=True, generated=len(gen),
                    waited_s=now - t0 if t0 is not None else 0.0)
        obs.counter(f"serve.deadline_miss.{kind}", 1)
        wall = time.perf_counter()
        obs.add_span("req.lifecycle", submit_t, wall, track=track,
                     id=req.id, reason=kind, tokens=len(gen),
                     ttft_s=ttft or 0.0)
        obs.instant("req.retire", track=track, id=req.id, reason=kind)
        if self.journal is not None:
            self.journal.log_finish(req.id, kind)
        self.finished[req.id] = FinishedRequest(
            id=req.id, tokens=np.asarray(gen, np.int32),
            prompt_len=int(res.get("prompt_len", req.prompt_len)),
            admitted_step=-1, finished_step=self.step_count,
            ttft_s=ttft or 0.0, reason=kind)

    @property
    def tokens_in_flight(self) -> int:
        return sum(s.request.total_tokens for s in self.slots
                   if s is not None)

    def num_step_signatures(self) -> int:
        """Compiled serve_step signatures so far — the engine's no-re-jit
        invariant is that this stays 1 across every admission/eviction."""
        return self._step_fn._cache_size()

    def step(self) -> None:
        """One engine tick: sweep SLOs (cancellations free capacity for
        this very tick), admit what fits, grow/park paged lanes, then one
        batched decode.  Under a virtual clock the tick ends by advancing
        ``step_time_s`` virtual seconds; the journal (if any) commits its
        buffered token records at the same boundary."""
        with obs.step_span("engine.tick", self.step_count):
            with obs.span("engine.slo_sweep"):
                self._slo_sweep()
            with obs.span("engine.schedule"):
                admits = self.scheduler.admit(
                    now_step=self.step_count,
                    free_slots=self.pool.free_slots,
                    tokens_in_flight=self.tokens_in_flight,
                    free_blocks=self.pool.free_blocks if self.paged else -1,
                    blocks_needed=self._admit_blocks if self.paged else None)
            for req in admits:
                try:
                    self._admit(req)
                except RuntimeError:
                    # share-aware pricing raced a chain invalidation (or
                    # the pool shrank between pricing and grant): the
                    # admission was rolled back — put the request back at
                    # the head and stop admitting this tick
                    self.scheduler.requeue_front([req])
                    break
                self._queued_at.pop(req.id, None)
            if self.paged:
                with obs.span("engine.grant"):
                    self._grant_pass()
            self._decode()
            self.step_count += 1
            if self.journal is not None:
                self.journal.commit()
            if self.clock is not None:
                self.clock.advance(self.step_time_s)
            # drain swap-outs to host np arrays AFTER the decode dispatched —
            # the device gather overlaps the step instead of blocking it
            if self._swap_pending:
                with obs.span("engine.swap_drain"):
                    while self._swap_pending:
                        handle = self.swap.get(self._swap_pending.pop())
                        if handle is not None and not handle.get("host"):
                            handle["cache"] = jax.tree.map(np.asarray,
                                                           handle["cache"])
                            handle["host"] = True

    def run(self, max_steps: int = 0) -> Dict[str, FinishedRequest]:
        """Drive steps until every submitted request retires."""
        while self.scheduler.pending or self.active_requests:
            if max_steps and self.step_count >= max_steps:
                raise RuntimeError(f"engine did not drain within "
                                   f"{max_steps} steps")
            self.step()
        return self.finished

    # -- internals -----------------------------------------------------------

    def _bucketed_len(self, req: Request) -> int:
        P = req.prompt_len
        Pb = bucket_len(P, self.prefill_bucket)
        if req.resume and self._ring_is_global and Pb > self.pool.cache_len:
            return P            # resumed prompts skip bucketing on overflow
        return Pb

    def _admit_blocks(self, req: Request) -> int:
        """Paged admission price: blocks covering the prefill ring extent
        (decode growth is granted on demand).  Share-aware: blocks served
        by a live prefix chain cost nothing — a whole-prompt hit admits
        free, which is what lets a cluster of identical histories oversubscribe
        the same pool bytes.  A swap-tier resume prices its saved extent."""
        res = req.resume or {}
        if self.swap_tier and res.get("swap") in self.swap:
            handle = self.swap[res["swap"]]
            return self.pool.blocks_for(min(handle["pos"],
                                            self.pool.ring_len))
        need = self.pool.blocks_for(self._bucketed_len(req))
        if self.share_prefixes:
            shared, full_hit, _ = self.pool.match_prefix(req.prompt)
            if full_hit:
                return 0
            need -= len(shared)
        return max(need, 0)

    def _admit(self, req: Request) -> None:
        track = f"req:{req.id}"
        t_admit = time.perf_counter()
        res = req.resume or {}
        queued_at = self._queued_at.get(req.id, t_admit)
        obs.add_span("req.queued", queued_at, t_admit, track=track,
                     id=req.id)
        # the span ends at the admission's last host sync (the prefix
        # index's logits pull, else the first token), before the token is
        # handed out
        with obs.span("engine.admit", track=track, id=req.id,
                      queued_ms=1e3 * (t_admit - queued_at),
                      prompt_len=req.prompt_len,
                      resumed=req.resume is not None) as admit_span:
            slot = self.pool.acquire()
            if self.swap_tier and res.get("swap") in self.swap:
                handle = self.swap.pop(res["swap"])
                admit_span.set(prefilled=0, shared_tokens=0)
                try:
                    self._swap_in(req, slot, handle)
                except RuntimeError:           # pool raced below the price
                    self.swap[res["swap"]] = handle
                    self.pool.release(slot)
                    raise
                return
            P = req.prompt_len
            Pb = self._bucketed_len(req)
            shared: List[int] = []
            full_hit, chain_logits = False, None
            if self.paged:
                if self.share_prefixes:
                    shared, full_hit, chain_logits = \
                        self.pool.match_prefix(req.prompt)
                try:
                    self.pool.share_map(slot, shared)
                    if not full_hit:
                        self.pool.grant_tail(
                            slot, len(shared),
                            self.pool.blocks_for(Pb) - len(shared))
                except RuntimeError:           # pool raced below the price
                    self.pool.release(slot)    # decrefs any shared mapping
                    raise
                if shared:
                    self.metrics.record_share(len(shared), full_hit)
                    obs.instant("pool.share_hit", track=track, id=req.id,
                                slot=slot, blocks=len(shared),
                                full_prompt=bool(full_hit),
                                bytes=len(shared) * self.pool.block_bytes)
            zero_prefill = full_hit and chain_logits is not None
            admit_span.set(
                prefilled=0 if zero_prefill else P,
                shared_tokens=min(len(shared) * self.pool.block_size, P)
                if shared else 0)

            if zero_prefill:
                # whole prompt lives in the pool already: zero prefill,
                # zero new blocks — the chain's stored last-token logits
                # row seeds the first sample exactly as a fresh prefill's
                # would
                logits = jnp.asarray(chain_logits)[None, None]
                self.metrics.record_admit(0)
            else:
                toks = np.zeros((1, Pb), np.int32)
                toks[0, :P] = req.prompt
                # true_len rides along whenever bucketing is on (one
                # bucketed prefill signature even for exact-fit prompts); a
                # resume that skipped bucketing prefills at its exact length
                true_len = (jnp.asarray([P], jnp.int32)
                            if self.prefill_bucket
                            and (Pb != P or not req.resume) else None)
                with obs.span("engine.admit.prefill", track=track,
                              padded_len=Pb, slot=slot):
                    cache1, logits = self._prefill_fn(self.params,
                                                      jnp.asarray(toks),
                                                      true_len)
                    if self.paged:
                        # shared prefix blocks are read-only — the donor's
                        # data is bit-identical, so mask them out of the
                        # scatter
                        self.pool.insert(cache1, slot,
                                         skip_blocks=len(shared))
                    else:
                        self.pool.insert(cache1, slot)
                self.metrics.record_admit(P)

            prior: List[int] = list(res.get("generated", []))
            sp = req.sampling
            # sample counter continues across eviction/recompute: token i of
            # the ORIGINAL request is always drawn from fold_in(key, i)
            with obs.span("engine.admit.first_token", track=track):
                base_key = np.asarray(jax.random.PRNGKey(sp.seed), np.uint32)
                tok0, ok0 = self._first_fn(
                    logits, jnp.asarray(base_key),
                    jnp.asarray(sp.temperature, jnp.float32),
                    jnp.asarray(sp.top_k, jnp.int32),
                    jnp.asarray(sp.top_p, jnp.float32),
                    jnp.asarray(len(prior), jnp.int32))
                finite, tok0 = bool(ok0), int(tok0)
            if not finite:
                # prefill already went non-finite: quarantine at admission,
                # BEFORE the prompt could be indexed as a prefix donor (a
                # poisoned chain would hand NaN logits to every sharer)
                self.quarantined[req.id] = QuarantinedRequest(
                    req.id, "nonfinite_logits", self.step_count,
                    int(res.get("prompt_len", req.prompt_len)), len(prior))
                self.metrics.record_quarantine("nonfinite_logits")
                self.pool.release(slot)
                self._audit_quarantine(req, "nonfinite_logits", slot=slot,
                                       generated=len(prior))
                return
            if not full_hit and self.share_prefixes and req.resume is None:
                # index this prompt for future sharers (resumes carry
                # generated continuations — not reusable prompts)
                with obs.span("engine.admit.index", track=track):
                    self.pool.register_prefix(
                        slot, req.prompt, np.asarray(logits[0, -1]))

        now = time.perf_counter()
        st = GenState(request=req, slot=slot, pos=P, last_token=tok0,
                      generated=prior,
                      admitted_step=self.step_count, admitted_time=now)
        done = st.remaining == 1 or tok0 == req.eos_id
        first_of_original = not prior          # st.emit appends into `prior`
        st.emit(tok0, is_last=done, now=now)
        if self.journal is not None:
            self.journal.log_token(req.id, tok0)
        if first_of_original:
            obs.instant("req.first_token", track=track, id=req.id)
        if done:
            self._retire(st, "eos" if tok0 == req.eos_id else "length")
            return
        self.slots[slot] = st
        self._tok[slot, 0] = tok0
        self._pos[slot] = P
        self._temp[slot] = sp.temperature
        self._topk[slot] = sp.top_k
        self._topp[slot] = sp.top_p
        self._key[slot] = base_key
        self._t[slot] = len(prior) + 1        # last token came from prefill

    # -- paged block lifecycle ----------------------------------------------

    def _grant_pass(self) -> None:
        """Before each paged decode: make sure every resident lane's next
        write slot has a physical block IT OWNS.  A write block with
        refcount > 1 is copy-on-written first (sharers never mutate a
        donor's prefix; CoW failure parks like any grant failure); a sole
        owner whose ring wrapped back over indexed prefix content drops the
        stale chain entries before the write lands.  Grants collect into
        one device-side kv_pos reset; lanes that can't be granted park
        (masked inactive, no writes — a parked lane can never corrupt a
        neighbour).  If parking leaves nothing runnable, the youngest
        parked lane leaves the pool — swapped to the host tier when
        enabled, evicted to recompute otherwise — and the pass retries.
        Same-tick victims requeue in ONE batch ordered by original submit
        order, so multi-eviction ticks preserve FIFO and a resumed TTFT
        never resets."""
        victims: List[Request] = []
        while True:
            fresh: List[int] = []
            parked: List[int] = []
            # walk lanes in original-submit order, NOT slot-index order:
            # blocks freed mid-tick (an SLO cancellation, a retire) must
            # unpark waiting lanes FIFO — the oldest parked request gets
            # the first grant, whatever slot it happens to occupy
            order = sorted(
                (i for i, s in enumerate(self.slots) if s is not None),
                key=lambda i: self._seq.get(self.slots[i].request.id, 0))
            for i in order:
                st = self.slots[i]
                lb = (st.pos % self.pool.ring_len) // self.pool.block_size
                pb = int(self.pool.table[i, lb])
                if pb >= 0:
                    if self.pool.refcount(pb) > 1:
                        try:                   # shared write block: CoW
                            old, new = self.pool.cow(i, lb)
                        except RuntimeError:   # no block for the copy
                            self._park(i, st)
                            parked.append(i)
                            continue
                        self.metrics.record_cow(self.pool.block_bytes)
                        obs.instant("pool.cow_copy",
                                    track=f"req:{st.request.id}",
                                    id=st.request.id, slot=i, src=old,
                                    dst=new,
                                    bytes=self.pool.block_bytes)
                    elif st.pos >= self.pool.ring_len:
                        # sole owner wrapping over indexed prefix content
                        self.pool.invalidate_block(pb)
                    if self._pos[i] < 0:      # runnable now — unpark
                        self._pos[i] = st.pos
                    continue
                try:
                    fresh.append(self.pool.grant(i, lb))
                    if self._pos[i] < 0:
                        self._pos[i] = st.pos
                except RuntimeError:          # pool exhausted — park
                    self._park(i, st)
                    parked.append(i)
            self.pool.reset_blocks(fresh)
            runnable = any(s is not None and self._pos[i] >= 0
                           for i, s in enumerate(self.slots))
            if runnable or not parked:
                break
            if len(parked) == len([s for s in self.slots if s is not None]) \
                    and len(parked) == 1:
                raise RuntimeError(
                    f"paged pool too small: a single resident request "
                    f"cannot grow ({self.pool.pool_blocks} blocks of "
                    f"{self.pool.block_size})")
            victim = max(parked, key=lambda i: (
                self.slots[i].admitted_step,
                self._seq.get(self.slots[i].request.id, 0)))
            # park-storm: nothing runnable, a lane is being displaced —
            # snapshot the flight recorder before state changes further
            obs.flight_maybe_dump("engine.park_storm")
            if self.swap_tier:
                victims.append(self._swap_out(victim))
            else:
                victims.append(self._evict(victim))
        if victims:
            victims.sort(key=lambda r: self._seq.get(r.id, 0))
            now = time.perf_counter()
            for r in victims:
                self._queued_at[r.id] = now
            self.scheduler.requeue_front(victims)

    def _park(self, slot: int, st: GenState) -> None:
        if self._pos[slot] >= 0:
            self.metrics.record_park()
            obs.instant("req.park", track=f"req:{st.request.id}",
                        id=st.request.id, slot=slot,
                        free_blocks=self.pool.free_blocks)
        self._pos[slot] = -1

    def _resume_request(self, st: GenState) -> Request:
        """The requeued form of a displaced lane: prompt := original prompt
        + everything generated, ``max_new_tokens`` the ORIGINAL horizon —
        ``GenState.generated`` carries the prior tokens, so the
        remaining-budget arithmetic, the per-token fold_in sample counter,
        and greedy continuations are all identical to the uninterrupted
        run.  The resume dict keeps the original submit time and
        first-token time, so TTFT never resets on recompute/swap-in."""
        req = st.request
        res = req.resume or {}
        orig_prompt_len = int(res.get("prompt_len", req.prompt_len))
        orig_prompt = np.asarray(req.prompt, np.int32)[:orig_prompt_len]
        done = np.asarray(st.generated, np.int32)   # prior + this residency
        return Request(
            id=req.id, prompt=np.concatenate([orig_prompt, done]),
            max_new_tokens=req.max_new_tokens,
            sampling=req.sampling, eos_id=req.eos_id, arrival_step=0,
            stream=req.stream,
            deadline_s=req.deadline_s, ttft_slo_s=req.ttft_slo_s,
            resume={"generated": [int(t) for t in done],
                    "prompt_len": orig_prompt_len,
                    "first_token_time": res.get("first_token_time")
                    or st.first_token_time,
                    "submitted": res.get("submitted")
                    or self._submit_time.get(req.id),
                    # SLO window keeps ticking across displacement
                    "slo_submit": self._slo_submit.get(req.id)})

    def _clear_lane(self, slot: int) -> None:
        self.slots[slot] = None
        self._pos[slot] = -1
        self._tok[slot, 0] = 0
        self.pool.release(slot)

    def _evict(self, slot: int) -> Request:
        """Recompute fallback: free the lane's blocks and return the
        resumed request (the caller batches same-tick victims into one
        FIFO-ordered requeue)."""
        st = self.slots[slot]
        resumed = self._resume_request(st)
        self._clear_lane(slot)
        self.metrics.record_evict()
        obs.instant("req.evict", track=f"req:{st.request.id}",
                    id=st.request.id, slot=slot,
                    generated=len(st.generated))
        obs.flight_maybe_dump("engine.evict")
        return resumed

    # -- swap tier ------------------------------------------------------------

    def _swap_out(self, slot: int) -> Request:
        """Displace a parked lane WITHOUT losing its KV: snapshot the
        logical ring on device (async — drained to host behind later
        steps), free the blocks, return the resumed request.  Recompute
        never happens unless the handle disappears."""
        st = self.slots[slot]
        req = st.request
        resumed = self._resume_request(st)
        resumed.resume["swap"] = req.id
        lane = self.pool.gather_lane(slot)     # BEFORE release zeroes the row
        blocks = self.pool.lane_blocks(slot)
        nbytes = blocks * self.pool.block_bytes
        self.swap[req.id] = {"cache": lane, "pos": st.pos, "blocks": blocks}
        self._swap_pending.append(req.id)
        self._clear_lane(slot)
        self.metrics.record_swap_out(nbytes)
        obs.instant("pool.swap_out", track=f"req:{req.id}", id=req.id,
                    slot=slot, blocks=blocks, bytes=nbytes,
                    generated=len(st.generated))
        return resumed

    def _swap_in(self, req: Request, slot: int, handle: dict) -> None:
        """Re-admit a swapped-out lane: grant blocks for the saved ring
        extent, re-insert the snapshot through the one compiled insert, and
        restore the batch rows exactly — no prefill, no resample; the next
        decode step continues where the lane left off."""
        res = req.resume or {}
        track = f"req:{req.id}"
        need = self.pool.blocks_for(min(handle["pos"], self.pool.ring_len))
        granted = self.pool.grant_prefix(slot, need)   # raises w/o effects
        nbytes = need * self.pool.block_bytes
        with obs.span("engine.admit.swap_in", track=track, slot=slot,
                      blocks=need, bytes=nbytes):
            self.pool.insert(jax.tree.map(jnp.asarray, handle["cache"]),
                             slot)
        del granted
        prior: List[int] = list(res.get("generated", []))
        sp = req.sampling
        now = time.perf_counter()
        st = GenState(request=req, slot=slot, pos=int(handle["pos"]),
                      last_token=prior[-1], generated=list(prior),
                      admitted_step=self.step_count, admitted_time=now)
        st.first_token_time = res.get("first_token_time") or 0.0
        self.metrics.record_admit(0)
        self.metrics.record_swap_in(nbytes)
        obs.instant("pool.swap_in", track=track, id=req.id, slot=slot,
                    blocks=need, bytes=nbytes)
        self.slots[slot] = st
        self._tok[slot, 0] = prior[-1]
        self._pos[slot] = st.pos
        self._temp[slot] = sp.temperature
        self._topk[slot] = sp.top_k
        self._topp[slot] = sp.top_p
        self._key[slot] = np.asarray(jax.random.PRNGKey(sp.seed), np.uint32)
        self._t[slot] = len(prior)            # next token's fold_in counter

    # -- decode / retire -----------------------------------------------------

    def decode_batch(self) -> dict:
        """The serve step's per-lane batch as the lanes stand now."""
        batch = {
            "token": jnp.asarray(self._tok),
            "pos": jnp.asarray(self._pos),
            "temperature": jnp.asarray(self._temp),
            "top_k": jnp.asarray(self._topk),
            "top_p": jnp.asarray(self._topp),
            "key": jnp.asarray(self._key),
            "t": jnp.asarray(self._t),
            "poison": jnp.asarray(self._poison_row),
        }
        if self.paged:
            batch["block_tbl"] = jnp.asarray(self.pool.table)
            batch["ring_len"] = jnp.asarray(self.pool.ring_len, jnp.int32)
        return batch

    def _decode(self) -> None:
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and self._pos[i] >= 0]
        if not active:
            return
        with obs.span("engine.batch"):
            # chaos NaN injector: the poison row is ALWAYS in the batch (all
            # False when disarmed) so arming it never changes the signature
            for i, s in enumerate(self.slots):
                self._poison_row[i] = (bool(self._poison) and s is not None
                                       and s.request.id in self._poison)
            batch = self.decode_batch()
        pages = {}
        if self._paged_kernel:
            pages["live_pages"] = self.pool.live_pages()
            self.metrics.record_page_visits(pages["live_pages"],
                                            self.pool.table.size)
        t0 = time.perf_counter()
        with obs.span("engine.decode_step", step=self.step_count,
                      active=len(active), lanes=len(self.slots), **pages):
            tok, ok, self.pool.cache = self._step_fn(self.params,
                                                     self.pool.cache, batch)
            tok_np = np.asarray(tok)          # blocks until the step lands
            ok_np = np.asarray(ok)
        with obs.span("engine.emit"):
            self._emit(active, tok_np, ok_np, time.perf_counter() - t0)

    def _emit(self, active: List[int], tok_np, ok_np, step_s: float) -> None:
        """A decode step's tokens to their lanes: metrics, the pool counter
        track, retires and the next tick's batch rows."""
        self.metrics.record_decode_step(
            len(active), len(active), step_s,
            in_flight=self.active_requests,
            blocks_in_use=self.pool.blocks_in_use,
            fragmentation=self.pool.fragmentation)
        obs.counter_track("pool", blocks_in_use=self.pool.blocks_in_use,
                          active_lanes=len(active))
        if obs.enabled() and self.step_count % 16 == 0:
            obs.watermark("engine.decode")     # devmem track, sampled
        now = time.perf_counter()
        for i in active:
            st = self.slots[i]
            if not bool(ok_np[i]):
                # this lane's logits slice went non-finite (organic or
                # injected): no token emitted, lane quarantined alone —
                # the scatter already wrote its cache row, but the blocks
                # are released with the lane, so nothing leaks
                self._quarantine_lane(st, "nonfinite_logits")
                continue
            t = int(tok_np[i, 0])
            done = st.remaining == 1 or t == st.request.eos_id
            st.emit(t, is_last=done, now=now)
            if self.journal is not None:
                self.journal.log_token(st.request.id, t)
            st.pos += 1
            st.steps_done += 1
            if done:
                self._retire(st, "eos" if t == st.request.eos_id
                             else "length")
            else:
                self._tok[i, 0] = t
                self._pos[i] = st.pos
                self._t[i] += 1

    def _retire(self, st: GenState, reason: str) -> None:
        slot = st.slot
        if self.slots[slot] is st:
            self.slots[slot] = None
        self._pos[slot] = -1
        self._tok[slot, 0] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._topp[slot] = 0.0
        self._key[slot] = 0
        self._t[slot] = 0
        self.pool.release(slot)
        res = st.request.resume or {}
        track = f"req:{st.request.id}"
        slo_t0 = self._slo_submit.pop(st.request.id, None)
        self._poison.discard(st.request.id)
        if reason in ("deadline", "ttft_slo"):
            # resident cancel: mid-decode, partial tokens kept, lane and
            # blocks just reclaimed above — audit the miss
            self.metrics.record_deadline_miss(ttft=reason == "ttft_slo")
            obs.instant("serve.deadline_miss", track=track,
                        id=st.request.id, kind=reason, queued=False,
                        generated=len(st.generated),
                        waited_s=(self._now() - slo_t0
                                  if slo_t0 is not None else 0.0))
            obs.counter(f"serve.deadline_miss.{reason}", 1)
        first_tok = res.get("first_token_time") or st.first_token_time
        # resumes carry the ORIGINAL submit time: TTFT measures the user's
        # wait, not the latest recompute/swap-in residency
        submit_t = (res.get("submitted")
                    or self._submit_time.get(st.request.id,
                                             st.admitted_time))
        ttft = first_tok - submit_t
        self.metrics.record_finish(ttft)
        now = time.perf_counter()
        obs.add_span("req.decode", first_tok, now, track=track,
                     id=st.request.id, tokens=len(st.generated))
        # exactly ONE lifecycle span per finished request (never re-emitted
        # on eviction/recompute): trace-validity checks count these against
        # metrics.requests_finished
        obs.add_span("req.lifecycle", submit_t, now, track=track,
                     id=st.request.id, reason=reason,
                     tokens=len(st.generated), ttft_s=ttft)
        obs.instant("req.retire", track=track, id=st.request.id,
                    reason=reason)
        if self.journal is not None:
            self.journal.log_finish(st.request.id, reason)
        self.finished[st.request.id] = FinishedRequest(
            id=st.request.id,
            tokens=np.asarray(st.generated, np.int32),
            prompt_len=res.get("prompt_len", st.request.prompt_len),
            admitted_step=st.admitted_step,
            finished_step=self.step_count,
            ttft_s=ttft,
            reason=reason)
