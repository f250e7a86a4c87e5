"""Serving metrics: throughput, latency percentiles, slot/block occupancy.

Host-side counters only — nothing here enters jit.  The engine calls the
record hooks; ``summary()`` folds them into the dict that
``benchmarks/serving_bench.py`` persists to ``BENCH_serving.json``.

Latency is reported as distributions, not just means: TTFT (submit ->
first token, one sample per finished request) and inter-token latency
(wall time of one batched decode step — every active request receives its
next token at the step boundary, so the step time IS each stream's
per-token latency) both feed ``repro.obs.Histogram`` reservoirs, and
``summary()`` exposes p50/p95/p99 for each.

Wall-clock accounting: ``wall_s`` spans from construction (or reset) to
the **last recorded event** — decode steps and retires both advance the
clock, so work after the final request finish (or a run where nothing
finishes at all) is priced into ``tok_per_s`` instead of silently
dropped.  ``steady_tok_per_s`` excludes the jit-compile-laden first decode
step: the steady token count is the total scaled by (steps−1)/steps, and
a run with a single decode step has no steady-state to report (0.0).

Summary fields
==============
``requests``              finished request count
``decode_steps``          batched decode steps executed
``decode_tokens``         tokens sampled across decode steps
``prefill_tokens``        real (unpadded) prompt tokens prefilled
``wall_s``                construction -> last recorded event
``tok_per_s``             decode_tokens / wall_s
``steady_tok_per_s``      decode rate excluding the first (compile) step
``mean_ttft_s``           mean submit -> first-token latency
``max_ttft_s``            worst TTFT
``ttft_p50/p95/p99_s``    TTFT percentiles (reservoir; exact below 4096
                          requests)
``itl_p50/p95/p99_s``     inter-token latency percentiles over decode
                          steps
``mean_occupancy``        mean active-lanes / num_slots per step
``mean_block_utilization``mean used-blocks / pool_blocks per step (the
                          paged pool's HBM win shows up here — lanes can
                          sit near-full while blocks do not)
``pool_blocks``           physical cache blocks (paged; lanes otherwise)
``peak_in_flight``        max resident requests observed
``parked_events``         block-grant failures (paged)
``evictions``             livelock-breaking evictions (recompute fallback)
``share_hits``            admissions that mapped >= 1 shared prefix block
``full_prompt_hits``      admissions that skipped prefill entirely (whole
                          prompt matched a live chain)
``shared_blocks``         blocks mapped read-only instead of allocated
``cow_copies``/``cow_bytes``       copy-on-write block copies / bytes moved
``swap_outs``/``swap_out_bytes``   lanes swapped to host / HBM bytes freed
``swap_ins``/``swap_in_bytes``     lanes restored from host / bytes refilled
``decode_page_visit_share`` block-table entries the paged flash-decode
                          kernel visits (1 + each lane's last granted
                          block) over all lanes x table width, summed over
                          decode steps (paged pool on the Pallas
                          flash-decode path; 0 otherwise)
``mean_fragmentation``    mean free-list shredding per step ((runs−1)/
                          (free−1) from ``BlockAllocator``; 0 contiguous,
                          1 fully shredded)
``peak_fragmentation``    worst per-step fragmentation observed
``requests_submitted``    submits the engine accepted (verdict "ok")
``shed``                  submits rejected by backpressure (bounded queue)
``deadline_misses``       SLO cancellations (whole-request OR first-token)
``ttft_slo_misses``       subset of the above where TTFT was the miss
``quarantined``           poisoned/malformed requests parked (total; the
                          per-reason split lives on ``quarantined`` dict)
``deadline_miss_rate``    deadline_misses / requests_submitted
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

from repro.obs import Histogram


@dataclasses.dataclass
class EngineMetrics:
    num_slots: int
    pool_blocks: int = 0                      # physical cache blocks (paged:
                                              # real blocks; lanes otherwise)
    started: float = dataclasses.field(default_factory=time.perf_counter)
    last_event_at: float = 0.0                # latest decode step OR finish
    decode_steps: int = 0
    decode_tokens: int = 0                    # tokens sampled in decode steps
    prefill_tokens: int = 0                   # real (unpadded) prompt tokens
    requests_admitted: int = 0
    requests_finished: int = 0
    occupancy_sum: float = 0.0                # sum over steps of active/slots
    block_util_sum: float = 0.0               # sum over steps of used/pool
    peak_in_flight: int = 0                   # max resident requests
    parked_events: int = 0                    # block-grant failures (paged)
    evictions: int = 0                        # livelock-breaking evictions
    share_hits: int = 0                       # admissions sharing >=1 block
    full_prompt_hits: int = 0                 # prefill skipped entirely
    shared_blocks: int = 0                    # blocks mapped, not allocated
    cow_copies: int = 0
    cow_bytes: int = 0
    swap_outs: int = 0
    swap_out_bytes: int = 0
    swap_ins: int = 0
    swap_in_bytes: int = 0
    decode_pages_live: int = 0                # sum over steps of the table
                                              # entries the kernel visits
    decode_pages_grid: int = 0                # sum over steps of B * T
    frag_sum: float = 0.0                     # sum over steps of pool frag
    peak_fragmentation: float = 0.0
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    ttft_hist: Histogram = dataclasses.field(default_factory=Histogram)
    itl_hist: Histogram = dataclasses.field(default_factory=Histogram)
    first_step_s: float = 0.0                 # jit-compile-laden first step
    steady_decode_s: float = 0.0              # decode wall time past step 1
    # fault-tolerance accounting (requests, not steps):
    requests_submitted: int = 0               # accepted submits (verdict ok)
    requests_shed: int = 0                    # backpressure rejections
    deadline_misses: int = 0                  # SLO cancellations, either kind
    ttft_slo_misses: int = 0                  # subset: first-token SLO
    quarantined: Dict[str, int] = dataclasses.field(default_factory=dict)

    def record_admit(self, prompt_len: int) -> None:
        self.requests_admitted += 1
        self.prefill_tokens += prompt_len

    def record_decode_step(self, active: int, tokens_out: int,
                           elapsed_s: float, *, in_flight: int = 0,
                           blocks_in_use: int = 0,
                           fragmentation: float = 0.0) -> None:
        """One batched decode step: ``active`` lanes produced
        ``tokens_out`` tokens in ``elapsed_s`` wall seconds."""
        if self.decode_steps == 0:
            self.first_step_s = elapsed_s
        else:
            self.steady_decode_s += elapsed_s
            # the first step's latency is dominated by jit compilation —
            # recording it would poison the p99 of every short run
            self.itl_hist.add(elapsed_s)
        self.decode_steps += 1
        self.decode_tokens += tokens_out
        self.occupancy_sum += active / max(self.num_slots, 1)
        self.block_util_sum += blocks_in_use / max(self.pool_blocks, 1)
        self.frag_sum += fragmentation
        self.peak_fragmentation = max(self.peak_fragmentation, fragmentation)
        self.peak_in_flight = max(self.peak_in_flight, in_flight or active)
        self.last_event_at = time.perf_counter()

    def record_page_visits(self, live: int, grid: int) -> None:
        """One paged decode step: the kernel visits ``live`` of the block
        table's ``grid`` (lanes x table width) entries."""
        self.decode_pages_live += live
        self.decode_pages_grid += grid

    def record_park(self) -> None:
        self.parked_events += 1

    def record_evict(self) -> None:
        self.evictions += 1

    def record_share(self, blocks: int, full_hit: bool) -> None:
        self.share_hits += 1
        self.shared_blocks += blocks
        self.full_prompt_hits += bool(full_hit)

    def record_cow(self, nbytes: int) -> None:
        self.cow_copies += 1
        self.cow_bytes += nbytes

    def record_swap_out(self, nbytes: int) -> None:
        self.swap_outs += 1
        self.swap_out_bytes += nbytes

    def record_swap_in(self, nbytes: int) -> None:
        self.swap_ins += 1
        self.swap_in_bytes += nbytes

    def record_finish(self, ttft_s: float = None) -> None:
        """``ttft_s=None`` counts the finish without a TTFT sample — an
        SLO-cancelled request that never produced a first token has no
        TTFT to report (recording the deadline value instead would poison
        the percentiles)."""
        self.requests_finished += 1
        if ttft_s is not None:
            self.ttft_s.append(ttft_s)
            self.ttft_hist.add(ttft_s)
        self.last_event_at = time.perf_counter()

    def record_submit(self) -> None:
        self.requests_submitted += 1

    def record_shed(self) -> None:
        self.requests_shed += 1

    def record_deadline_miss(self, *, ttft: bool = False) -> None:
        """One SLO cancellation; ``ttft=True`` when the first-token SLO
        (rather than the whole-request deadline) was the one missed."""
        self.deadline_misses += 1
        self.ttft_slo_misses += bool(ttft)

    def record_quarantine(self, reason: str) -> None:
        self.quarantined[reason] = self.quarantined.get(reason, 0) + 1

    def summary(self) -> Dict[str, float]:
        # span to the LAST recorded event, not the last request finish:
        # decode steps after the final finish (and runs where no request
        # ever finishes) must still be priced into tok_per_s.  With no
        # events at all, fall back to "now".
        span = (self.last_event_at or time.perf_counter()) - self.started
        # steady-state excludes the compile-laden first step; with a single
        # decode step there is no steady state (the old (steps-1)/steps
        # scaling degenerated at decode_steps == 1)
        if self.decode_steps > 1 and self.steady_decode_s > 0:
            steady_tokens = (self.decode_tokens *
                             (self.decode_steps - 1) / self.decode_steps)
            steady = steady_tokens / self.steady_decode_s
        else:
            steady = 0.0
        th, ih = self.ttft_hist, self.itl_hist
        return {
            "requests": self.requests_finished,
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
            "wall_s": span,
            "tok_per_s": self.decode_tokens / span if span > 0 else 0.0,
            "steady_tok_per_s": steady,
            "mean_ttft_s": (sum(self.ttft_s) / len(self.ttft_s)
                            if self.ttft_s else 0.0),
            "max_ttft_s": max(self.ttft_s) if self.ttft_s else 0.0,
            "ttft_p50_s": th.percentile(50),
            "ttft_p95_s": th.percentile(95),
            "ttft_p99_s": th.percentile(99),
            "itl_p50_s": ih.percentile(50),
            "itl_p95_s": ih.percentile(95),
            "itl_p99_s": ih.percentile(99),
            "mean_occupancy": (self.occupancy_sum / self.decode_steps
                               if self.decode_steps else 0.0),
            # block-level utilization: the paged pool's win shows up here —
            # lanes can sit near-full while blocks (actual HBM) do not
            "mean_block_utilization": (
                self.block_util_sum / self.decode_steps
                if self.decode_steps else 0.0),
            "pool_blocks": self.pool_blocks,
            "peak_in_flight": self.peak_in_flight,
            "parked_events": self.parked_events,
            "evictions": self.evictions,
            "share_hits": self.share_hits,
            "full_prompt_hits": self.full_prompt_hits,
            "shared_blocks": self.shared_blocks,
            "cow_copies": self.cow_copies,
            "cow_bytes": self.cow_bytes,
            "swap_outs": self.swap_outs,
            "swap_out_bytes": self.swap_out_bytes,
            "swap_ins": self.swap_ins,
            "swap_in_bytes": self.swap_in_bytes,
            "decode_page_visit_share": (
                self.decode_pages_live / self.decode_pages_grid
                if self.decode_pages_grid else 0.0),
            "mean_fragmentation": (self.frag_sum / self.decode_steps
                                   if self.decode_steps else 0.0),
            "peak_fragmentation": self.peak_fragmentation,
            "requests_submitted": self.requests_submitted,
            "shed": self.requests_shed,
            "deadline_misses": self.deadline_misses,
            "ttft_slo_misses": self.ttft_slo_misses,
            "quarantined": int(sum(self.quarantined.values())),
            # rate over accepted submits: either-SLO cancellations per
            # request the engine agreed to serve (sheds excluded — they
            # never entered an SLO window)
            "deadline_miss_rate": (
                self.deadline_misses / self.requests_submitted
                if self.requests_submitted else 0.0),
        }
