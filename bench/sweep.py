"""Find the knee of an open-loop serving cell once, on the chip: the
highest arrival rate the engine sustains without a growing backlog.

    python bench/sweep.py --workload serve.shared_prefix \\
        --rates 0.2,0.4,0.6 --seconds 60 --seed 1

One engine (``kinds.serve.Session``), warmed once, serves the cell's mix
at each rate in turn (drained between rates), each rate's window opening
after the mix's pre-roll.  For each rate it prints the requests due and
finished, the queue left at the close, TTFT p50/p90 of the first and
second half of the requests, and the mean share of the KV pool's blocks
in use: a backlog that grows shows as a queue at the close and a second
half slower than the first.  The cell's traffic file then fixes its rate
at about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = common.load_workload(args.workload)
    jax = common.start_jax(False)
    common.check_device(jax, cell["chips"])
    from kinds import serve
    from traffic import gen
    mix = cell["traffic_data"]
    s = serve.Session(cell, args.seed, args.seconds)
    engine, h = s.engine, s.h
    pre = mix.get("preroll_s", 0.0)
    for k, r in enumerate(float(x) for x in args.rates.split(",")):
        m = dict(mix, rate_per_s=r)
        reqs = gen.serve_requests(m, args.seed + k, s.cfg.vocab_size,
                                  pre + args.seconds)
        for q in reqs:
            q["id"] = f"k{k}{q['id']}"
        loop = serve.make_loop(h, reqs, m)
        loop.run(time.perf_counter() + pre)
        t0 = time.perf_counter()
        end = t0 + args.seconds
        sent = loop.run(end)
        queued = engine.scheduler.pending
        done = sum(1 for q in sent
                   if len(h.stamps[q["id"]]) >= q["max_new_tokens"])
        ttft = [h.stamps[q["id"]][0] - h.due[q["id"]] for q in sent
                if h.stamps[q["id"]] and h.stamps[q["id"]][0] <= end]
        half = len(ttft) // 2
        ticks = [t for t, _ in h.ticks if t0 <= t <= end]
        fill = [n for t, n in h.fill if t0 <= t <= end]
        out_tok = sum(1 for q in sent for t in h.stamps[q["id"]] if t <= end)
        row = {"rate_per_s": r, "due": len(sent), "finished": done,
               "queued_at_close": queued,
               "no_first_token_at_close": len(sent) - len(ttft),
               "ttft_p50_first_half_s": common.percentile(ttft[:half], 50),
               "ttft_p50_second_half_s": common.percentile(ttft[half:], 50),
               "ttft_p90_s": common.percentile(ttft, 90),
               "out_tokens_per_s": out_tok / (end - t0),
               "ticks": len(ticks),
               "tick_s_median": common.percentile(
                   [b - a for a, b in zip(ticks, ticks[1:])], 50),
               "kv_pool_fill_mean": sum(fill) / max(len(fill), 1)
               / engine.pool.pool_blocks}
        print(json.dumps(row), flush=True)
        while h.busy():
            h.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
