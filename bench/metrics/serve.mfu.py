"""Model FLOPs of the prefills and decoded tokens in the window over
window x the chip's peak bf16 rate (``flops.prefill_flops`` per prompt the
engine prefilled, ``flops.decode_flops`` per token a serve step produced,
at its position).  Moves ``itl_p95_ms``."""

import flops


def read(run):
    cfg = run["cfg"]
    total = sum(flops.prefill_flops(cfg, n) for _, _, n, _ in run["admits"]
                if n > 0)
    total += sum(flops.decode_flops(cfg, p) for _, ps in run["ticks"]
                 for p in ps)
    if not total:
        return None
    return flops.share(total / run["peak"]["bf16_flops_per_s"],
                       run["window_s"])
