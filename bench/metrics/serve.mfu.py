"""Model FLOPs of the prefills and decoded tokens in the window over
window x the chip's peak bf16 rate (the architecture module's
``prefill_flops`` per prompt the engine prefilled, ``decode_flops`` per
token a serve step produced, at its position).  Moves ``itl_p95_ms``."""

import flops


def read(run):
    cfg, model = run["cfg"], run["model"]
    total = sum(model.prefill_flops(cfg, n) for _, _, n, _ in run["admits"]
                if n > 0)
    total += sum(model.decode_flops(cfg, p) for _, ps in run["ticks"]
                 for p in ps)
    if not total:
        return None
    return flops.share(total / run["peak"]["bf16_flops_per_s"],
                       run["window_s"])
