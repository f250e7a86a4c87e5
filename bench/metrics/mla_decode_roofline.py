"""Share of its roofline the paged kernel's latent mode
(``kernels/flash_decode.py``, the launch named ``mla_decode``: 16 query
heads over one 576-wide latent head a position) reaches, in %: the least
time the chip could take for the bytes and FLOPs the window's latent
decode calls need (each lane's latent pages at the entry's needed width,
their position rows, q and out; the architecture module's
``paged_decode_bytes`` and ``decode_attention_flops``) over the kernel's
device time in the trace.  Moves ``itl_p95_ms``."""

import flops
import tracing

KERNEL = "mla_decode"


def read(run):
    tr = run["trace"]
    secs = sum(tr.op_seconds(
        run["lo"], run["hi"],
        match=lambda n: KERNEL in tracing.short_name(n)).values())
    cfg, model = run["cfg"], run["model"]
    nbytes = sum(model.paged_decode_bytes(cfg, ps, run["block_size"])
                 for _, ps in run["ticks"] if ps)
    fl = sum(model.decode_attention_flops(cfg, p)
             for _, ps in run["ticks"] for p in ps)
    if not secs or not nbytes:
        return None
    need, _ = flops.roofline_seconds(fl, nbytes, run["peak"])
    return flops.share(need, secs)
