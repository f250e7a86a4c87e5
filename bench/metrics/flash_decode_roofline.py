"""Share of its roofline the paged flash-decode kernel
(``kernels/flash_decode.py``) reaches, in %: the least time the chip could
take for the bytes and FLOPs the window's decode calls need (K/V blocks
holding each lane's positions, their position rows, q and out; the
architecture module's ``paged_decode_bytes`` and
``decode_attention_flops``) over the kernel's device time in the trace.
Memory bound: the FLOPs are far under the bytes' time.  Moves
``itl_p95_ms``."""

import flops

KERNEL = "flash_decode"


def read(run):
    tr = run["trace"]
    secs = sum(tr.op_seconds(run["lo"], run["hi"],
                             match=lambda n: KERNEL in n).values())
    cfg, model = run["cfg"], run["model"]
    nbytes = sum(model.paged_decode_bytes(cfg, ps, run["block_size"])
                 for _, ps in run["ticks"] if ps)
    fl = sum(model.decode_attention_flops(cfg, p)
             for _, ps in run["ticks"] for p in ps)
    if not secs or not nbytes:
        return None
    need, _ = flops.roofline_seconds(fl, nbytes, run["peak"])
    return flops.share(need, secs)
