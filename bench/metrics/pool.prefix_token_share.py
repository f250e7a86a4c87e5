"""Prompt tokens served from shared prefix blocks over prompt tokens
admitted in the window, in % (the engine's ``shared_blocks`` counter read
around each admission, times the block size, at most the prompt).  Moves
``ttft_p90_ms``."""


def read(run):
    admitted = sum(p for _, p, _, _ in run["admits"])
    if not admitted:
        return None
    return 100.0 * sum(s for _, _, _, s in run["admits"]) / admitted
