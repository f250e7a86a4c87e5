"""The one traffic generator: it reads a mix file (``bench/traffic/<mix>.json``)
and makes the run's inputs from ``--seed``.

Every seed gets the same work.  The sizes and their order, the tenants,
which earlier prompt a repeat repeats, and the arrival instants are drawn
once from the mix's own ``size_seed``; ``--seed`` draws only the token
ids.  So two seeds differ in content, not in how much there is to do or
when: a tail over a few dozen requests moves with the order of their
sizes.

Mixes:

* serving, ``"loop": "open"`` — requests due on a Poisson schedule at
  ``rate_per_s``;
* serving, ``"loop": "closed"`` — ``clients`` callers, each sending its
  next request when its last one finishes.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

_SEED_MIX = 0x5EED


def _rng(seed: int, *salt: int) -> np.random.Generator:
    """A generator for any whole-number seed (also past 2**32)."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *salt])


def draw_len(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from a ``{"dist": ..., ...}`` spec, clipped."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def zipf_choice(rng: np.random.Generator, k: int, s: float,
                n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, k + 1) ** s
    return rng.choice(k, size=n, p=p / p.sum())


def serve_requests(mix: dict, seed: int, vocab: int,
                   seconds: float) -> List[dict]:
    """The request list of a serving mix: dicts with ``id``, ``prompt``
    (int32 ids), ``max_new_tokens``, ``due_s`` (open loop) and
    ``tenant``/``repeat_of`` where the mix shares prefixes.

    Open loop: ``rate_per_s x seconds`` requests, due at instants of a
    Poisson process conditioned on that count (sorted uniform draws over
    the window).  Closed loop: ``requests`` requests in blocks of
    ``size_block``; every block holds the same multiset of sizes.  In both,
    the instants, the sizes and their order come from ``size_seed``;
    ``seed`` draws the tokens."""
    fixed = _rng(mix["size_seed"])
    picks = _rng(mix["size_seed"], _SEED_MIX)
    mine = _rng(seed, _SEED_MIX)
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        block = n
    else:
        n, block = int(mix["requests"]), int(mix["size_block"])
    turn_set = draw_len(fixed, mix["prompt"], block)
    out_set = draw_len(fixed, mix["output"], block)
    order = [fixed.permutation(block) for _ in range(-(-n // block))]
    turn = np.concatenate([turn_set[o] for o in order])[:n]
    out_len = np.concatenate([out_set[o] for o in order])[:n]
    shared = mix.get("shared_prefix")
    if shared:
        tenants = zipf_choice(fixed, shared["tenants"], shared["zipf_s"], n)
        repeat = fixed.random(n) < shared["repeat_share"]
        pre = mine.integers(0, vocab, (shared["tenants"],
                                       shared["preamble_tokens"]),
                            dtype=np.int32)
    else:
        tenants = np.zeros(n, np.int64)
        repeat = np.zeros(n, bool)
        pre = None
    reqs: List[dict] = []
    last: Dict[int, List[int]] = {}
    for i in range(n):
        t = int(tenants[i])
        earlier = last.get(t, [])
        if repeat[i] and earlier:
            j = earlier[int(picks.integers(0, len(earlier)))]
            prompt, rep = reqs[j]["prompt"], j
        else:
            body = mine.integers(0, vocab, int(turn[i]), dtype=np.int32)
            prompt = body if pre is None else np.concatenate([pre[t], body])
            rep = None
        reqs.append({"id": f"r{i}", "prompt": prompt,
                     "max_new_tokens": int(out_len[i]), "tenant": t,
                     "repeat_of": rep})
        last.setdefault(t, []).append(i)
    if mix["loop"] == "open":
        due = np.sort(fixed.uniform(0.0, seconds, n))
        for r, d in zip(reqs, due):
            r["due_s"] = float(d)
    return reqs


def prefill_buckets(mix: dict, bucket: int) -> List[int]:
    """Every prefill length (a multiple of ``bucket``) this mix's prompts
    can be padded to — the shapes a run warms up."""
    lo = mix["prompt"].get("min", 1)
    hi = mix["prompt"]["max"]
    if mix.get("shared_prefix"):
        lo += mix["shared_prefix"]["preamble_tokens"]
        hi += mix["shared_prefix"]["preamble_tokens"]
    first = -(-lo // bucket) * bucket
    last = -(-hi // bucket) * bucket
    return list(range(first, last + 1, bucket))
