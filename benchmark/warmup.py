"""Which requests warm up every program a cell's traffic can reach — found
from the traffic file's lengths and the engine's buckets, not listed by hand.

The engine compiles one program per (prefill bucket, padded group size) for
one-shot prefills, one per bucket for chunked extends (prompts beyond the
largest bucket, and suffixes after a prefix-cache hit), and one decode
program per context-window bucket. A wave is a set of requests sent at the
same instant so that the scheduler prefills them as one group.
"""

from __future__ import annotations


def plan(shapes: dict, engine: dict) -> list[list[tuple[int, int]]]:
    """Waves of (prompt tokens, max_tokens). `shapes` comes from the cell's
    generator (`shapes(traffic)`), `engine` from /bench/info."""
    buckets = sorted(engine["prefill_buckets"])
    largest = buckets[-1]
    lo, hi = shapes["prompt_tokens"]
    burst = int(engine.get("decode_burst") or 1)
    waves: list[list[tuple[int, int]]] = []

    # one-shot prefills: every bucket some uncached prompt can land in,
    # at every padded group size that simultaneous arrivals can form
    groups = [g for g in (1, 2, 4, 8) if g <= max(1, shapes["max_prefill_group"])]
    prev = 0
    for b in buckets:
        if lo <= b and prev < min(hi, largest):
            n = min(b, hi)
            for g in groups:
                waves.append([(n, 1)] * g)
        prev = b

    # chunked extends: a prompt of largest + b tokens runs one chunk of the
    # largest bucket and one of bucket b
    if hi > largest or shapes.get("shared_prefix"):
        for b in buckets:
            if largest + b < engine["slot_capacity"]:
                waves.append([(largest + b, 1)])

    # decode windows: a context just past the previous bucket decodes one
    # burst in this one
    prev = 0
    for w in engine["window_buckets"]:
        if prev < shapes["max_context_tokens"]:
            n = max(8, prev) if prev else 32
            n = min(n, engine["slot_capacity"] - 2 * burst - 2)
            waves.append([(n, burst + 1)])
        prev = w
    return waves
