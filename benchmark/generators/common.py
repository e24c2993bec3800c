"""What every generator shares: fixed quantile grids in place of draws, fixed
arrivals per one-second slot, seeded prompt contents.

The rule (ISSUE 23): offered work is equal in every run. A traffic file fixes
the multiset of (prompt tokens, output tokens) pairs and the number of
arrivals in every one-second slot; `--seed` decides only the order of the
pairs, the offset of each arrival inside its slot, and the contents.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

from benchmark.tokenizer import TOKENS_FOR_REPLY, TOKENS_PER_MESSAGE

_FIRST_PLAIN_ID = 8  # ids below are kept for role markers


def quantile_grid(dist: dict, n: int) -> list[int]:
    """The midpoints of an n-cell quantile grid of `dist`, as whole token
    counts clipped to [lo, hi] — the same n numbers every time.

    dist: {"kind": "lognormal", "median", "sigma", "lo", "hi"} or
          {"kind": "uniform", "lo", "hi"}."""
    if n <= 0:
        return []
    lo, hi = int(dist["lo"]), int(dist["hi"])
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist["kind"] == "lognormal":
            x = math.exp(math.log(dist["median"])
                         + dist["sigma"] * NormalDist().inv_cdf(p))
        elif dist["kind"] == "uniform":
            x = lo + (hi - lo) * p
        else:
            raise ValueError(f"unknown distribution kind {dist['kind']!r}")
        out.append(int(min(hi, max(lo, round(x)))))
    return out


def strided(xs: list, frac: float = 0.6180339887) -> list:
    """xs in a fixed other order: every (frac·n)-th element, wrapping — a
    permutation that needs no seed."""
    n = len(xs)
    if n == 0:
        return []
    stride = max(1, round(n * frac))
    while math.gcd(stride, n) != 1:
        stride += 1
    return [xs[(i * stride) % n] for i in range(n)]


def fixed_pairs(prompts: list[int], outputs: list[int]) -> list[tuple[int, int]]:
    """Pair the i-th prompt length with an output length by a fixed stride
    through the (sorted) output grid, so long prompts do not all get long
    answers — and no seed is involved."""
    if len(prompts) != len(outputs):
        raise ValueError("grids differ in length")
    return list(zip(prompts, strided(outputs)))


ORDER_STRATA = 4  # the count PR 23's chip runs were made with (PERF.md)


def stratified_order(pairs: list[tuple[int, int]], rng: random.Random
                     ) -> list[tuple[int, int]]:
    """The pairs in an order the seed chooses, but with the work spread
    evenly along it: sorted by prompt length into ORDER_STRATA classes of
    equal size, every block of that many consecutive requests takes one pair
    of each class (which one, and in what order inside the block, is the
    seed's).
    A plain shuffle clusters the long prompts differently in every run, and
    a cluster of chunked prefills is a queue: the first chip runs of
    chat-paced showed TTFT's median following its 90th percentile from run
    to run (PERF.md, PR 23). Burstiness belongs in a cell of its own."""
    ranked = sorted(pairs)
    n = len(ranked)
    k = max(1, min(ORDER_STRATA, n))
    classes = [ranked[(i * n) // k:((i + 1) * n) // k] for i in range(k)]
    for c in classes:
        rng.shuffle(c)
    out = []
    while any(classes):
        block = [c.pop() for c in classes if c]
        rng.shuffle(block)
        out.extend(block)
    return out


def slot_counts(rate_per_s: float, first_slot: int, n_slots: int) -> list[int]:
    """Arrivals in each one-second slot k = first_slot .. first_slot+n-1
    (slot 0 starts the window): floor((k+1)·rate) − floor(k·rate), so 3.4 a
    second is 3, 4, 3, 3, 4 … and never a draw."""
    return [math.floor((k + 1) * rate_per_s) - math.floor(k * rate_per_s)
            for k in range(first_slot, first_slot + n_slots)]


def random_words(rng: random.Random, n: int, vocab: int) -> str:
    """n seeded words, each one token; distinct from the first word on, so
    two prompts share no cacheable prefix by accident."""
    return " ".join(f"t{rng.randrange(_FIRST_PLAIN_ID, vocab)}"
                    for _ in range(n))


def single_message(rng: random.Random, prompt_tokens: int, vocab: int) -> list[dict]:
    """A one-message chat whose prompt is exactly `prompt_tokens` tokens at
    the engine, template overhead included."""
    words = prompt_tokens - TOKENS_PER_MESSAGE - TOKENS_FOR_REPLY
    if words < 1:
        raise ValueError(f"a prompt of {prompt_tokens} tokens has no room "
                         "for a message")
    return [{"role": "user", "content": random_words(rng, words, vocab)}]
