"""Open loop: independent users, requests sent on a schedule whether or not
earlier ones have finished. Parameters (traffic file):

  rate_per_s   offered rate, fixed in the file (a share of the swept knee)
  prompt, output   distributions, see common.quantile_grid
  ramp_s       seconds of the same traffic before the window (part of set-up)
  tail_s       seconds of schedule after the window, offered while the
               window's requests finish (not counted)
  max_prefill_group   the largest group of simultaneous arrivals warmed up

The sample is the requests DUE inside the window; each is timed from its due
instant, so a stall's cost to later requests counts.
"""

from __future__ import annotations

import asyncio
import random

from benchmark.generators import common


def shapes(traffic: dict) -> dict:
    """What warm-up has to cover, found from the file."""
    return {
        "prompt_tokens": (traffic["prompt"]["lo"], traffic["prompt"]["hi"]),
        "max_context_tokens": traffic["prompt"]["hi"] + traffic["output"]["hi"],
        "max_prefill_group": traffic.get("max_prefill_group", 4),
        "shared_prefix": False,
    }


def _phase(traffic: dict, rng: random.Random, first_slot: int, n_slots: int,
           vocab: int) -> list[dict]:
    """The requests of slots [first_slot, first_slot + n_slots): a fixed
    multiset of pairs and fixed per-slot counts; the seed orders the pairs
    (common.stratified_order) and draws each arrival's offset inside its
    slot, so two or three arrivals can fall into one decode burst and be
    prefilled as a group."""
    counts = common.slot_counts(traffic["rate_per_s"], first_slot, n_slots)
    n = sum(counts)
    pairs = common.fixed_pairs(common.quantile_grid(traffic["prompt"], n),
                               common.quantile_grid(traffic["output"], n))
    pairs = common.stratified_order(pairs, rng)
    out = []
    it = iter(pairs)
    for k, c in zip(range(first_slot, first_slot + n_slots), counts):
        for offset in sorted(rng.random() for _ in range(c)):
            p, o = next(it)
            out.append({"due_s": k + offset, "prompt_tokens": p,
                        "max_tokens": o,
                        "messages": common.single_message(rng, p, vocab)})
    return out


def schedule(traffic: dict, seed: int, seconds: int, vocab: int) -> list[dict]:
    """Every request of a run, in due order; `in_sample` marks those due in
    the window. Ramp, window and tail each have their own fixed multiset."""
    rng = random.Random(seed)
    ramp = int(traffic["ramp_s"])
    tail = int(traffic.get("tail_s", 30))
    plan = []
    for first, n, sample in ((-ramp, ramp, False), (0, seconds, True),
                             (seconds, tail, False)):
        for r in _phase(traffic, rng, first, n, vocab):
            r["in_sample"] = sample
            plan.append(r)
    return plan


async def drive(ctx) -> None:
    """Send the schedule against ctx's clock (0 = start of the window); keep
    offering the tail until every request due in the window has finished,
    then cancel what is still in flight (it was never counted)."""
    plan = schedule(ctx.traffic, ctx.seed, ctx.seconds, ctx.vocab)
    n_sample = sum(1 for r in plan if r["in_sample"])
    sample_tasks, other_tasks = [], []

    async def sender():
        for r in plan:
            await ctx.sleep_until(r["due_s"])
            task = asyncio.create_task(ctx.send(
                r["messages"], r["max_tokens"], due_s=r["due_s"],
                prompt_tokens=r["prompt_tokens"], in_sample=r["in_sample"]))
            (sample_tasks if r["in_sample"] else other_tasks).append(task)

    send_task = asyncio.create_task(sender())
    while len(sample_tasks) < n_sample and not send_task.done():
        await asyncio.sleep(0.05)
    if sample_tasks:
        await asyncio.wait(sample_tasks)
    send_task.cancel()
    for t in other_tasks:
        t.cancel()
    await asyncio.gather(send_task, *other_tasks, return_exceptions=True)
