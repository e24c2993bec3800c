"""Closed loop: N callers, each sending its next request when the last one
ends. Parameters (traffic file):

  clients      number of callers (one per engine slot in decode-saturated)
  prompt       distribution of prompt tokens, see common.quantile_grid
  max_tokens   every request runs to this many output tokens
  ramp_s       the first caller starts this long before the window, so that
               by the window every slot is busy
  start_after_tokens    each further caller starts when the one before it
               has received this many tokens of its first answer (or that
               answer has ended). The stagger counts in the server's steps
               and not in seconds, so it repeats from run to run and stays
               whole when the program gets faster. With requests of one
               length, callers that start on a clock's grid and once meet in
               one admission stay together for the whole run, and chance
               decides how many do (PERF.md, PR 26). Choose it so that all
               have started by the window and completions are spread in time.
  requests_per_client   length of each caller's fixed list (cycled)

A closed loop has no due instants: the sample is the requests that were sent
after the ramp began and FINISHED inside the window.
"""

from __future__ import annotations

import asyncio
import random

from benchmark.generators import common


def shapes(traffic: dict) -> dict:
    return {
        "prompt_tokens": (traffic["prompt"]["lo"], traffic["prompt"]["hi"]),
        "max_context_tokens": traffic["prompt"]["hi"] + traffic["max_tokens"],
        "max_prefill_group": traffic.get("max_prefill_group", 4),
        "shared_prefix": False,
    }


def client_plans(traffic: dict, seed: int, vocab: int) -> list[dict]:
    """For each caller, in the order they start: its list of prompt lengths.
    Every seed gives the same multiset of lengths; the seed deals them out."""
    rng = random.Random(seed)
    n, per = int(traffic["clients"]), int(traffic.get("requests_per_client", 8))
    lengths = common.quantile_grid(traffic["prompt"], n * per)
    rng.shuffle(lengths)
    return [{"prompt_tokens": lengths[i * per:(i + 1) * per]} for i in range(n)]


async def drive(ctx) -> None:
    plans = client_plans(ctx.traffic, ctx.seed, ctx.vocab)
    max_tokens = int(ctx.traffic["max_tokens"])
    after = int(ctx.traffic["start_after_tokens"])
    # under_way[i]: caller i's first answer has reached `after` tokens
    under_way = [asyncio.Event() for _ in plans]

    async def caller(i: int, plan: dict):
        rng = random.Random(ctx.seed * 1000003 + i)

        def progress(words: int) -> None:
            if words >= after:
                under_way[i].set()

        if i == 0:
            await ctx.sleep_until(-float(ctx.traffic["ramp_s"]))
        else:
            await under_way[i - 1].wait()
        k = 0
        while ctx.now() < ctx.seconds:
            p = plan["prompt_tokens"][k % len(plan["prompt_tokens"])]
            k += 1
            try:
                rec = await ctx.send(
                    common.single_message(rng, p, ctx.vocab), max_tokens,
                    due_s=ctx.now(), prompt_tokens=p, in_sample=False,
                    on_words=progress)
            finally:
                under_way[i].set()  # a failed first answer holds nobody back
            # finished inside the window: it counts
            if rec.get("last_s") is not None and 0 <= rec["end_s"] < ctx.seconds:
                rec["in_sample"] = True

    tasks = [asyncio.create_task(caller(i, p)) for i, p in enumerate(plans)]
    await ctx.sleep_until(ctx.seconds)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
