"""Multi-turn sessions over shared system prompts: closed per session.

  sessions, system_prompts, system_tokens
  user, answer     distributions of a turn's user message and answer length
  think_s, think_jitter_s   pause after each answer (uniform ± jitter)
  max_context_tokens   a session starts anew on its system prompt when the
               next turn would take its context past this
  ramp_s       sessions start staggered over the first part of the ramp and
               advance about two turns before the window
  turns_per_session   length of each session's fixed list of turns (cycled)

A turn is DUE when its think time ends; its TTFT runs from that instant. The
sample is the follow-up turns (history present, so the prefix cache can
serve them) due inside the window; load continues until they have finished.
"""

from __future__ import annotations

import asyncio
import random

from benchmark.generators import common
from benchmark.tokenizer import TOKENS_FOR_REPLY, TOKENS_PER_MESSAGE, count_words


def shapes(traffic: dict) -> dict:
    first = traffic["system_tokens"] + traffic["user"]["hi"] + 2
    return {
        "prompt_tokens": (traffic["system_tokens"] + traffic["user"]["lo"],
                          traffic["max_context_tokens"]),
        "max_context_tokens": max(first, traffic["max_context_tokens"]),
        "max_prefill_group": traffic.get("max_prefill_group", 2),
        "shared_prefix": True,
    }


def session_plans(traffic: dict, seed: int, vocab: int) -> list[dict]:
    """For each session: its system prompt, start offset, and fixed list of
    (user tokens, answer tokens, think seconds). Same multisets for every
    seed; the seed deals them out and writes the contents."""
    rng = random.Random(seed)
    n, per = int(traffic["sessions"]), int(traffic.get("turns_per_session", 12))
    users = common.quantile_grid(traffic["user"], n * per)
    answers = common.quantile_grid(traffic["answer"], n * per)
    thinks = [traffic["think_s"] + traffic["think_jitter_s"] * (2 * (i + 0.5) / (n * per) - 1)
              for i in range(n * per)]
    # the grids are sorted: pair them by fixed strides, not by the seed
    turns = [(u, a, t) for (u, a), t in zip(common.fixed_pairs(users, answers),
                                           common.strided(thinks, 0.3819660113))]
    rng.shuffle(turns)
    n_sys = int(traffic["system_prompts"])
    # a system message is role marker + words: system_tokens in all
    systems = [common.random_words(rng, traffic["system_tokens"] - TOKENS_PER_MESSAGE,
                                   vocab) for _ in range(n_sys)]
    stagger = traffic.get("stagger_s", traffic["ramp_s"] / 2)
    starts = [i * stagger / n for i in range(n)]
    rng.shuffle(starts)
    return [{"system": systems[i % n_sys],
             "start_s": starts[i] - traffic["ramp_s"],
             "turns": turns[i * per:(i + 1) * per]} for i in range(n)]


def context_tokens(messages: list[dict]) -> int:
    return (sum(count_words(m["content"]) + TOKENS_PER_MESSAGE for m in messages)
            + TOKENS_FOR_REPLY)


async def drive(ctx) -> None:
    plans = session_plans(ctx.traffic, ctx.seed, ctx.vocab)
    limit = int(ctx.traffic["max_context_tokens"])
    pending_sample: set = set()
    window_over = asyncio.Event()

    async def session(i: int, plan: dict):
        rng = random.Random(ctx.seed * 1000003 + i)
        history = [{"role": "system", "content": plan["system"]}]
        await ctx.sleep_until(plan["start_s"])
        due = ctx.now()
        k = 0
        while True:
            user, answer, think = plan["turns"][k % len(plan["turns"])]
            k += 1
            msg = {"role": "user",
                   "content": common.random_words(rng, user, ctx.vocab)}
            if context_tokens(history + [msg]) + answer > limit:
                history = history[:1]  # anew on the system prompt
            follow_up = len(history) > 1
            history = history + [msg]
            if due >= ctx.seconds and window_over.is_set() and not pending_sample:
                return
            in_sample = follow_up and 0 <= due < ctx.seconds
            if in_sample:
                pending_sample.add((i, k))
            rec = await ctx.send(history, answer, due_s=due,
                                 prompt_tokens=context_tokens(history),
                                 in_sample=in_sample,
                                 kind="turn" if follow_up else "first")
            pending_sample.discard((i, k))
            if rec.get("text") is None:
                return  # a failed turn ends the session; it is counted failed
            history = history + [{"role": "assistant", "content": rec["text"]}]
            due = ctx.now() + think
            await ctx.sleep_until(due)

    tasks = [asyncio.create_task(session(i, p)) for i, p in enumerate(plans)]
    await ctx.sleep_until(ctx.seconds)
    window_over.set()
    while pending_sample and not all(t.done() for t in tasks):
        await asyncio.sleep(0.05)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
