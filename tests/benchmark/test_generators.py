"""The generators' invariants: offered work is equal in every run. Any two
seeds give the same multiset of (prompt tokens, output tokens) pairs and the
same arrivals per one-second slot; they differ in order, offsets and
contents."""

import asyncio
import collections
import math
import time

import pytest

from benchmark import manifest as mf
from benchmark.generators import closed_loop, common, open_loop, sessions
from benchmark.tokenizer import WordTokenizer

OPEN_FILES = ["chat-paced"]
# the session mix measured in PR 23 (PERF.md section 7: it stood at its knee,
# so it is no cell yet); the generator's invariants are shown on its numbers
SESSIONS_MIX = {
    "generator": "sessions", "sessions": 24, "system_prompts": 4,
    "system_tokens": 1024, "user": {"kind": "uniform", "lo": 64, "hi": 256},
    "answer": {"kind": "uniform", "lo": 64, "hi": 128}, "think_s": 1.5,
    "think_jitter_s": 0.5, "max_context_tokens": 1920, "ramp_s": 12,
    "stagger_s": 5.5, "turns_per_session": 12, "max_prefill_group": 2}
SEEDS = [(0, 1), (7, 2147483659), (123456789, 3)]


def per_slot(plan):
    return collections.Counter(math.floor(r["due_s"]) for r in plan)


def pairs(plan, sample=None):
    return sorted((r["prompt_tokens"], r["max_tokens"]) for r in plan
                  if sample is None or r["in_sample"] == sample)


@pytest.mark.parametrize("name", OPEN_FILES)
@pytest.mark.parametrize("a,b", SEEDS)
def test_open_loop_offers_equal_work_for_any_two_seeds(name, a, b):
    traffic = mf.load_traffic(name)
    pa = open_loop.schedule(traffic, a, 45, 32000)
    pb = open_loop.schedule(traffic, b, 45, 32000)
    assert pairs(pa) == pairs(pb)
    assert pairs(pa, True) == pairs(pb, True)
    assert per_slot(pa) == per_slot(pb)
    # and the seed does change the order, the offsets and the contents
    assert [r["prompt_tokens"] for r in pa] != [r["prompt_tokens"] for r in pb]
    assert [r["due_s"] for r in pa] != [r["due_s"] for r in pb]
    # each arrival has an offset of its own inside its slot: the gaps differ
    gaps = lambda p: {round(y["due_s"] - x["due_s"], 6) for x, y in zip(p, p[1:])}
    assert len(gaps(pa)) > len(pa) // 2
    assert pa[0]["messages"] != pb[0]["messages"]
    # the same seed gives the same inputs
    assert pa == open_loop.schedule(traffic, a, 45, 32000)


@pytest.mark.parametrize("name", OPEN_FILES)
def test_open_loop_sample_is_the_requests_due_in_the_window(name):
    traffic = mf.load_traffic(name)
    plan = open_loop.schedule(traffic, 5, 45, 32000)
    assert plan == sorted(plan, key=lambda r: r["due_s"])
    for r in plan:
        assert r["in_sample"] == (0 <= r["due_s"] < 45)
    assert min(r["due_s"] for r in plan) >= -traffic["ramp_s"]
    n = sum(1 for r in plan if r["in_sample"])
    assert n == math.floor(45 * traffic["rate_per_s"])
    tok = WordTokenizer(32000)
    for r in plan[:20]:  # a prompt is exactly the tokens the plan says
        assert len(tok.encode(tok.apply_chat_template(r["messages"]))) == r["prompt_tokens"]
        assert traffic["prompt"]["lo"] <= r["prompt_tokens"] <= traffic["prompt"]["hi"]
        assert traffic["output"]["lo"] <= r["max_tokens"] <= traffic["output"]["hi"]


@pytest.mark.parametrize("rate,first,n,want", [
    (3.4, 0, 5, [3, 3, 4, 3, 4]),
    (3.4, -2, 2, [3, 4]),
    (0.5, 0, 4, [0, 1, 0, 1]),
    (4.0, 0, 3, [4, 4, 4]),
])
def test_slot_counts_are_a_fixed_sequence(rate, first, n, want):
    assert common.slot_counts(rate, first, n) == want
    assert sum(common.slot_counts(rate, 0, 100)) == math.floor(100 * rate)


@pytest.mark.parametrize("dist,n", [
    ({"kind": "lognormal", "median": 256, "sigma": 0.9, "lo": 32, "hi": 1536}, 153),
    ({"kind": "lognormal", "median": 96, "sigma": 0.6, "lo": 16, "hi": 384}, 153),
    ({"kind": "uniform", "lo": 64, "hi": 128}, 64),
])
def test_quantile_grid_is_midpoints_not_draws(dist, n):
    grid = common.quantile_grid(dist, n)
    assert grid == common.quantile_grid(dist, n) == sorted(grid)
    assert len(grid) == n and dist["lo"] <= grid[0] and grid[-1] <= dist["hi"]
    if dist["kind"] == "lognormal":
        assert abs(grid[n // 2] - dist["median"]) <= 0.03 * dist["median"]


@pytest.mark.parametrize("seed", [0, 5, 2147483659])
@pytest.mark.parametrize("n", [124, 27, 3])
def test_stratified_order_spreads_the_long_prompts_evenly(seed, n):
    import random

    pairs = [(10 * i, i % 7) for i in range(n)]
    got = common.stratified_order(list(pairs), random.Random(seed))
    assert sorted(got) == sorted(pairs)
    k = min(common.ORDER_STRATA, n)
    assert common.ORDER_STRATA == 4
    cut = sorted(pairs)[(3 * n) // k][0] if k == 4 else None
    for b in range(0, n - k + 1, k):
        block = got[b:b + k]
        if k == 4 and b + k <= (n // 4) * 4:
            # one pair of each quartile class in every whole block: exactly
            # one of the longest quarter
            assert sum(1 for p in block if p[0] >= cut) == 1
    assert got != common.stratified_order(list(pairs), random.Random(seed + 1)) or n < 4


def test_fixed_pairs_keep_both_multisets_and_use_no_seed():
    p, o = list(range(10, 20)), list(range(100, 110))
    got = common.fixed_pairs(p, o)
    assert got == common.fixed_pairs(p, o)
    assert sorted(a for a, _ in got) == p and sorted(b for _, b in got) == o
    assert [b for _, b in got] != o  # long prompts do not all get long answers


@pytest.mark.parametrize("a,b", SEEDS)
def test_closed_loop_deals_out_the_same_lengths(a, b):
    traffic = mf.load_traffic("decode-saturated")
    pa = closed_loop.client_plans(traffic, a, 32000)
    pb = closed_loop.client_plans(traffic, b, 32000)
    assert len(pa) == traffic["clients"] == 32
    flat = lambda ps: sorted(n for p in ps for n in p["prompt_tokens"])
    assert flat(pa) == flat(pb)
    assert [p["prompt_tokens"] for p in pa] != [p["prompt_tokens"] for p in pb]
    assert pa == closed_loop.client_plans(traffic, a, 32000)
    # the stagger is the server's progress, not a clock's grid (PERF.md, PR 26)
    assert traffic["start_after_tokens"] == 2


@pytest.mark.parametrize("a,b", SEEDS)
def test_sessions_deal_out_the_same_turns(a, b):
    traffic = SESSIONS_MIX
    pa = sessions.session_plans(traffic, a, 32000)
    pb = sessions.session_plans(traffic, b, 32000)
    turns = lambda ps: sorted(t for p in ps for t in p["turns"])
    assert turns(pa) == turns(pb)
    assert [p["turns"] for p in pa] != [p["turns"] for p in pb]
    assert len({p["system"] for p in pa}) == traffic["system_prompts"]
    counts = collections.Counter(p["system"] for p in pa)
    assert set(counts.values()) == {traffic["sessions"] // traffic["system_prompts"]}
    tok = WordTokenizer(32000)
    sys_ids = tok.encode(tok.apply_chat_template(
        [{"role": "system", "content": pa[0]["system"]}]))
    assert len(sys_ids) - 1 == traffic["system_tokens"]  # minus <|assistant|>
    for u, ans, think in pa[0]["turns"]:
        assert traffic["user"]["lo"] <= u <= traffic["user"]["hi"]
        assert traffic["answer"]["lo"] <= ans <= traffic["answer"]["hi"]
        assert abs(think - traffic["think_s"]) <= traffic["think_jitter_s"]


class FakeCtx:
    """The harness's side of a generator, on the real clock at a small
    scale: send answers after `service_s`."""

    def __init__(self, traffic, seed, seconds, service_s=0.0):
        self.traffic, self.seed, self.seconds, self.vocab = traffic, seed, seconds, 512
        self.t0 = time.monotonic() + float(traffic.get("ramp_s", 0))
        self.sent = []
        self.service_s = service_s
        self.fail_first = False

    def now(self):
        return time.monotonic() - self.t0

    async def sleep_until(self, t):
        await asyncio.sleep(max(0.0, t - self.now()))

    async def send(self, messages, max_tokens, *, due_s, prompt_tokens,
                   in_sample, kind="request", on_first=None, on_words=None):
        rec = {"due_s": due_s, "send_s": self.now(), "in_sample": in_sample,
               "kind": kind, "prompt_tokens": prompt_tokens,
               "max_tokens": max_tokens, "first_s": self.now(),
               "text": " ".join(f"t{9 + i}" for i in range(max_tokens)) + " "}
        self.sent.append(rec)
        if self.fail_first and len(self.sent) == 1:
            raise ConnectionError("the first answer never came")
        # the answer arrives in four parts, as a burst's frames do
        for part in range(1, 5):
            await asyncio.sleep(self.service_s / 4)
            if on_words is not None:
                on_words(max_tokens * part // 4)
        rec["last_s"] = rec["end_s"] = self.now()
        return rec


def test_open_loop_drive_sends_every_sampled_request_at_its_due_time():
    traffic = {"generator": "open_loop", "rate_per_s": 12.5, "ramp_s": 1,
               "tail_s": 1, "prompt": {"kind": "uniform", "lo": 8, "hi": 40},
               "output": {"kind": "uniform", "lo": 2, "hi": 6}}
    ctx = FakeCtx(traffic, 11, 1, service_s=0.01)
    asyncio.run(open_loop.drive(ctx))
    sample = [r for r in ctx.sent if r["in_sample"]]
    assert len(sample) == 12  # floor(1 * 12.5)
    assert all(0 <= r["due_s"] < 1 for r in sample)
    assert all(0 <= r["send_s"] - r["due_s"] < 0.2 for r in ctx.sent)
    assert any(r["due_s"] < 0 for r in ctx.sent)  # the ramp was offered


def test_sessions_drive_samples_follow_up_turns_due_in_the_window():
    traffic = {"generator": "sessions", "sessions": 4, "system_prompts": 2,
               "system_tokens": 40, "user": {"kind": "uniform", "lo": 8, "hi": 16},
               "answer": {"kind": "uniform", "lo": 4, "hi": 8},
               "think_s": 0.05, "think_jitter_s": 0.02,
               "max_context_tokens": 120, "ramp_s": 0.3, "stagger_s": 0.1,
               "turns_per_session": 6}
    ctx = FakeCtx(traffic, 3, 1, service_s=0.02)
    asyncio.run(sessions.drive(ctx))
    sample = [r for r in ctx.sent if r["in_sample"]]
    assert sample and all(r["kind"] == "turn" and 0 <= r["due_s"] < 1 for r in sample)
    assert all(r["prompt_tokens"] + r["max_tokens"] <= traffic["max_context_tokens"]
               for r in ctx.sent)
    firsts = [r for r in ctx.sent if r["kind"] == "first"]
    assert len(firsts) > traffic["sessions"]  # every session started, some anew
    assert all(not r["in_sample"] for r in firsts)
    # a follow-up turn's prompt grows by the answer and the next message
    assert max(r["prompt_tokens"] for r in sample) > 40 + 16 + 8


def test_closed_loop_drive_samples_what_finished_in_the_window():
    traffic = {"generator": "closed_loop", "clients": 3, "max_tokens": 4,
               "prompt": {"kind": "uniform", "lo": 8, "hi": 16}, "ramp_s": 0.3,
               "requests_per_client": 2, "start_after_tokens": 2}
    ctx = FakeCtx(traffic, 1, 1, service_s=0.1)
    asyncio.run(closed_loop.drive(ctx))
    done = [r for r in ctx.sent if "end_s" in r]
    assert len(done) >= 20
    for r in done:
        assert r["in_sample"] == (0 <= r["end_s"] < 1)
    assert sum(r["in_sample"] for r in done) >= 20


@pytest.mark.parametrize("fail_first", [False, True])
def test_closed_loop_starts_each_caller_behind_the_last_ones_progress(fail_first):
    """`start_after_tokens`: the stagger counts in tokens the caller before
    has received, not in seconds; a first answer that fails holds nobody
    back."""
    traffic = {"generator": "closed_loop", "clients": 4, "max_tokens": 8,
               "prompt": {"kind": "uniform", "lo": 8, "hi": 16}, "ramp_s": 0.5,
               "requests_per_client": 2, "start_after_tokens": 2}
    ctx = FakeCtx(traffic, 5, 0.3, service_s=0.2)
    ctx.fail_first = fail_first

    async def run():
        try:
            await closed_loop.drive(ctx)
        except ConnectionError:
            pass

    asyncio.run(run())
    first = ctx.sent[:4]  # the four callers' first requests, in caller order
    gaps = [b["send_s"] - a["send_s"] for a, b in zip(first, first[1:])]
    assert first[0]["send_s"] == pytest.approx(-0.5, abs=0.03)
    # a quarter of an answer (2 of 8 tokens) is a quarter of the service time
    want = [0.0 if fail_first else 0.05, 0.05, 0.05]
    assert gaps == pytest.approx(want, abs=0.03)
    assert len(ctx.sent) > 8  # and every caller went on after its first
