"""A dense decode burst leaves before its predecessor is emitted.

The step loop runs a decode cycle in one of two orders (docs/scheduling.md).
Today's: host_sync, dispatch, compute, fetch, emit, record, back through the
loop. Ahead: right after a burst's fetch the next one is dispatched, and the
fetched tokens are delivered, the record closed and the burst after that
prepared while it computes. Which one a cycle takes is decided by what the
loop observes in its own state (`EngineCore._ahead_blocker`), never by a
setting. These tests hold what the reorder has to keep true:

(a) the same requests give the same streams, finish reasons and usage in both
    orders — rows ending by max_tokens, by EOS inside a burst with a new
    request taking the slot at once, and a cancel among them;
(b) a request that arrives while a burst is in flight is prefilled before
    any further burst is dispatched;
(c) a grammar, a drafter, a drain and a free list too short each keep the
    cycle in today's order, and say so on the record;
(d) the step records still tile the loop's time, and the counters add up.

Most engines here are driven inline (`tests.support.InlineLoop`:
`pending.put`, then the loop's own iteration on the test's thread, with
`_running` set so that the loop's own predicate decides): the steps and their
order are the test's. "While burst n is in flight" is `_prepare_burst`, which
every dense burst calls between its dispatch and the wait for it.

Rows are greedy or sampled under a per-request seed. A row on the shared
batch key is not compared: its tokens depend on where an activation's key
split falls among the bursts' (the key is split at every dispatch, in
dispatch order), which is a matter of arrival timing on the parent too.
"""

import asyncio
import time

import pytest

from llmlb_tpu.engine.metrics import AHEAD_BLOCKERS
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.engine.stepstats import INFLIGHT_SPANS, LOOP_BUCKETS
from llmlb_tpu.engine.tokenizer import ByteTokenizer
from llmlb_tpu.structured import ConstraintCompiler
from tests.support import InlineLoop as Inline
from tests.support import collect, collect_events

CFG = get_preset("debug-tiny")
TOK = ByteTokenizer(CFG.vocab_size)
BURST = 4


def _core(**kwargs) -> EngineCore:
    kwargs = {"num_slots": 4, "slot_capacity": 128,
              "prefill_buckets": (16, 32), "kv_page_size": 8, "seed": 0,
              "decode_burst": BURST, "prefix_cache": False, **kwargs}
    return EngineCore(CFG, **kwargs)


def _prompt(j: int, n: int = 6) -> list[int]:
    return [(7 * i + 3 * j) % 251 + 1 for i in range(n + j)]


def _greedy(j: int, max_tokens: int) -> Request:
    return Request(prompt_ids=_prompt(j), sampling=SamplingParams(
        temperature=0.0, max_tokens=max_tokens))


def _seeded(j: int, max_tokens: int, seed: int) -> Request:
    return Request(prompt_ids=_prompt(j), sampling=SamplingParams(
        temperature=0.9, seed=seed, max_tokens=max_tokens))


# ------------------------------------------------ (a) the same streams


NEVER = CFG.vocab_size + 7  # an EOS id no row samples


def _scenario(eos: int, ends_at: int, *, todays_order: bool):
    """Four rows of one prefill group — greedy to max_tokens 30, seeded to 22
    (neither a multiple of the burst), greedy to EOS as its decode token
    `ends_at`, greedy and cancelled while burst 2 is in flight — and two that
    arrive later: one while the burst after the EOS is in flight, which
    takes the slot the EOS freed, and one three bursts on. Returns every
    request's events and the run."""
    core = _core(eos_id=eos)
    run = Inline(core, todays_order=todays_order)
    reqs = {"long": _greedy(0, 30), "seeded": _seeded(1, 22, seed=1234),
            "eos": _greedy(2, 40), "cancelled": _greedy(3, 64),
            "takes_the_slot": _seeded(4, 11, seed=77),
            "late": _greedy(5, 9)}
    for name in ("long", "seeded", "eos", "cancelled"):
        core.pending.put(reqs[name])
    ends_in = -(-ends_at // BURST)  # the burst that holds the EOS
    run.during[2] = [reqs["cancelled"].cancel]
    run.during[ends_in + 1] = [
        lambda: core.pending.put(reqs["takes_the_slot"])]
    run.during[ends_in + 4] = [lambda: core.pending.put(reqs["late"])]
    run.run()
    events = {name: collect_events(r, timeout=None)
              for name, r in reqs.items()}
    return events, run


@pytest.fixture(scope="module")
def eos_inside_a_burst() -> tuple[int, int]:
    """(token, index): a token that the scenario's greedy row emits as its
    decode token 10, 11, 14 or 15 — inside its third or fourth burst, not at
    the burst's end — and that no row emits anywhere else. As the engine's
    EOS it ends that row, and that row alone, there."""
    events, _ = _scenario(NEVER, 10, todays_order=True)
    tokens = events["eos"][0]
    everything = [t for toks, _f, _s in events.values() for t in toks]
    for index in (10, 11, 14, 15):
        if everything.count(tokens[index]) == 1:
            return tokens[index], index
    raise AssertionError("no token of the row is its own: change a prompt")


def test_both_orders_give_the_same_streams_reasons_and_usage(
        eos_inside_a_burst):
    eos, ends_at = eos_inside_a_burst
    today, run_today = _scenario(eos, ends_at, todays_order=True)
    ahead, run = _scenario(eos, ends_at, todays_order=False)
    # tokens, finish reason and the size of every content event (usage is
    # the prompt's length and the number of tokens)
    assert ahead == today
    assert {name: (len(t), finish)
            for name, (t, finish, _s) in ahead.items()} == {
        "long": (30, "length"), "seeded": (22, "length"),
        "eos": (ends_at, "stop"), "cancelled": (1 + BURST, "cancelled"),
        "takes_the_slot": (11, "length"), "late": (9, "length")}
    # the order under test engaged ...
    records, records_today = run.decode_records(), run_today.decode_records()
    assert not any(r["dispatched_ahead"] for r in records_today)
    assert sum(r["dispatched_ahead"] for r in records) >= 4
    # ... and the rows that ended unseen by the dispatch were in a burst that
    # had left already: the cancelled one in burst 3, the one that met its
    # EOS in the burst after. Their columns went to nobody: the request that
    # took the EOS row's slot (slot 2, activated after that burst's fetch)
    # has the stream of today's order, above.
    ends_in = -(-ends_at // BURST)
    for burst in (3, ends_in + 1):
        assert records[burst - 1]["dispatched_ahead"]
        assert records[burst - 1]["active_slots"] == \
            records_today[burst - 1]["active_slots"] + 1
    took = records[ends_in + 1]  # the first burst after the arrival
    assert took["ahead_blocked_by"] == "admission"
    assert "2" in took["request_ids"]
    totals = run.core.metrics.summary()
    assert totals["decode_bursts_dispatched_ahead_total"] == sum(
        r["dispatched_ahead"] for r in records)


def test_a_started_engine_serves_the_same_usage_in_both_orders(
        eos_inside_a_burst):
    """The same through the service layer and the loop's own thread: six
    callers on four slots, so that rows end and slots change hands while
    bursts are in flight."""

    async def serve(todays_order: bool):
        core = _core(eos_id=eos_inside_a_burst[0])
        if todays_order:
            core._ahead_blocker = lambda plan: "control"
        core.start()
        engine = Engine("debug-tiny", core, TOK)
        try:
            finals = await asyncio.gather(*(
                engine.complete(_prompt(j), SamplingParams(
                    temperature=0.0 if j % 2 == 0 else 0.8,
                    seed=None if j % 2 == 0 else 100 + j,
                    max_tokens=17 + 5 * j))
                for j in range(6)))
            return ([(f.text, f.finish_reason, f.prompt_tokens,
                      f.completion_tokens) for f in finals],
                    core.metrics.summary())
        finally:
            engine.shutdown()

    today, totals_today = asyncio.run(serve(True))
    ahead, totals = asyncio.run(serve(False))
    assert ahead == today
    assert totals_today["decode_bursts_dispatched_ahead_total"] == 0
    assert totals["decode_bursts_dispatched_ahead_total"] > 0


# ------------------------------------------------ (b) admission is not behind


def test_an_arrival_is_prefilled_before_any_further_burst():
    core = _core()
    run = Inline(core)
    first, second = _greedy(0, 60), _seeded(1, 60, seed=5)
    late = _greedy(2, 12)
    core.pending.put(first)
    core.pending.put(second)
    run.during[3] = [lambda: core.pending.put(late)]
    run.run()
    records = core.step_stats.snapshot(limit=512)["records"][::-1]
    kinds = [r["kind"] for r in records]
    at = kinds.index("prefill", 1)  # the late request's
    # decode 1 (after the group's prefill), 2 and 3 ahead; the arrival came
    # while 3 was in flight: the next record is its prefill, then a decode
    assert kinds[:at + 2] == ["prefill", "decode", "decode", "decode",
                              "prefill", "decode"]
    assert [r.get("dispatched_ahead") for r in records[1:at]] == [
        False, True, True]
    after = records[at + 1]
    assert not after["dispatched_ahead"]
    assert after["ahead_blocked_by"] == "admission"
    assert after["active_slots"] == 3  # the late row decodes at once
    # and the order resumes behind it
    assert records[at + 2]["dispatched_ahead"]
    assert len(collect(late, timeout=None)[0]) == 12


# ------------------------------------------------ (c) today's order, and why


SCHEMA = {"type": "object", "properties": {"ok": {"type": "boolean"}},
          "required": ["ok"]}


def _blocked_run(case: str, *, todays_order: bool):
    """One run of a case that keeps the cycle in today's order; returns the
    streams and the run."""
    kwargs, requests, drain_during = {}, [], None
    if case == "constraint":
        requests = [
            Request(prompt_ids=_prompt(0), sampling=SamplingParams(
                temperature=0.0, max_tokens=24,
                constraint={"type": "json_schema", "schema": SCHEMA})),
            _greedy(1, 16)]
        kwargs = {"eos_id": TOK.eos_id}
    elif case == "draft":
        # a repetitive prompt: the prompt-lookup drafter is attached, and
        # whether or not it proposes, the row reads what _emit appends
        requests = [Request(prompt_ids=[5, 6, 7, 8, 9] * 3,
                            sampling=SamplingParams(temperature=0.0,
                                                    max_tokens=20)),
                    _seeded(1, 20, seed=9)]
        kwargs = {"spec_decode": True}
    elif case == "control":
        requests = [_greedy(0, 20), _seeded(1, 20, seed=3)]
        drain_during = 1
    elif case == "pages":
        # 8 pages of 4 cells for two rows of 5: while burst 2 is in flight
        # the short row (done with it, by its budget) still holds 4 pages
        # and the long one has 4, so the free list cannot give the fifth
        # that burst 3 writes; once the short row is emitted it can
        requests = [
            Request(prompt_ids=[3, 1, 4, 1, 5], sampling=SamplingParams(
                temperature=0.0, max_tokens=17)),
            Request(prompt_ids=[2, 7, 1, 8, 2], sampling=SamplingParams(
                temperature=0.8, seed=11, max_tokens=9))]
        kwargs = {"slot_capacity": 32, "kv_page_size": 4, "kv_pages": 9,
                  "prefill_buckets": (16,), "num_slots": 2}
    core = _core(**kwargs)
    if case == "constraint":
        core.constraint_compiler = ConstraintCompiler(TOK, CFG.vocab_size)
    run = Inline(core, todays_order=todays_order)
    for r in requests:
        core.pending.put(r)
    if drain_during:
        run.during[drain_during] = [core.begin_drain]
    run.run()
    return [collect(r, timeout=None) for r in requests], run


@pytest.mark.parametrize("case", ["constraint", "draft", "control", "pages"])
def test_what_needs_the_host_between_two_bursts_keeps_todays_order(case):
    today, _ = _blocked_run(case, todays_order=True)
    streams, run = _blocked_run(case, todays_order=False)
    assert streams == today
    assert all(finish in ("length", "stop") for _t, finish in streams)
    records = run.decode_records()
    assert records and records[0]["ahead_blocked_by"] == "first"
    assert all((r["ahead_blocked_by"] is None) == r["dispatched_ahead"]
               for r in records)
    if case == "pages":
        # bursts 1 and 2 (2 ahead), 3 held back by the free list, 4 ahead
        assert [r["ahead_blocked_by"] for r in records] == [
            "first", None, "pages", None]
        assert run.core.page_pool.available() == 8  # nothing leaked
    else:
        # no burst of such a batch ever leaves ahead
        assert not any(r["dispatched_ahead"] for r in records)
        assert {r["ahead_blocked_by"] for r in records[1:]} == {case}
    if case in ("constraint", "draft"):
        # a batch with a row that needs the host is not even prepared for
        assert all("host_sync_inflight" not in [n for n, _a, _d in r["spans"]]
                   for r in records if r["active_slots"] == 2)
    totals = run.core.metrics.summary()
    assert totals["decode_bursts_total"] == len(records)
    assert totals["decode_bursts_not_ahead_total"][case] == sum(
        r["ahead_blocked_by"] == case for r in records)


def test_a_verify_step_is_not_a_burst_and_nothing_is_prepared_for_it():
    """With drafts that match, the step is a `verify`: no burst counters."""
    core = _core(spec_decode=True)
    run = Inline(core)
    core.pending.put(Request(prompt_ids=[5, 6, 7, 8, 9] * 3,
                             sampling=SamplingParams(temperature=0.0,
                                                     max_tokens=20)))
    run.run()
    records = core.step_stats.snapshot(limit=512)["records"]
    for r in records:
        assert ("dispatched_ahead" in r) == (
            r["kind"] == "decode"), r["kind"]
    assert not any(r.get("dispatched_ahead") for r in records)


# ------------------------------------------------ (d) the records tile


def test_records_tile_the_loops_time_and_the_totals_add_up():
    core = _core()
    core.start()
    metrics = core.metrics
    try:
        t0, before = time.perf_counter(), metrics.summary()
        reqs = [_greedy(j, 30 + 9 * j) if j % 2 else
                _seeded(j, 30 + 9 * j, seed=j) for j in range(6)]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            assert collect(r)[1] == "length"
        t1, after = time.perf_counter(), metrics.summary()
    finally:
        core.stop()
    records = core.step_stats.snapshot(limit=512)["records"][::-1]
    decode = [r for r in records if r["kind"] == "decode"]
    assert any(r["dispatched_ahead"] for r in decode)
    for r in records:
        # a record's spans lie end to end and sum to its wall time
        at = 0.0
        for _name, offset, dur in r["spans"]:
            assert offset == pytest.approx(at, abs=3e-6)
            at += dur
        assert at == pytest.approx(r["wall_s"], abs=5e-6)
        # legacy phases: their sum is the wall time and the admission, and
        # host work with a burst in flight is `compute`
        assert r["total_s"] == pytest.approx(
            r["wall_s"] + r["since_prev"]["admit_s"], abs=1e-5)
        inflight = sum(d for n, _a, d in r["spans"] if n in INFLIGHT_SPANS)
        waited = sum(d for n, _a, d in r["spans"] if n == "compute")
        assert r["phases_s"]["compute"] == pytest.approx(
            waited + inflight, abs=5e-6)
        assert r["host_cpu_s"] <= r["wall_s"] - waited + 1e-3
    for prev, cur in zip(records, records[1:]):
        # consecutive records of the loop do not overlap, and what lies
        # between them is the next one's gap
        assert cur["seq"] == prev["seq"] + 1
        assert cur["t0_s"] >= prev["t1_s"] - 2e-6
        assert prev["t1_s"] + sum(cur["since_prev"].values()) == \
            pytest.approx(cur["t0_s"], abs=50e-6)
    # every second of the loop thread is in one bucket
    delta = {b: after["loop_seconds_total"]["main"][b]
             - before["loop_seconds_total"]["main"][b] for b in LOOP_BUCKETS}
    assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.02)
    steps = sum(r["wall_s"] for r in records)
    assert delta["step"] == pytest.approx(steps, rel=0.02)
    # the two totals and the reasons add up, to the records
    assert set(after["decode_bursts_not_ahead_total"]) == set(AHEAD_BLOCKERS)
    assert after["decode_bursts_total"] == len(decode)
    assert after["decode_bursts_dispatched_ahead_total"] == sum(
        r["dispatched_ahead"] for r in decode)
    assert (after["decode_bursts_dispatched_ahead_total"]
            + sum(after["decode_bursts_not_ahead_total"].values())
            == after["decode_bursts_total"])
    for reason, n in after["decode_bursts_not_ahead_total"].items():
        assert n == sum(r["ahead_blocked_by"] == reason for r in decode)
    text = metrics.render(queue_depth=0, active_slots=0, num_slots=4)
    assert (f"llmlb_engine_decode_bursts_total {len(decode)}\n") in text
    assert 'llmlb_engine_decode_bursts_not_ahead_total{reason="first"}' in text
