"""A dense decode burst leaves before its predecessor is emitted, an
arrival's prefill before that, and with every slot held a burst leaves before
its predecessor is even fetched.

The step loop runs a decode cycle in one of five orders (docs/scheduling.md);
these are the first four, and tests/engine/test_mixed_admission.py the fifth's.
Today's: host_sync, dispatch, compute, fetch, emit, record, back through the
loop. Ahead: right after a burst's fetch the next one is dispatched, and the
fetched tokens are delivered, the record closed and the burst after that
prepared while it computes. Admission ahead: where the one thing in the way
is an arrival that can be placed without that emit, its prefill, its
activation and the next burst (the new row among its rows) are dispatched
back to back right after the fetch. Queued behind: where nothing stands in
the prepared burst's way and no slot is free for an arrival, it is dispatched
BEFORE the wait for the burst in flight, and the device starts it the moment
that one ends. Which one a cycle takes is decided by what the loop observes
in its own state (`EngineCore._ahead_blocker`, `EngineCore._arrivals_ahead`,
`EngineCore._queues_behind`), never by a setting. These tests hold what the
reorder has to keep true:

(a) the same requests give the same streams, finish reasons and usage in all
    four orders — rows ending by max_tokens, by EOS inside a burst with a new
    request taking the slot at once, and a cancel among them; an arrival, two
    arrivals, an arrival with an EOS in the un-emitted burst, one cancelled
    before its first token, one that ends inside the burst behind its
    prefill, and an EOS inside a burst whose successor is queued already;
(b) a request that arrives while a burst is in flight is prefilled before
    any further burst is dispatched;
(c) a grammar, a drafter, a drain and a free list too short each keep the
    cycle in today's order, and say so on the record; so does an arrival that
    needs an eviction, a chunked prefill, a grammar or a slot nobody has yet;
    the same four and a free slot keep a burst from being queued;
(d) the step records still tile the loop's time, and the counters add up;
(e) a run of queued bursts is bounded (`EngineCore.QUEUED_RUN`, for the trace
    reader: docs/scheduling.md): behind a whole run the next burst leaves
    ahead, and the streams are the same. The order's own tests, (a) to (d),
    run with the bound lifted (`ORDERS["queued_behind"]`); `queued_bounded`
    is the loop as it is.

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
from tests.support import RUN_LIFTED
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

# InlineLoop's arguments for four of the five orders of a decode cycle
ORDERS = {"today": {"todays_order": True},
          # PR 39's
          "ahead": {"admission_ahead": False, "queued_behind": False},
          # PR 49's: an arrival's prefill and activation leave ahead
          "admission_ahead": {"queued_behind": False, "rides": False},
          # the fourth, a run of queued bursts as long as a full house
          # lasts: EngineCore.QUEUED_RUN bounds it for the trace reader,
          # not for the order
          "queued_behind": {"queued_run": RUN_LIFTED, "rides": False},
          # the loop as it is: a lone arrival RIDES the next burst's first
          # step (tests/engine/test_mixed_admission.py holds that order)
          "queued_bounded": {}}


def _without_frames(events: dict) -> dict:
    """Every request's tokens and finish reason, without the sizes of its
    content events: a row that RODE a burst (the loop as it is, PR 61) gets
    its first token and the burst's other k - 1 in one event where a
    prefilled row gets 1 + k (tests/engine/test_mixed_admission.py holds
    the sizes of that order)."""
    return {name: (tokens, finish)
            for name, (tokens, finish, _sizes) in events.items()}


def _longest_run(flags: list[bool]) -> int:
    """The most true flags in a row."""
    longest = run = 0
    for flag in flags:
        run = run + 1 if flag else 0
        longest = max(longest, run)
    return longest


def _scenario(eos: int, ends_at: int, **order):
    """Four rows of one prefill group — greedy to max_tokens 30, seeded to 22
    (neither a multiple of the burst), greedy to EOS as its decode token
    `ends_at`, greedy and cancelled while burst 2 is in flight — and two that
    arrive later: one while the burst after the EOS is in flight, which
    takes the slot the EOS freed, and one three bursts on. Returns every
    request's events and the run."""
    core = _core(eos_id=eos)
    run = Inline(core, **order)
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
    today, run_today = _scenario(eos, ends_at, **ORDERS["today"])
    held, run_held = _scenario(eos, ends_at, **ORDERS["ahead"])
    ahead, run = _scenario(eos, ends_at, **ORDERS["admission_ahead"])
    queued, run_queued = _scenario(eos, ends_at, **ORDERS["queued_behind"])
    # tokens, finish reason and the size of every content event (usage is
    # the prompt's length and the number of tokens)
    assert ahead == today and held == today and queued == today
    # and with the run of queued bursts bounded, as the engine bounds it
    bounded, run_bounded = _scenario(eos, ends_at, **ORDERS["queued_bounded"])
    assert _without_frames(bounded) == _without_frames(today)
    queued_bounded = [r["queued_behind"]
                      for r in run_bounded.decode_records()]
    assert any(queued_bounded)
    assert _longest_run(queued_bounded) <= EngineCore.QUEUED_RUN
    assert {name: (len(t), finish)
            for name, (t, finish, _s) in ahead.items()} == {
        "long": (30, "length"), "seeded": (22, "length"),
        "eos": (ends_at, "stop"), "cancelled": (1 + BURST, "cancelled"),
        "takes_the_slot": (11, "length"), "late": (9, "length")}
    # the orders under test engaged ...
    records, records_today = run.decode_records(), run_today.decode_records()
    records_held = run_held.decode_records()
    assert not any(r["dispatched_ahead"] for r in records_today)
    assert sum(r["dispatched_ahead"] for r in records_held) >= 4
    assert sum(r["dispatched_ahead"] for r in records) >= 6
    assert not any(r["queued_behind"]
                   for r in records + records_held + records_today)
    # with the four rows' slots held, bursts 2 and 3 were queued behind their
    # predecessors — 3 with the cancelled row's column, which went to nobody
    # — until the emit of burst 2 saw the cancel and freed a slot: from
    # there on a free slot kept the cycle in the order of the run above,
    # record for record
    records_queued = run_queued.decode_records()
    assert [r["queued_behind"] for r in records_queued[:4]] == [
        False, True, True, False]
    assert [(r["dispatched_ahead"], r["ahead_blocked_by"], r["active_slots"])
            for r in records_queued[3:]] == [
        (r["dispatched_ahead"], r["ahead_blocked_by"], r["active_slots"])
        for r in records[3:]]
    assert [r["active_slots"] for r in records_queued[:3]] == [
        r["active_slots"] for r in records[:3]]
    assert [r["dispatched_ahead"] for r in run_queued.records("prefill")] == [
        False, True, False]
    _assert_totals_add_up(run_queued.core.metrics.summary(),
                          run_queued.records())
    # ... and the rows that ended unseen by the dispatch were in a burst that
    # had left already: the cancelled one in burst 3, the one that met its
    # EOS in the burst after. Their columns went to nobody: the request that
    # took the EOS row's slot (slot 2, activated after that burst's fetch)
    # has the stream of today's order, above.
    ends_in = -(-ends_at // BURST)
    for burst in (3, ends_in + 1):
        for recs in (records, records_held):
            assert recs[burst - 1]["dispatched_ahead"]
            assert recs[burst - 1]["active_slots"] == \
                records_today[burst - 1]["active_slots"] + 1
    # the first burst after the arrival: held for it where an arrival waits
    # for the emit, behind its prefill where it does not
    took_held, took = records_held[ends_in + 1], records[ends_in + 1]
    assert took_held["ahead_blocked_by"] == "admission"
    assert took["dispatched_ahead"]
    assert "2" in took["request_ids"] and "2" in took_held["request_ids"]
    # the last arrival came while the last burst of the rows before it was
    # in flight: with no burst to put it in, it waited for the emit
    prefills = run.records("prefill")
    assert [r["dispatched_ahead"] for r in prefills] == [False, True, False]
    assert records[-2]["ahead_blocked_by"] == "admission"
    assert not any(r["dispatched_ahead"] for r in run_held.records("prefill"))
    totals = run.core.metrics.summary()
    assert totals["decode_bursts_dispatched_ahead_total"] == sum(
        r["dispatched_ahead"] for r in records)
    assert totals["prefills_dispatched_ahead_total"] == 1
    assert totals["prefill_dispatches_total"] == 3


def _arrival_case(case: str, eos: int, **order):
    """One run of a case of (a): two rows decoding, and what arrives while a
    burst is in flight. Returns every request's events and the run."""
    core = _core(eos_id=eos, num_slots={
        "eos_unemitted": 3, "eos_with_its_successor_queued": 2}.get(case, 4))
    run = Inline(core, **order)
    reqs = {"first": _greedy(0, 40), "second": _seeded(1, 40, seed=21)}
    for r in reqs.values():
        core.pending.put(r)
    if case == "two_arrivals":
        reqs["late"], reqs["later"] = _seeded(2, 14, seed=8), _greedy(3, 11)
    elif case == "max_tokens_in_the_burst_behind":
        reqs["late"] = _greedy(2, 3)  # its first token and two of the burst
    else:
        reqs["late"] = _seeded(2, 14, seed=8)
    arrive = [lambda r=r: core.pending.put(r)
              for name, r in reqs.items() if name.startswith("late")]
    # "eos_unemitted": `first` meets its EOS in burst 2, and the arrival comes
    # while that burst is in flight; "eos_with_its_successor_queued": the
    # two rows hold both slots, so burst 3 is on the device before burst 2,
    # which holds the EOS, is fetched, and the arrival comes while 3 is in
    # flight, the slot freed by the emit under it
    run.during[2 if case == "eos_unemitted" else 3] = arrive
    if case == "cancelled_before_its_first_token":
        # placed behind burst 3's fetch; its first token comes with burst 4's
        run.during[4] = [reqs["late"].cancel]
    run.run()
    return {name: collect_events(r, timeout=None)
            for name, r in reqs.items()}, run


@pytest.fixture(scope="module")
def eos_of_the_first_row() -> int:
    """A token that the cases' greedy row emits as its decode token 5 or 6 —
    inside its second burst — and no row of the case emits anywhere else."""
    events, _ = _arrival_case("eos_unemitted", NEVER, todays_order=True)
    tokens = events["first"][0]
    everything = [t for toks, _f, _s in events.values() for t in toks]
    for index in (5, 6):
        if everything.count(tokens[index]) == 1:
            return tokens[index]
    raise AssertionError("no token of the row is its own: change a prompt")


@pytest.mark.parametrize("case", [
    "an_arrival", "two_arrivals", "eos_unemitted",
    "cancelled_before_its_first_token", "max_tokens_in_the_burst_behind",
    "eos_with_its_successor_queued"])
def test_an_arrival_gets_the_same_stream_in_every_order(
        case, eos_of_the_first_row):
    eos = eos_of_the_first_row if case.startswith("eos_") else NEVER
    runs = {name: _arrival_case(case, eos, **order)
            for name, order in ORDERS.items()}
    today, run_today = runs["today"]
    for name in ("ahead", "admission_ahead", "queued_behind"):
        assert runs[name][0] == today, name
    assert _without_frames(runs["queued_bounded"][0]) == _without_frames(
        today)
    late = today["late"]
    assert (len(late[0]), late[1]) == {
        "cancelled_before_its_first_token": (0, "cancelled"),
        "max_tokens_in_the_burst_behind": (3, "length"),
    }.get(case, (14, "length"))
    # which order each run took, by its records
    assert not any(r["dispatched_ahead"] for r in run_today.records()
                   if "dispatched_ahead" in r)
    _, run_held = runs["ahead"]
    assert any(r["dispatched_ahead"] for r in run_held.decode_records())
    assert not any(r["dispatched_ahead"] for r in run_held.records("prefill"))
    assert "admission" in {r["ahead_blocked_by"]
                           for r in run_held.decode_records()}
    _, run = runs["admission_ahead"]
    records = run.records()
    at = [r["kind"] for r in records].index("prefill", 1)  # the arrival's
    before, prefill, behind = records[at - 1:at + 2]
    assert prefill["dispatched_ahead"] and behind["dispatched_ahead"]
    assert behind["kind"] == before["kind"] == "decode"
    new_rows = 2 if case == "two_arrivals" else 1
    assert prefill["active_slots"] == new_rows
    assert "admission" not in {r["ahead_blocked_by"]
                               for r in run.decode_records()}
    _, run_queued = runs["queued_behind"]
    order = [(r["kind"], r["active_slots"], r["dispatched_ahead"],
              r.get("queued_behind")) for r in run_queued.records()]
    if case == "eos_with_its_successor_queued":
        # both slots held: bursts 2 and 3 left before their predecessors'
        # fetch, 3 with the column of the row that met its EOS in 2 — it
        # went to nobody. The emit of 2, under 3, freed the slot, so burst 4
        # was not queued: the arrival took the slot behind 3's fetch and its
        # prefill went in front of burst 4, which left right behind it
        assert today["first"][1] == "stop"
        assert order[:7] == [
            ("prefill", 2, False, None), ("decode", 2, False, False),
            ("decode", 2, False, True), ("decode", 2, False, True),
            ("prefill", 1, True, None), ("decode", 2, True, False),
            # ... and with both slots held again the order resumes
            ("decode", 2, False, True)]
        assert list(run_queued.records()[4]["request_ids"]) == ["0"]
        # before this order the slot changed hands the same way, one
        # dispatch later each time
        assert behind["active_slots"] == before["active_slots"] == 2
        assert list(prefill["request_ids"]) == ["0"]
        return
    # while a slot was free no burst was queued, and the cycle took the
    # order of the run above, record for record
    same = at + 2 if case == "two_arrivals" else len(records)
    assert order[:same] == [
        (r["kind"], r["active_slots"], r["dispatched_ahead"],
         r.get("queued_behind")) for r in records[:same]]
    if case == "two_arrivals":
        # the two took the last two slots: from the burst behind their
        # prefill on, bursts were queued
        assert order[same] == ("decode", 4, False, True)
    else:
        assert not any(r["queued_behind"]
                       for r in run_queued.decode_records())
    # the burst behind the prefill holds the new rows beside the old
    assert behind["active_slots"] == before["active_slots"] + new_rows
    if case == "eos_unemitted":
        # the row that met its EOS in the un-emitted burst held slot 0 until
        # that burst's emit: the arrival placed ahead of it took slot 2, the
        # one placed behind it slot 0 — and the burst behind the prefill
        # still carried the ended row's column, which went to nobody
        assert today["first"][1] == "stop"
        assert list(prefill["request_ids"]) == ["2"]
        assert list(run_today.records("prefill")[1]["request_ids"]) == ["0"]
        assert behind["active_slots"] == 3
    assert run.core.metrics.summary()["prefills_dispatched_ahead_total"] == 1


def test_a_started_engine_serves_the_same_usage_in_both_orders(
        eos_inside_a_burst):
    """The same through the service layer and the loop's own thread: six
    callers on four slots, so that rows end and slots change hands while
    bursts are in flight, and two more that come one by one while there is
    room."""

    async def serve(order: str):
        core = _core(eos_id=eos_inside_a_burst[0])
        if order == "today":
            core._ahead_blocker = lambda plan: "control"
        if order == "ahead":
            core._arrivals_ahead = lambda plan, k: None
        if order != "queued_behind":
            core._queues_behind = lambda plan: False
        core.start()
        engine = Engine("debug-tiny", core, TOK)

        def caller(j: int):
            return engine.complete(_prompt(j), SamplingParams(
                temperature=0.0 if j % 2 == 0 else 0.8,
                seed=None if j % 2 == 0 else 100 + j,
                max_tokens=17 + 5 * j))

        async def latecomer(j: int, after_s: float):
            await asyncio.sleep(after_s)
            return await caller(j)

        try:
            finals = await asyncio.gather(
                *(caller(j) for j in range(6)),
                latecomer(11, 0.3), latecomer(12, 0.6))
            return ([(f.text, f.finish_reason, f.prompt_tokens,
                      f.completion_tokens) for f in finals],
                    core.metrics.summary())
        finally:
            engine.shutdown()

    today, totals_today = asyncio.run(serve("today"))
    held, totals_held = asyncio.run(serve("ahead"))
    ahead, totals = asyncio.run(serve("admission_ahead"))
    queued, totals_queued = asyncio.run(serve("queued_behind"))
    assert ahead == today and held == today and queued == today
    for t in (totals_today, totals_held, totals):
        assert t["decode_bursts_queued_behind_total"] == 0
    assert (totals_queued["decode_bursts_queued_behind_total"]
            + totals_queued["decode_bursts_dispatched_ahead_total"]
            + sum(totals_queued["decode_bursts_not_ahead_total"].values())
            == totals_queued["decode_bursts_total"])
    assert totals_today["decode_bursts_dispatched_ahead_total"] == 0
    assert totals_held["decode_bursts_dispatched_ahead_total"] > 0
    assert totals["decode_bursts_dispatched_ahead_total"] > 0
    assert totals_today["prefills_dispatched_ahead_total"] == 0
    assert totals_held["prefills_dispatched_ahead_total"] == 0


# ------------------------------------------------ (b) admission is not behind


def test_an_arrival_is_prefilled_before_any_further_burst():
    core = _core()
    run = Inline(core, rides=False)  # with a prefill of its own: PR 49's
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
    assert [r.get("dispatched_ahead") for r in records[:at]] == [
        False, False, True, True]
    # a slot was free for the arrival all along, so no burst was on the
    # device before its predecessor was fetched
    assert not any(r.get("queued_behind") for r in records)
    # no burst that was not in flight when the arrival was seen runs in
    # front of its prefill: the prefill left right after burst 3's fetch,
    # before that burst was emitted, and the burst behind it at once
    prefill, after = records[at], records[at + 1]
    assert prefill["dispatched_ahead"]
    assert after["dispatched_ahead"] and after["ahead_blocked_by"] is None
    assert after["active_slots"] == 3  # the late row decodes at once
    assert prefill["t0_s"] - records[at - 1]["t1_s"] == pytest.approx(
        prefill["since_prev"]["admit_s"], abs=50e-6)
    assert after["t0_s"] == pytest.approx(prefill["t1_s"], abs=50e-6)
    # and the order resumes behind it
    assert records[at + 2]["dispatched_ahead"]
    assert len(collect(late, timeout=None)[0]) == 12


# ------------------------------------------------ (c) today's order, and why


SCHEMA = {"type": "object", "properties": {"ok": {"type": "boolean"}},
          "required": ["ok"]}


def _blocked_run(case: str, *, full_house: bool = False, **order):
    """One run of a case that keeps the cycle in today's order; returns the
    streams and the run. `full_house`: as many slots as rows, so that a free
    slot is not what keeps a burst from being queued."""
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
    elif case == "free_slot":
        requests = [_greedy(0, 20), _seeded(1, 20, seed=3)]
        kwargs = {"num_slots": 3}
    if full_house:
        kwargs["num_slots"] = len(requests)
    core = _core(**kwargs)
    if case == "constraint":
        core.constraint_compiler = ConstraintCompiler(TOK, CFG.vocab_size)
    run = Inline(core, **order)
    for r in requests:
        core.pending.put(r)
    if drain_during:
        run.during[drain_during] = [core.begin_drain]
    run.run()
    return [collect(r, timeout=None) for r in requests], run


@pytest.mark.parametrize("case", ["constraint", "draft", "control", "pages"])
def test_what_needs_the_host_between_two_bursts_keeps_todays_order(case):
    today, _ = _blocked_run(case, **ORDERS["today"])
    streams, run = _blocked_run(case, **ORDERS["admission_ahead"])
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


@pytest.mark.parametrize("case", ["constraint", "draft", "control", "pages",
                                  "free_slot"])
def test_what_keeps_a_burst_from_being_queued_says_so_on_the_record(case):
    """Every slot held (but in `free_slot`), the loop as it is: what needs
    the host between two bursts keeps a burst from leaving before its
    predecessor's fetch as it keeps it from leaving before the emit, and so
    does a free slot, behind which the burst leaves ahead."""
    full = case != "free_slot"
    today, _ = _blocked_run(case, full_house=full, **ORDERS["today"])
    streams, run = _blocked_run(case, full_house=full,
                                **ORDERS["queued_behind"])
    assert streams == today
    records = run.decode_records()
    assert len(records) >= 3 and records[0]["ahead_blocked_by"] == "first"
    # one of the three fields says which order a cycle took
    assert all((r["ahead_blocked_by"] is None)
               == (r["dispatched_ahead"] or r["queued_behind"])
               and not (r["dispatched_ahead"] and r["queued_behind"])
               for r in records)
    queued = [r["queued_behind"] for r in records]
    spans = [[n for n, _a, _d in r["spans"]] for r in records]
    if case == "pages":
        # burst 2 queued behind 1 with both slots held; 3 held back by the
        # free list (asked before the wait for 2, and again behind its
        # fetch), and the short row's emit left a slot free: 4 left ahead
        assert [r["ahead_blocked_by"] for r in records] == [
            "first", None, "pages", None]
        assert queued == [False, True, False, False]
        assert records[3]["dispatched_ahead"]
        assert run.core.page_pool.available() == 8  # nothing leaked
    elif case == "free_slot":
        assert not any(queued)
        assert all(r["dispatched_ahead"] for r in records[1:])
    else:
        # the drain began while burst 1 was in flight; a grammar's and a
        # drafter's rows never leave the host out
        assert not any(queued)
        assert {r["ahead_blocked_by"] for r in records[1:]} == {case}
    # a burst is queued under `dispatch_inflight` in its predecessor's
    # record, which ends in `fetch_inflight`; no other record holds either
    for i, names in enumerate(spans):
        behind_it = i + 1 < len(records) and queued[i + 1]
        assert ("dispatch_inflight" in names) == behind_it
        assert ("fetch_inflight" in names) == behind_it
        assert ("fetch" in names) != behind_it
    _assert_totals_add_up(run.core.metrics.summary(), run.records())


def _held_arrival_run(case: str, **order):
    """One run of a case in which an arrival cannot be placed ahead: two
    rows decoding, the arrival while burst 3 is in flight. Returns the
    streams and the run."""
    kwargs: dict = {}
    first, second = _greedy(0, 14), _seeded(1, 14, seed=31)
    late = _seeded(2, 6, seed=4)
    before: list[Request] = []
    if case == "eviction":
        # 10 pages of 8 cells: a finished request's head pins 2, the two
        # rows hold 3 each by burst 3's fetch, and the arrival's 20 tokens
        # and its first burst take 4 where the free list has 2 — today's
        # order evicts the pinned head for them
        kwargs = {"prefix_cache": True, "kv_pages": 11, "num_slots": 3,
                  "slot_capacity": 64}
        before = [Request(prompt_ids=_prompt(14), sampling=SamplingParams(
            temperature=0.0, max_tokens=2))]
        late = Request(prompt_ids=_prompt(13, 7), sampling=SamplingParams(
            temperature=0.9, seed=4, max_tokens=6))
    elif case == "chunked":
        # past the largest one-shot bucket: chunks between the bursts
        late = Request(prompt_ids=_prompt(0, 40), sampling=SamplingParams(
            temperature=0.9, seed=4, max_tokens=6))
    elif case == "constrained":
        late = Request(prompt_ids=_prompt(2), sampling=SamplingParams(
            temperature=0.0, max_tokens=24,
            constraint={"type": "json_schema", "schema": SCHEMA}))
        kwargs = {"eos_id": TOK.eos_id}
    elif case == "full_house":
        kwargs = {"num_slots": 2}
    core = _core(**kwargs)
    if case == "constrained":
        core.constraint_compiler = ConstraintCompiler(TOK, CFG.vocab_size)
    for r in before:
        core.pending.put(r)
        Inline(core).run()  # alone, to its end: its head is pinned
    run = Inline(core, **order)
    run.first_seq = core.step_stats.seq + 1  # the two rows' group prefill
    core.pending.put(first)
    core.pending.put(second)
    run.during[3] = [lambda: core.pending.put(late)]
    run.run()
    return [collect(r, timeout=None) for r in (first, second, late)], run


@pytest.mark.parametrize("case", ["eviction", "chunked", "constrained",
                                  "full_house"])
def test_an_arrival_that_cannot_be_placed_ahead_keeps_todays_order(case):
    today, run_today = _held_arrival_run(case, **ORDERS["today"])
    streams, run = _held_arrival_run(case, **ORDERS["admission_ahead"])
    assert streams == today
    assert all(finish in ("length", "stop") for _t, finish in streams)
    # no prefill left ahead, and the bursts say what held them: the arrival
    totals = run.core.metrics.summary()
    assert totals["prefills_dispatched_ahead_total"] == 0
    assert not any(r.get("dispatched_ahead") for r in run.records("prefill"))
    records = [r for r in run.records() if r["seq"] >= run.first_seq]
    kinds = [r["kind"] for r in records]
    assert kinds[:4] == ["prefill", "decode", "decode", "decode"]
    fourth = next(r for r in records[4:] if r["kind"] == "decode")
    assert fourth["ahead_blocked_by"] == "admission"
    # ... and the cycle was the parent's: the same steps in the same order,
    # with the same rows
    recs_today = [r for r in run_today.records()
                  if r["seq"] >= run_today.first_seq]
    assert [(r["kind"], r["active_slots"], r["tokens"])
            for r in records] == [(r["kind"], r["active_slots"], r["tokens"])
                                  for r in recs_today]
    blocked = [r["ahead_blocked_by"] for r in records if r["kind"] == "decode"]
    if case == "eviction":
        assert totals["prefix_evictions_total"] == 1
        assert run_today.core.metrics.summary()["prefix_evictions_total"] == 1
    elif case == "chunked":
        # the arrival's prompt goes in by chunks between the bursts
        assert "prefilling" in blocked
        assert kinds.count("prefill") == 1 + 2  # 40 tokens by chunks of 32
    elif case == "constrained":
        assert "constraint" in blocked
    elif case == "full_house":
        # held from burst 4 until a row ends and its emit frees the slot
        assert blocked.count("admission") >= 2
        assert records[-1]["active_slots"] == 1


def test_a_verify_step_is_not_a_burst_and_nothing_is_prepared_for_it():
    """With drafts that match, the step is a `verify`: no burst counters."""
    core = _core(spec_decode=True)
    run = Inline(core)
    core.pending.put(Request(prompt_ids=[5, 6, 7, 8, 9] * 3,
                             sampling=SamplingParams(temperature=0.0,
                                                     max_tokens=20)))
    run.run()
    records = core.step_stats.snapshot(limit=512)["records"]
    assert {r["kind"] for r in records} == {"prefill", "decode", "verify"}
    for r in records:
        # a dense burst's field, and a one-shot prefill group's
        assert ("dispatched_ahead" in r) == (
            r["kind"] != "verify"), r["kind"]
    assert not any(r.get("dispatched_ahead") for r in records)


# ------------------------------------------------ (d) the records tile


def _assert_records_tile(records: list[dict]) -> None:
    for r in records:
        # a record's spans lie end to end and sum to its wall time
        at = 0.0
        for _name, offset, dur in r["spans"]:
            assert offset == pytest.approx(at, abs=3e-6)
            at += dur
        assert at == pytest.approx(r["wall_s"], abs=5e-6)
        # legacy phases: their sum is the wall time and the admission, and
        # host work with a program of the loop on the device is `compute`
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


def _assert_totals_add_up(after: dict, records: list[dict]) -> None:
    """The counters of the orders against the records of a whole run."""
    decode = [r for r in records if r["kind"] == "decode"]
    prefill = [r for r in records if r["kind"] == "prefill"]
    assert set(after["decode_bursts_not_ahead_total"]) == set(AHEAD_BLOCKERS)
    assert after["decode_bursts_total"] == len(decode)
    assert after["decode_bursts_dispatched_ahead_total"] == sum(
        r["dispatched_ahead"] for r in decode)
    assert after["decode_bursts_queued_behind_total"] == sum(
        r["queued_behind"] for r in decode)
    assert (after["decode_bursts_dispatched_ahead_total"]
            + after["decode_bursts_queued_behind_total"]
            + sum(after["decode_bursts_not_ahead_total"].values())
            == after["decode_bursts_total"])
    for reason, n in after["decode_bursts_not_ahead_total"].items():
        assert n == sum(r["ahead_blocked_by"] == reason for r in decode)
    assert after["prefill_dispatches_total"] == len(prefill)
    assert after["prefills_dispatched_ahead_total"] == sum(
        r["dispatched_ahead"] for r in prefill)


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
    # six callers on four slots: with every slot held and nobody waiting,
    # bursts were queued behind the one in flight
    assert any(r["queued_behind"] for r in decode)
    _assert_records_tile(records)
    # every second of the loop thread is in one bucket
    delta = {b: after["loop_seconds_total"]["main"][b]
             - before["loop_seconds_total"]["main"][b] for b in LOOP_BUCKETS}
    assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.02)
    steps = sum(r["wall_s"] for r in records)
    assert delta["step"] == pytest.approx(steps, rel=0.02)
    # the totals and the reasons add up, to the records
    _assert_totals_add_up(after, records)
    text = metrics.render(queue_depth=0, active_slots=0, num_slots=4)
    assert (f"llmlb_engine_decode_bursts_total {len(decode)}\n") in text
    assert 'llmlb_engine_decode_bursts_not_ahead_total{reason="first"}' in text
    assert ("llmlb_engine_decode_bursts_queued_behind_total "
            f"{after['decode_bursts_queued_behind_total']}\n") in text
    assert "llmlb_engine_prefills_dispatched_ahead_total " in text
    assert "llmlb_engine_prefill_dispatches_total " in text


def test_records_tile_with_a_prefill_between_two_bursts_that_left_ahead():
    """Admission ahead: the predecessor's record, the prefill's and the
    burst's end and begin at one stamp each, the placing between the first
    two is the prefill's `admit`, and nothing of the three is counted twice."""
    core = _core()
    run = Inline(core, rides=False)
    clock = core._clock()  # the loop's clock: made before the first reading
    core.pending.put(_greedy(0, 40))
    core.pending.put(_seeded(1, 40, seed=2))
    run.during[3] = [lambda: core.pending.put(_seeded(2, 13, seed=6))]
    run.during[6] = [lambda: core.pending.put(_greedy(3, 9))]
    t0, before = time.perf_counter(), dict(clock.snapshot())
    run.run()
    t1, after = time.perf_counter(), dict(clock.snapshot())
    records = run.records()
    _assert_records_tile(records)
    kinds = [r["kind"] for r in records]
    ahead = [i for i, r in enumerate(records)
             if r["kind"] == "prefill" and r["dispatched_ahead"]]
    assert len(ahead) == 2
    for at in ahead:
        before_it, prefill, behind = records[at - 1:at + 2]
        assert kinds[at - 1] == kinds[at + 1] == "decode"
        assert before_it["dispatched_ahead"] and behind["dispatched_ahead"]
        names = [n for n, _a, _d in prefill["spans"]]
        assert names == ["dispatch", "activate_inflight"]
        # nothing between the three but the placing, which is `admit`
        gap = prefill["since_prev"]
        assert gap["admit_s"] > 0
        assert sum(gap.values()) - gap["admit_s"] < 200e-6  # the close
        assert sum(behind["since_prev"].values()) < 50e-6
        assert [n for n, _a, _d in behind["spans"]][:3] == [
            "dispatch_inflight", "emit_inflight", "host_sync_inflight"]
        # the predecessor has no `emit` of its own: it is in `behind`
        assert [n for n, _a, _d in before_it["spans"]][-1] == "fetch"
        # legacy phases of the prefill: the placing is its plan, the
        # activation behind the dispatch is compute, nothing is emit
        assert prefill["phases_s"]["plan"] == pytest.approx(gap["admit_s"])
        assert prefill["phases_s"]["emit"] == 0.0
        assert prefill["phases_s"]["compute"] == pytest.approx(
            prefill["wall_s"] - prefill["phases_s"]["dispatch"], abs=5e-6)
    delta = {b: after[b] - before[b] for b in LOOP_BUCKETS}
    assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.02)
    assert delta["step"] == pytest.approx(
        sum(r["wall_s"] for r in records), rel=0.02)
    _assert_totals_add_up(core.metrics.summary(), records)
    # the prefill histogram has every group, dispatched ahead or not
    assert core.metrics.prefill_step.n == kinds.count("prefill")


def test_records_tile_with_bursts_queued_behind_the_one_in_flight():
    """Queued behind: a burst's record begins at its predecessor's fetch
    and, where its successor is queued in its turn, holds no span in which
    the device has nothing from the loop; the predecessor's record ends in
    `fetch_inflight` behind the `dispatch_inflight` that queued it; nothing
    is counted twice, and nothing is in flight when the loop comes back.
    (The run's bound lifted: five in a row.)"""
    core = _core(num_slots=2)
    run = Inline(core, **ORDERS["queued_behind"])
    clock = core._clock()  # the loop's clock: made before the first reading
    core.pending.put(_greedy(0, 33))
    core.pending.put(_seeded(1, 21, seed=2))
    t0, before = time.perf_counter(), dict(clock.snapshot())
    run.run()  # asserts that nothing is in flight behind every iteration
    t1, after = time.perf_counter(), dict(clock.snapshot())
    records = run.records()
    _assert_records_tile(records)
    decode = run.decode_records()
    # 1 first token + 8 bursts of 4: bursts 2 to 6 queued while both rows
    # hold their slots (the short one is counted to its end in burst 5 and
    # is no row of 6, but holds its slot until 5 is emitted, under 6); from
    # that emit on a slot is free and the long row's bursts leave ahead
    assert [r["queued_behind"] for r in decode] == [
        False, True, True, True, True, True, False, False]
    assert [r["dispatched_ahead"] for r in decode] == [
        False, False, False, False, False, False, True, True]
    assert [r["active_slots"] for r in decode] == [2, 2, 2, 2, 2, 1, 1, 1]
    names = [[n for n, _a, _d in r["spans"]] for r in decode]
    assert names[0] == ["host_sync", "dispatch", "host_sync_inflight",
                        "dispatch_inflight", "compute", "fetch_inflight"]
    for queued_too in names[1:5]:
        assert queued_too == ["emit_inflight", "host_sync_inflight",
                              "dispatch_inflight", "compute",
                              "fetch_inflight"]
    # the last one queued: nothing behind it on the device at its fetch
    assert names[5] == ["emit_inflight", "host_sync_inflight", "compute",
                        "fetch"]
    assert names[6][:2] == ["dispatch", "emit_inflight"]
    for r in decode[1:6]:
        # it begins at the stamp its predecessor ends at, and the host's
        # share of the legacy phases is what the device was not covering
        assert sum(r["since_prev"].values()) < 50e-6
        assert r["phases_s"]["dispatch"] == r["phases_s"]["emit"] == 0.0
        assert r["phases_s"]["host_sync"] == 0.0
    for r in decode[1:5]:
        assert r["phases_s"]["fetch"] == 0.0
        assert r["phases_s"]["compute"] == pytest.approx(r["wall_s"],
                                                         abs=5e-6)
    assert decode[5]["phases_s"]["fetch"] > 0.0
    delta = {b: after[b] - before[b] for b in LOOP_BUCKETS}
    assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.02)
    assert delta["step"] == pytest.approx(
        sum(r["wall_s"] for r in records), rel=0.02)
    _assert_totals_add_up(core.metrics.summary(), records)
    assert core._in_flight is None


def test_a_full_house_queues_a_run_of_bursts_then_one_leaves_ahead():
    """Both slots held through 18 bursts: QUEUED_RUN bursts in a row are
    queued behind their predecessors, the next waits for its predecessor's
    fetch and leaves ahead (one gap on the device, for the benchmark's trace
    reader: EngineCore.QUEUED_RUN), and the run begins again. The streams
    are today's, the records tile, and the counters add up."""
    def full_house(**order):
        core = _core(num_slots=2)
        run = Inline(core, **order)
        reqs = [_greedy(0, 70), _seeded(1, 70, seed=5)]
        for r in reqs:
            core.pending.put(r)
        run.run()
        return [collect_events(r, timeout=None) for r in reqs], run

    today, _ = full_house(**ORDERS["today"])
    streams, run = full_house(**ORDERS["queued_bounded"])
    assert streams == today
    decode = run.decode_records()
    n = EngineCore.QUEUED_RUN
    # 1 first token + 17 whole bursts of 4 + 1 of the last token: both rows
    # end in burst 18, which is no burst of a run cut short
    queued = [r["queued_behind"] for r in decode]
    assert len(queued) == 18 and queued == [
        i % (n + 1) != 0 for i in range(18)]
    for i, r in enumerate(decode[1:], 1):
        # the burst behind a whole run left ahead, blocked by nothing
        assert r["dispatched_ahead"] == (i % (n + 1) == 0)
        assert r["ahead_blocked_by"] is None
    # the burst a run ends behind is fetched with nothing queued behind it
    for i in range(n, 18 - 1, n + 1):
        assert [name for name, _a, _d in decode[i]["spans"]][-2:] == [
            "compute", "fetch"]
    _assert_records_tile(run.records())
    _assert_totals_add_up(run.core.metrics.summary(), run.records())
    assert run.core._in_flight is None
