"""A block family's burst leaves before its predecessor is emitted, an
arrival's prefill before that, and with every slot held a burst leaves before
its predecessor is even fetched.

The step loop runs a decode cycle in one of five orders (docs/scheduling.md
"The five orders of a decode cycle"; tests/engine/test_decode_overlap.py
holds them for a dense burst). A family that generates by diffusion over
blocks takes the first four, through the same loop
(`EngineCore._decode_bursts`), with what a block burst needs that a dense one
does not:

- the host cannot COUNT how many blocks a row commits in the burst in
  flight, only bound it (a commit a pass), so the next burst's pages and
  window are taken for the worst case (`_prepare_burst`);
- every row that holds its request goes into the next burst, the device
  stops the ones that ended, and the emit drops their columns by the (slot,
  request) pair of the dispatch (`_emit_blocks`) — but where NO row is sure
  to outlive the burst in flight the cycle is today's (`first`);
- an arrival placed ahead prefills its prompt's WHOLE blocks, the remainder
  opens its first block as given tokens, and its row joins the burst with no
  first token pending.

These tests hold what the reorder has to keep true: (a) the same tokens,
finish reasons, usage and `commit` flight-recorder counts in all four
orders; (b) an arrival seen while a burst is in flight is prefilled before
any further burst; (c) what keeps today's order, or keeps a burst from being
queued behind the one in flight, says so on the record; (d) the records tile
the loop's time, the counters add up, and a record closed by
`LoopClock.handover` carries the `block` counts of one closed by
`_record_step`.

One tiny block engine's shapes a module, driven inline
(`tests.support.InlineLoop`). Blocks of 4, bursts of 4 passes: a row may
advance 16 positions in a burst (`denoising_steps` 1 does) and a burst
reaches 20 past a row's length. Rows are greedy or seeded: a row on the
shared batch key is not compared (see test_decode_overlap.py).
"""

import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from llmlb_tpu.engine.metrics import BLOCK_COUNTS
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
# the tiny block model of the scheduler's own tests: blocks of 4, float32
from tests.engine.test_block_scheduler import B, CFG, HF, MASK, PARAMS
from tests.engine.test_decode_overlap import (
    ORDERS,
    _assert_records_tile,
    _assert_totals_add_up,
)
from tests.support import InlineLoop as Inline
from tests.support import collect_events

BURST = 4
NEVER = -1  # an EOS id no row samples


def _core(**kw) -> EngineCore:
    args = dict(num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
                kv_page_size=8, decode_burst=BURST, eos_id=NEVER, seed=0,
                prefix_cache=False)
    return EngineCore(CFG, PARAMS, **{**args, **kw})


def _prompt(n: int, j: int) -> list[int]:
    return np.random.default_rng(j).integers(8, MASK, size=n).tolist()


def _request(n: int, j: int, max_tokens: int, **sampling) -> Request:
    """A request of prompt `j` of n tokens, greedy unless told otherwise."""
    sampling.setdefault("temperature", 0.0)
    return Request(prompt_ids=_prompt(n, j), sampling=SamplingParams(
        max_tokens=max_tokens, **sampling))


def _seeded(n: int, j: int, max_tokens: int, **sampling) -> Request:
    return _request(n, j, max_tokens, temperature=0.9, seed=1000 + j,
                    **sampling)


def _commits(core: EngineCore, request: Request) -> list[tuple[int, int]]:
    """A request's `commit` flight-recorder events: (blocks, tokens) a
    burst that brought it any."""
    events = core.flightrec.timeline(request.request_id)["events"]
    return [(e["attrs"]["blocks"], e["attrs"]["tokens"])
            for e in events if e["event"] == "commit"]


def _outcome(core: EngineCore, reqs: dict[str, Request]) -> dict:
    """What a caller sees of each request and what its flight record
    counted: (tokens, finish reason, tokens a content event, commits)."""
    return {name: (*collect_events(r, timeout=None), _commits(core, r))
            for name, r in reqs.items()}


# ------------------------------------------------ (a) the same streams


def _case(case: str, eos: int, ends_in: int = 0, **order):
    """One run of a case of (a). Two rows decode from one prefill group —
    `first`, greedy, and `second`, seeded at a temperature — and what the
    case adds; `ends_in`: the burst in which `first` meets the EOS. Returns
    every request's outcome and the run."""
    slots = {"eos_takes_the_slot": 2, "eos_unemitted": 3}.get(case, 4)
    core = _core(eos_id=eos, num_slots=slots)
    run = Inline(core, **order)
    # 30 and 22: neither ends on a block's edge (7 blocks and 2, 5 and 2)
    reqs = {"first": _request(8, 1, 30), "second": _seeded(12, 2, 22)}
    late = None
    if case == "max_tokens_inside_a_block":
        pass
    elif case == "eos_takes_the_slot":
        # the arrival comes while the burst after the one with the EOS is in
        # flight, the slot freed by the emit under it
        reqs["first"] = _request(8, 1, 60)
        reqs["second"] = _seeded(12, 2, 40)
        late = (ends_in + 1, _seeded(9, 3, 11))
    elif case == "eos_unemitted":
        # the arrival comes while the burst that holds the EOS is in flight
        reqs["first"] = _request(8, 1, 60)
        reqs["second"] = _seeded(12, 2, 40)
        late = (ends_in, _seeded(9, 3, 11))
    elif case == "cancel_in_flight":
        reqs["second"] = _seeded(12, 2, 40)
        reqs["cancelled"] = _request(8, 4, 64)
        # burst 3 is in flight, burst 2 (the row's first block) emitted: the
        # row runs on in burst 4, which left before the emit that saw it
        run.during[3] = [reqs["cancelled"].cancel]
        late = (5, _request(16, 5, 9))
    elif case == "seeded_arrival":
        late = (2, _seeded(16, 6, 14))
    elif case == "a_commit_every_pass":
        # four blocks a burst: the bound of _prepare_burst met, two pages of
        # 8 a burst, beside a row that commits one
        reqs["every_pass"] = _request(8, 7, 60, denoising_steps=1)
        late = (2, _request(8, 8, 24, denoising_steps=1))
    elif case == "given_tokens":
        # 13 = three whole blocks and one given token; 3: no whole block,
        # nothing to prefill, three given tokens
        late = (2, _seeded(13, 9, 14))
        reqs["shorter_than_a_block"] = _request(3, 10, 9)
    else:
        raise AssertionError(case)
    for name, r in reqs.items():
        if name != "shorter_than_a_block":
            core.pending.put(r)
    if late is not None:
        reqs["late"] = late[1]
        run.during[late[0]] = run.during.get(late[0], []) + [
            lambda: core.pending.put(late[1])]
    if case == "given_tokens":
        run.during[4] = [
            lambda: core.pending.put(reqs["shorter_than_a_block"])]
    run.run()
    return _outcome(core, reqs), run


CASES = {  # case -> {request: (tokens, finish reason)} where it is fixed
    "max_tokens_inside_a_block": {"first": (30, "length"),
                                  "second": (22, "length")},
    "eos_takes_the_slot": {"second": (40, "length"), "late": (11, "length")},
    "eos_unemitted": {"second": (40, "length"), "late": (11, "length")},
    "cancel_in_flight": {"first": (30, "length"), "late": (9, "length")},
    "seeded_arrival": {"late": (14, "length")},
    "a_commit_every_pass": {"every_pass": (60, "length"),
                            "late": (24, "length")},
    "given_tokens": {"late": (14, "length"),
                     "shorter_than_a_block": (9, "length")},
}


@pytest.fixture(scope="module")
def eos_of_the_first_row() -> tuple[int, int]:
    """(token, burst): a token that the cases' greedy row emits inside its
    second or third block and that no row of the two EOS cases emits
    anywhere else (its whole 60 tokens among them), and the burst whose
    fetch brings the block that holds it."""
    outcome, _ = _case("eos_takes_the_slot", NEVER, 3, todays_order=True)
    tokens = outcome["first"][0]
    everything = [t for toks, *_ in outcome.values() for t in toks]
    for index in (5, 6, 9, 10):
        if everything.count(tokens[index]) == 1:
            break
    else:
        raise AssertionError("no token of the row is its own: change a prompt")
    _, run = _case("eos_takes_the_slot", tokens[index], 3, todays_order=True)
    # a record names the requests its slots hold once it is emitted
    held = ["0" in r["request_ids"] for r in run.decode_records()]
    return tokens[index], held.index(False) + 1


@pytest.mark.parametrize("case", list(CASES))
def test_every_order_gives_the_same_streams_usage_and_commits(
        case, eos_of_the_first_row):
    eos, ends_in = (eos_of_the_first_row if case.startswith("eos_")
                    else (NEVER, 0))
    runs = {name: _case(case, eos, ends_in, **order)
            for name, order in ORDERS.items()}
    today, run_today = runs["today"]
    # tokens, finish reason, the size of every content event (usage is the
    # prompt's length and the number of tokens) and the commits a burst
    for name in ("ahead", "admission_ahead", "queued_behind",
                 "queued_bounded"):
        assert runs[name][0] == today, name
    for name, want in CASES[case].items():
        assert (len(today[name][0]), today[name][1]) == want, name
    for tokens, finish, frames, commits in today.values():
        assert sum(frames) == len(tokens) and max(frames, default=0) <= B
        assert finish in ("length", "stop", "cancelled")
    if case.startswith("eos_"):
        assert today["first"][1] == "stop" and len(today["first"][0]) < 12
    if case == "cancel_in_flight":
        assert today["cancelled"][1] == "cancelled"
        assert 0 < len(today["cancelled"][0]) <= 2 * B
    # which order each run took, by its records
    assert not any(r["dispatched_ahead"] for r in run_today.records())
    _, run_held = runs["ahead"]
    assert any(r["dispatched_ahead"] for r in run_held.decode_records())
    assert not any(r["dispatched_ahead"] for r in run_held.records("prefill"))
    _, run = runs["admission_ahead"]
    records = run.records()
    assert sum(r["dispatched_ahead"] for r in run.decode_records()) >= 3
    assert not any(r["queued_behind"] for name in ORDERS
                   if not name.startswith("queued_")
                   for r in runs[name][1].decode_records())
    _, run_queued = runs["queued_behind"]
    queued = [r["queued_behind"] for r in run_queued.decode_records()]
    if case == "eos_takes_the_slot":
        # two rows on two slots: the burst behind the one that holds the EOS
        # was on the device before that one was fetched, with the ended
        # row's column, which went to nobody; the emit under it freed the
        # slot, the next burst was NOT queued, and the arrival's prefill
        # went in front of it
        assert queued[1:ends_in + 1] == [True] * ends_in
        assert not queued[ends_in + 1]
        mine = run_queued.records()
        at = [r["kind"] for r in mine].index("prefill", 1)
        assert mine[at]["dispatched_ahead"]
        assert list(mine[at]["request_ids"]) == ["0"]
        assert mine[at + 1]["dispatched_ahead"]
        assert mine[at + 2]["queued_behind"]  # both slots held again
    elif case in ("max_tokens_inside_a_block", "eos_unemitted",
                  "seeded_arrival"):
        # a slot was free all along: the records are the run's above
        assert not any(queued)
        assert [(r["kind"], r["active_slots"], r["dispatched_ahead"])
                for r in run_queued.records()] == [
            (r["kind"], r["active_slots"], r["dispatched_ahead"])
            for r in records]
    _assert_totals_add_up(run_queued.core.metrics.summary(),
                          run_queued.records())
    if "late" not in today:
        return
    assert "admission" in {r["ahead_blocked_by"]
                           for r in run_held.decode_records()}
    at = [r["kind"] for r in records].index("prefill", 1)  # the arrival's
    before, prefill, behind = records[at - 1:at + 2]
    assert prefill["dispatched_ahead"] and behind["dispatched_ahead"]
    assert behind["kind"] == before["kind"] == "decode"
    assert [n for n, _a, _d in prefill["spans"]] == [
        "dispatch", "activate_inflight"]
    # the burst behind the prefill holds the new row beside the old, and the
    # prefill took the prompt's whole blocks
    if case != "eos_takes_the_slot":
        assert behind["active_slots"] == before["active_slots"] + 1
    if case == "given_tokens":
        assert prefill["tokens"] == 12  # of 13
        # the prompt shorter than a block went ahead too: nothing prefilled
        assert [r["tokens"] for r in run.records("prefill")
                if r["dispatched_ahead"]] == [12, 0]
    if case == "eos_takes_the_slot":
        # the row that met its EOS was emitted under the burst after it,
        # which had left with its column: the arrival took its slot (0)
        # behind that burst's fetch, before its emit — the column went to
        # nobody
        assert list(prefill["request_ids"]) == ["0"]
        assert before["active_slots"] == behind["active_slots"] == 2
    if case == "eos_unemitted":
        # the row held slot 0 until its burst's emit: the arrival placed
        # ahead of that emit took slot 2, the one placed behind it slot 0
        assert list(prefill["request_ids"]) == ["2"]
        assert list(run_today.records("prefill")[1]["request_ids"]) == ["0"]
        assert behind["active_slots"] == 3
    if case == "a_commit_every_pass":
        # the bound was met: a burst in which the row committed in every
        # pass, dispatched before the burst in front of it was emitted
        assert any(r["dispatched_ahead"] and r["blocks_committed"] >= BURST + 1
                   for r in run.decode_records())
        # the reference's blocks come one after another: its first 20
        # tokens (past the first burst's 16 and two page edges) are the
        # first 20 of 60
        want = ref.generate(PARAMS, HF, _prompt(8, 7), 20, denoising_steps=1)
        assert today["every_pass"][0][:20] == want


# ------------------------------------------------ (b) admission is not behind


def test_an_arrival_is_prefilled_before_any_further_burst():
    core = _core()
    run = Inline(core)
    late = _request(9, 23, 12)
    core.pending.put(_request(8, 21, 60))
    core.pending.put(_seeded(12, 22, 60))
    run.during[3] = [lambda: core.pending.put(late)]
    run.run()
    records = run.records()
    kinds = [r["kind"] for r in records]
    at = kinds.index("prefill", 1)  # the late request's
    # decode 1 (after the group's prefill), 2 and 3 ahead; the arrival came
    # while 3 was in flight: the next record is its prefill, then a decode
    assert kinds[:at + 2] == ["prefill", "decode", "decode", "decode",
                              "prefill", "decode"]
    assert [r.get("dispatched_ahead") for r in records[:at]] == [
        False, False, True, True]
    prefill, after = records[at], records[at + 1]
    assert prefill["dispatched_ahead"]
    assert after["dispatched_ahead"] and after["ahead_blocked_by"] is None
    assert after["active_slots"] == 3  # the late row decodes at once
    assert after["t0_s"] == pytest.approx(prefill["t1_s"], abs=50e-6)
    assert records[at + 2]["dispatched_ahead"]  # and the order resumes
    # a slot was free for the arrival all along: no burst was queued
    assert not any(r.get("queued_behind") for r in records)
    assert len(collect_events(late, timeout=None)[0]) == 12


# ------------------------------------------------ (c) today's order, and why


def _blocked_run(case: str, *, full_house: bool = False, **order):
    """One run of a case that keeps a cycle in today's order; returns the
    outcomes and the run. `full_house`: as many slots as rows, so that a
    free slot is not what keeps a burst from being queued."""
    kwargs: dict = {"num_slots": 2} if full_house else {}
    reqs = {"first": _request(8, 31, 24), "second": _seeded(8, 32, 24)}
    late = None
    if case == "pages":
        # 10 pages of 8 cells, two rows of 8 tokens: burst 1 takes 4 pages
        # a row (8 + 20 cells), the bound for the next six (8 + 16 + 20),
        # and the free list has 2 for the 4 that lack; the short row's one
        # block comes with burst 2 (a first block's commit rides in the
        # fifth pass) and that emit frees its 4
        reqs["second"] = _seeded(8, 32, 4)
        kwargs = {"kv_pages": 11, "num_slots": 2, "slot_capacity": 64}
    elif case == "free_slot":
        kwargs = {"num_slots": 3}
    elif case == "first":
        # 12 tokens: no row is ever sure to outlive a burst that may
        # commit 16
        reqs = {"first": _request(8, 31, 12), "second": _seeded(8, 32, 12)}
    elif case == "chunked":
        # past the largest one-shot bucket: chunks between the bursts
        late = _seeded(40, 33, 6)
    elif case == "prefix_hit":
        kwargs = {"prefix_cache": True}
        late = Request(prompt_ids=_prompt(16, 34) + _prompt(5, 35),
                       sampling=SamplingParams(temperature=0.9, seed=4,
                                               max_tokens=6))
    core = _core(**kwargs)
    if case == "prefix_hit":
        # alone, to its end: its head of 16 is pinned
        core.pending.put(Request(
            prompt_ids=_prompt(16, 34) + _prompt(7, 36),
            sampling=SamplingParams(temperature=0.0, max_tokens=2)))
        Inline(core).run()
    run = Inline(core, **order)
    run.first_seq = core.step_stats.seq + 1  # the two rows' group prefill
    for r in reqs.values():
        core.pending.put(r)
    if case == "control":
        run.during[1] = [core.begin_drain]
    if late is not None:
        reqs["late"] = late
        run.during[2] = [lambda: core.pending.put(late)]
    run.run()
    return _outcome(core, reqs), run


@pytest.mark.parametrize("case", ["pages", "control", "first", "chunked",
                                  "prefix_hit"])
def test_what_keeps_todays_order_says_so_on_the_record(case):
    today, run_today = _blocked_run(case, **ORDERS["today"])
    outcome, run = _blocked_run(case, **ORDERS["admission_ahead"])
    assert outcome == today
    assert all(finish == "length" for _t, finish, _f, _c in outcome.values())
    records = [r for r in run.decode_records() if r["seq"] >= run.first_seq]
    blocked = [r["ahead_blocked_by"] for r in records]
    assert blocked[0] == "first"
    assert all((r["ahead_blocked_by"] is None) == r["dispatched_ahead"]
               for r in records)
    totals = run.core.metrics.summary()
    if case == "pages":
        # bursts 2 and 3 held back by the free list; 4 ahead; from 5 on
        # the row's last 16 tokens: it may end in the burst in flight
        assert blocked[:5] == ["first", "pages", "pages", None, "first"]
        assert run.core.page_pool.available() == 10  # nothing leaked
    elif case == "control":
        assert set(blocked[1:]) == {"control"}
    elif case == "first":
        assert set(blocked) == {"first"}
        # nothing was taken for a burst that was not offered
        assert all("host_sync_inflight" in [n for n, _a, _d in r["spans"]]
                   for r in records)
    else:
        # the arrival cannot be placed ahead: no prefill left ahead, the
        # burst behind it says `admission`, and the steps are the parent's
        assert totals["prefills_dispatched_ahead_total"] == 0
        assert blocked[:3] == ["first", None, "admission"]
        mine = [r for r in run.records() if r["seq"] >= run.first_seq]
        theirs = [r for r in run_today.records()
                  if r["seq"] >= run_today.first_seq]
        assert [(r["kind"], r["active_slots"], r["tokens"])
                for r in mine] == [(r["kind"], r["active_slots"], r["tokens"])
                                   for r in theirs]
        if case == "chunked":
            assert "prefilling" in blocked
        else:
            assert totals["prefix_hits_total"] == 1
    assert totals["decode_bursts_not_ahead_total"][case if case in (
        "pages", "control", "first") else "admission"] >= 1


@pytest.mark.parametrize("case", ["pages", "control", "first", "free_slot",
                                  "none"])
def test_what_keeps_a_block_burst_from_being_queued_says_so_on_the_record(
        case):
    """Every slot held (but in `free_slot`), the loop as it is: a free list
    too short for the bound, a drain and a batch of which no row is sure to
    outlive the burst in flight keep a burst from leaving before its
    predecessor's fetch, and so does a free slot, behind which it leaves
    ahead; with none of them (`none`) it is queued."""
    full = case != "free_slot"
    today, _ = _blocked_run(case, full_house=full, **ORDERS["today"])
    outcome, run = _blocked_run(case, full_house=full,
                                **ORDERS["queued_behind"])
    assert outcome == today
    records = run.decode_records()
    assert records[0]["ahead_blocked_by"] == "first"
    # one of the three fields says which order a cycle took
    assert all((r["ahead_blocked_by"] is None)
               == (r["dispatched_ahead"] or r["queued_behind"])
               and not (r["dispatched_ahead"] and r["queued_behind"])
               for r in records)
    queued = [r["queued_behind"] for r in records]
    blocked = [r["ahead_blocked_by"] for r in records]
    if case == "pages":
        # 2 and 3 held back by the free list, asked before the wait and
        # again behind the fetch; the short row's emit left a slot free, so
        # 4 left ahead and was not queued
        assert blocked[:5] == ["first", "pages", "pages", None, "first"]
        assert not any(queued) and records[3]["dispatched_ahead"]
        assert run.core.page_pool.available() == 10  # nothing leaked
    elif case == "control":
        assert not any(queued) and set(blocked[1:]) == {"control"}
    elif case == "first":
        assert not any(queued) and set(blocked) == {"first"}
    elif case == "free_slot":
        assert not any(queued)
        assert any(r["dispatched_ahead"] for r in records)
    else:
        # 24 tokens a row: bursts are queued while each row is sure to
        # outlive the one in flight (it may commit 16); then none is offered
        last = queued.index(True, 1)
        while queued[last + 1]:
            last += 1
        assert queued[1] and blocked[last + 1] == "first"
        assert not any(queued[last + 1:])
        names = [[n for n, _a, _d in r["spans"]] for r in records]
        assert names[0][-3:] == ["dispatch_inflight", "compute",
                                 "fetch_inflight"]
        assert names[last] == ["emit_inflight", "host_sync_inflight",
                               "compute", "fetch", "emit"]
    _assert_totals_add_up(run.core.metrics.summary(), run.records())


# ------------------------------------------------ (d) the records tile


def _tiling_run(**order):
    core = _core()
    run = Inline(core, **order)
    core.pending.put(_request(8, 41, 40))
    core.pending.put(_seeded(12, 42, 40))
    run.during[3] = [lambda: core.pending.put(_seeded(9, 43, 13))]
    run.during[6] = [lambda: core.pending.put(_request(16, 44, 9))]
    run.run()
    return run


def test_records_tile_and_a_handover_keeps_the_block_counts():
    run_today = _tiling_run(**ORDERS["today"])
    run = _tiling_run(**ORDERS["admission_ahead"])
    records = run.records()
    _assert_records_tile(records)
    _assert_totals_add_up(run.core.metrics.summary(), records)
    decode, decode_today = run.decode_records(), run_today.decode_records()
    prefills = run.records("prefill")
    assert [r["dispatched_ahead"] for r in prefills] == [False, True, True]
    ahead = [r for r in decode if r["dispatched_ahead"]]
    assert len(ahead) >= 6
    # a record closed by LoopClock.handover (every burst that another left
    # ahead of: it has no `emit` span of its own) carries the counts that
    # _record_step gives the same burst in today's order
    handed = [[n for n, _a, _d in r["spans"]][-1] == "fetch" for r in decode]
    assert sum(handed) >= 6 and not all(handed)
    assert not any([n for n, _a, _d in r["spans"]][-1] == "fetch"
                   for r in decode_today)
    keys = BLOCK_COUNTS + ("tokens", "active_slots", "experts_touched",
                           "expert_assignments")
    assert len(decode) == len(decode_today)
    for mine, theirs in zip(decode, decode_today):
        assert mine["block_passes"] == BURST
        # a burst that left ahead carries the columns of rows that had
        # ended unseen: the device ran no pass for them
        assert mine["active_slots"] >= theirs["active_slots"]
        assert {k: mine[k] for k in keys if k != "active_slots"} == {
            k: theirs[k] for k in keys if k != "active_slots"}
    totals, totals_today = (run.core.metrics.summary(),
                            run_today.core.metrics.summary())
    for name in BLOCK_COUNTS:
        assert totals[f"{name}_total"] == totals_today[f"{name}_total"] == sum(
            r[name] for r in decode)
    assert totals["decode_bursts_total"] == len(decode)
    assert totals["prefills_dispatched_ahead_total"] == 2


def test_records_tile_with_block_bursts_queued_behind_the_one_in_flight():
    """Two rows on two slots: every burst a row is sure to outlive is
    queued behind the one in flight; the records tile, the totals add up,
    and a record that ends in `fetch_inflight` carries the `block` counts
    of the same burst in today's order."""
    def two_rows(**order):
        core = _core(num_slots=2)
        run = Inline(core, **order)
        core.pending.put(_request(8, 41, 56))
        core.pending.put(_seeded(12, 42, 56))
        run.run()
        return run

    run_today, run = (two_rows(**ORDERS["today"]),
                      two_rows(**ORDERS["queued_behind"]))
    records = run.records()
    _assert_records_tile(records)
    _assert_totals_add_up(run.core.metrics.summary(), records)
    decode, decode_today = run.decode_records(), run_today.decode_records()
    queued = [r["queued_behind"] for r in decode]
    assert sum(queued) >= 4 and not queued[0]
    for r, nxt in zip(decode, decode[1:]):
        names = [n for n, _a, _d in r["spans"]]
        assert (names[-1] == "fetch_inflight") == nxt["queued_behind"]
        assert ("dispatch_inflight" in names) == nxt["queued_behind"]
        if r["queued_behind"]:
            assert names[:2] == ["emit_inflight", "host_sync_inflight"]
    keys = BLOCK_COUNTS + ("tokens", "experts_touched", "expert_assignments")
    assert len(decode) == len(decode_today)
    for mine, theirs in zip(decode, decode_today):
        assert {k: mine[k] for k in keys} == {k: theirs[k] for k in keys}
    assert run.core._in_flight is None


def test_a_full_house_of_block_rows_queues_runs_of_bursts():
    """Both slots held through a dozen block bursts: never more than
    EngineCore.QUEUED_RUN of them in a row are queued; the burst behind a
    whole run waits for its predecessor's fetch and leaves ahead, and the
    run begins again. The outcomes are today's and the totals add up."""
    def full_house(**order):
        core = _core(num_slots=2)
        run = Inline(core, **order)
        reqs = {"first": _request(8, 61, 112), "second": _seeded(12, 62, 112)}
        for r in reqs.values():
            core.pending.put(r)
        run.run()
        return _outcome(core, reqs), run

    today, _ = full_house(**ORDERS["today"])
    outcome, run = full_house(**ORDERS["queued_bounded"])
    assert outcome == today
    decode = run.decode_records()
    n, runs, length = EngineCore.QUEUED_RUN, [], 0
    for i, r in enumerate(decode):
        if r["queued_behind"]:
            length += 1
            continue
        if length:
            runs.append((length, i))
        length = 0
    assert runs and max(length for length, _ in runs) == n
    # (the last run ends where a row does: that cycle is today's)
    ahead = [decode[behind] for length, behind in runs[:-1] if length == n]
    assert len(ahead) >= 2 and all(
        r["dispatched_ahead"] and r["ahead_blocked_by"] is None
        for r in ahead)
    _assert_records_tile(run.records())
    _assert_totals_add_up(run.core.metrics.summary(), run.records())
    assert run.core._in_flight is None
