"""An arrival's prompt rides the next burst's first step.

The fifth order of a decode cycle (docs/scheduling.md "An arrival rides a
burst"): where ONE arrival can be placed without the emit of the burst just
fetched and its prompt fits the mixed step's width, the loop places it as
admission ahead does and dispatches NO prefill and NO activation — the next
burst is `StepPrograms.admit_many`, whose first step carries the prompt in
the same pass over the weights as the rows' tokens. These tests hold:

(a) the tokens of EVERY request are those of an engine whose family's record
    offers no mixed step, greedy and seeded alike; the riding row's first
    content event carries the burst's k tokens, the first of them its first;
(b) the record: `admitted` on that `decode` record, no `prefill` record, the
    counters (`mixed_admissions_total`, no prefill dispatch), the records
    still tile the loop's time;
(c) the host's mirrors of the riding row right after its first burst and at
    its end: `generated`, `_seq_lens`, its pages, `max_tokens` exact;
(d) who does not ride: two arrivals at once, a prompt wider than the width,
    a constrained request, a resume — each takes the order it took;
(e) the six stamps of a riding request's way in telescope to its `ttft_s`;
(f) what the width is read off, and that a started engine has built every
    window's mixed program by a call before an arrival rides one.
"""

import dataclasses
import time

import numpy as np
import pytest

from llmlb_tpu.engine import compilelog
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.stepstats import WAY_IN_STAMPS, way_in_stages
from llmlb_tpu.engine.tokenizer import ByteTokenizer
from llmlb_tpu.models import llama
from llmlb_tpu.structured import ConstraintCompiler
from tests.support import InlineLoop as Inline
from tests.support import collect_events

CFG = get_preset("debug-tiny")
TOK = ByteTokenizer(CFG.vocab_size)
BURST = 4
PAGE = 8


def _core(**kwargs) -> EngineCore:
    kwargs = {"num_slots": 4, "slot_capacity": 128,
              "prefill_buckets": (16, 32), "kv_page_size": PAGE, "seed": 0,
              "decode_burst": BURST, "prefix_cache": False, **kwargs}
    return EngineCore(CFG, **kwargs)


def _prompt(j: int, n: int) -> list[int]:
    return [(7 * i + 3 * j) % 251 + 1 for i in range(n)]


def _request(j: int, n: int, max_tokens: int, seed: int | None = None,
             **sampling) -> Request:
    if seed is None:
        sampling = {"temperature": 0.0, **sampling}
    else:
        sampling = {"temperature": 0.9, "seed": seed, **sampling}
    return Request(prompt_ids=_prompt(j, n), sampling=SamplingParams(
        max_tokens=max_tokens, **sampling))


def _house(core, late: list[Request], at: int = 3, **order):
    """Two rows decoding (one greedy, one seeded), and `late` arriving while
    burst `at` is in flight. Returns every request's events and the run."""
    run = Inline(core, **order)
    reqs = [_request(0, 6, 40), _request(1, 7, 37, seed=21), *late]
    core.pending.put(reqs[0])
    core.pending.put(reqs[1])
    run.during[at] = [lambda r=r: core.pending.put(r) for r in late]
    run.run()
    return [collect_events(r, timeout=None) for r in reqs], run


@pytest.fixture
def no_mixed_step(monkeypatch):
    """The dense family with a record that offers no mixed step: what every
    family read before this order existed, and the nine cells' still do."""
    monkeypatch.setattr(llama, "FAMILY", dataclasses.replace(
        llama.FAMILY, mixed_step=False))


def _admitted(run) -> list[dict]:
    return [r for r in run.decode_records() if "admitted" in r]


# ------------------------------------- (a), (b) the same tokens, the record


@pytest.mark.parametrize("n", [5, 16, 27, 32],
                         ids=lambda n: f"prompt_of_{n}")
@pytest.mark.parametrize("seed", [None, 77], ids=["greedy", "seeded"])
def test_a_riding_arrival_gets_the_tokens_of_a_prefilled_one(
        n, seed, monkeypatch):
    late = lambda: _request(2, n, 14, seed=seed)  # noqa: E731
    core = _core()
    assert core.mixed_width == 32
    streams, run = _house(core, [late()])
    with monkeypatch.context() as patched:
        patched.setattr(llama, "FAMILY", dataclasses.replace(
            llama.FAMILY, mixed_step=False))
        plain = _core()
        assert plain.mixed_width == 0
        want, run_plain = _house(plain, [late()])
    # every request's tokens and finish reason; the two rows that were
    # decoding got their events as they did
    assert [(t, f) for t, f, _ in streams] == [(t, f) for t, f, _ in want]
    assert streams[:2] == want[:2]
    tokens, finish, sizes = streams[2]
    assert (len(tokens), finish) == (14, "length")
    # one fetch brought the row its first token and the burst's k - 1 next
    assert sizes == [BURST, BURST, BURST, 2] and want[2][2] == [
        1 + BURST, BURST, BURST, 1]

    # the record: one decode burst admitted it, and no prefill was dispatched
    # for it (the one prefill record is the first two rows' group)
    (record,) = _admitted(run)
    assert record["admitted"] == {"slot": 2, "prompt_tokens": n}
    assert record["active_slots"] == 3 and record["tokens"] == 3 * BURST
    assert record["dispatched_ahead"] and record["dispatches"] == 1
    assert [r["tokens"] for r in run.records("prefill")] == [6 + 7]
    assert [r["tokens"] for r in run_plain.records("prefill")] == [6 + 7, n]
    assert not _admitted(run_plain)
    totals, totals_plain = (r.core.metrics.summary()
                            for r in (run, run_plain))
    assert totals["mixed_admissions_total"] == 1
    assert totals["prefill_dispatches_total"] == 1
    assert totals_plain["mixed_admissions_total"] == 0
    assert totals_plain["prefill_dispatches_total"] == 2
    assert "llmlb_engine_mixed_admissions_total 1" in (
        run.core.metrics.render(queue_depth=0, active_slots=0, num_slots=4))
    # the burst's record begins behind the placing: the stretch between the
    # fetched burst's end and its begin is the loop's `admit`
    records = run.records()
    at = records.index(record)
    gap = record["since_prev"]
    assert record["t0_s"] - records[at - 1]["t1_s"] == pytest.approx(
        gap["admit_s"] + gap["record_s"], abs=50e-6)
    assert gap["admit_s"] > gap["record_s"]
    for before, after in zip(records, records[1:]):
        assert after["t0_s"] >= before["t1_s"] - 50e-6
    assert core._in_flight is None


# --------------------------------------------- (c) the host's mirrors


@pytest.mark.parametrize("max_tokens", [BURST - 1, BURST, BURST + 1, 11],
                         ids=lambda n: f"max_tokens_{n}")
def test_the_riding_rows_mirrors_are_a_decode_rows(max_tokens):
    """A riding row enters its burst with n - 1 tokens and every step of
    the burst brings it one: `generated`, `_seq_lens` and its pages are
    what the device wrote, right after the burst and at its end."""
    n = 13  # 12 cells before its last prompt token: a page boundary inside
    core = _core()
    run = Inline(core)
    late = _request(2, n, max_tokens)
    core.pending.put(_request(0, 6, 60))
    core.pending.put(_request(1, 7, 60, seed=3))
    seen = {}

    def placed():
        # burst 4 — the one that carries the prompt — is in flight: the
        # row is its slot's, its mirror stands one before the prompt's end
        slot = core.slots[2]
        assert slot.request is late and not slot.first_pending
        seen["placed"] = (slot.generated, int(core._seq_lens[2]),
                          len(core._slot_pages[2]))

    def rode():
        slot = core.slots[2]
        if slot.request is late:
            seen["rode"] = (slot.generated, int(core._seq_lens[2]),
                            list(slot.out_tokens))
        else:
            seen["ended_inside"] = True

    run.during[3] = [lambda: core.pending.put(late)]
    run.during[4] = [placed]
    run.during[5] = [rode]
    run.run()
    tokens, finish, sizes = collect_events(late, timeout=None)
    assert (len(tokens), finish) == (max_tokens, "length")
    assert sum(sizes) == max_tokens
    # n + reach cells, a burst's k and the cell the host keeps ahead
    assert seen["placed"] == (0, n - 1, -(-(n + BURST + 1) // PAGE))
    if max_tokens > BURST:
        generated, length, out = seen["rode"]
        assert (generated, length) == (BURST, n - 1 + BURST)
        assert out == tokens[:BURST]
    else:
        assert seen["ended_inside"]  # counted to its end inside its burst
    # its pages went back, the slot is free
    assert core.slots[2].request is None and not core._slot_pages[2]


def test_a_row_that_rode_is_prepared_like_any_other():
    """The bursts behind the one it rode count the row as they count every
    row: the page it grows into is taken a burst ahead, the window follows
    its length, and it ends by max_tokens inside a burst counted to its
    end — no burst runs for it after."""
    core = _core()
    run = Inline(core)
    late = _request(2, 30, 23)
    core.pending.put(_request(0, 6, 40))
    core.pending.put(_request(1, 7, 40, seed=3))
    run.during[2] = [lambda: core.pending.put(late)]
    run.run()
    tokens, finish, _ = collect_events(late, timeout=None)
    assert (len(tokens), finish) == (23, "length")
    decode = run.decode_records()
    rode = next(i for i, r in enumerate(decode) if "admitted" in r)
    # 23 tokens at 4 a burst from the burst it rode: six bursts hold it
    # (the two rows that were decoding outlive it)
    assert [r["active_slots"] for r in decode[rode:rode + 7]] == [3] * 6 + [2]
    # pages: what its live rows hold once the burst's tokens are written
    first = decode[rode]
    others = sum(-(-(int(length) + BURST) // PAGE)
                 for length in (6 + 2 * BURST, 7 + 2 * BURST))
    assert first["kv_pages_live"] == others + -(-(30 - 1 + BURST) // PAGE)


# ------------------------------------------------ (d) who does not ride


def test_two_arrivals_at_once_are_prefilled_as_a_group():
    core = _core()
    streams, run = _house(core, [_request(2, 9, 10), _request(3, 9, 12)])
    assert not _admitted(run)
    assert [r["tokens"] for r in run.records("prefill")] == [13, 18]
    assert run.records("prefill")[1]["dispatched_ahead"]
    assert [len(t) for t, _, _ in streams[2:]] == [10, 12]
    assert core.metrics.summary()["mixed_admissions_total"] == 0


def test_a_prompt_wider_than_the_width_is_prefilled():
    """512 is a one-shot bucket and over the ridge at 4 slots: the width is
    16, a prompt of 17 takes the admission-ahead order, one of 16 rides."""
    sizes = {"slot_capacity": 1024, "prefill_buckets": (16, 512)}
    core = _core(**sizes)
    assert core.mixed_width == 16
    streams, run = _house(core, [_request(2, 17, 9)])
    assert not _admitted(run)
    assert [r["tokens"] for r in run.records("prefill")] == [13, 17]
    assert run.records("prefill")[1]["dispatched_ahead"]
    assert len(streams[2][0]) == 9
    streams, run = _house(_core(**sizes), [_request(2, 16, 9)])
    assert [r["admitted"]["prompt_tokens"] for r in _admitted(run)] == [16]
    assert len(streams[2][0]) == 9


def test_an_arrival_does_not_ride_a_window_whose_program_is_not_there():
    """Nothing is built between two bursts of a house that decodes: until
    the prewarm thread has lowered a window's mixed program, an arrival
    into a burst of that window is prefilled ahead, as before."""
    core = _core()
    run = Inline(core)
    core._mixed_ready.clear()
    reqs = [_request(0, 6, 40), _request(1, 7, 37, seed=21),
            _request(2, 9, 10), _request(3, 9, 7)]
    core.pending.put(reqs[0])
    core.pending.put(reqs[1])
    run.during[3] = [lambda: core.pending.put(reqs[2])]
    # ... and from there on it is
    run.during[6] = [lambda: core._mixed_ready.add(128),
                     lambda: core.pending.put(reqs[3])]
    run.run()
    assert [len(collect_events(r, timeout=None)[0]) for r in reqs] == [
        40, 37, 10, 7]
    assert [r["tokens"] for r in run.records("prefill")] == [13, 9]
    assert run.records("prefill")[1]["dispatched_ahead"]
    assert [r["admitted"]["prompt_tokens"] for r in _admitted(run)] == [9]
    assert ("admit_many", BURST, 128, False) in core.programs.cache


def test_a_constrained_arrival_takes_todays_order():
    schema = {"type": "object", "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"]}
    core = _core(eos_id=TOK.eos_id)
    core.constraint_compiler = ConstraintCompiler(TOK, CFG.vocab_size)
    late = _request(2, 9, 24, constraint={"type": "json_schema",
                                          "schema": schema})
    _streams, run = _house(core, [late])
    assert not _admitted(run)
    prefills = run.records("prefill")
    assert len(prefills) == 2 and not prefills[1]["dispatched_ahead"]
    assert "admission" in {r["ahead_blocked_by"]
                           for r in run.decode_records()}


def test_a_resume_takes_todays_order():
    """A parked request carries tokens it already emitted: its prompt is
    replayed by chunked prefill, and its activation restores its cursor."""
    core = _core()
    run = Inline(core)
    victim = _request(2, 9, 30)
    core.pending.put(_request(0, 6, 60))
    core.pending.put(victim)

    def park():
        core._park_rids.add(victim.request_id)

    run.during[2] = [park]
    run.run()
    tokens, finish, _ = collect_events(victim, timeout=None)
    assert (len(tokens), finish) == (30, "length")
    assert core.metrics.summary()["preempt_resumes_total"] >= 1
    assert not _admitted(run)


def test_an_engine_that_carries_an_adapter_pool_or_no_record_has_no_width(
        no_mixed_step):
    assert _core().mixed_width == 0


def test_the_width_is_the_largest_bucket_under_the_ridge():
    """num_slots + T tokens a pass stay under the ridge of the chip's
    published figures (a chip the table does not know is sized as a v5e:
    240 tokens at two bytes a weight, 480 at this configuration's four)."""
    assert _core().mixed_width == 32
    wide = {"slot_capacity": 1024, "prefill_buckets": (64, 128, 256, 512)}
    assert _core(**wide).mixed_width == 256
    assert _core(num_slots=300, **wide).mixed_width == 128
    assert _core(num_slots=470, **wide).mixed_width == 0
    # a legacy single step is three dispatches already: nothing rides it
    assert _core(decode_burst=1, fused_decode=False).mixed_width == 0


# ------------------------------------------------ (e) the way in


def test_a_riding_requests_stamps_telescope_to_its_ttft():
    core = _core()
    late = _request(2, 21, 9)
    _streams, run = _house(core, [late])
    stamps = [getattr(late, name) for name in WAY_IN_STAMPS[1:]]
    assert all(s is not None for s in stamps)
    assert stamps == sorted(stamps)
    stages = way_in_stages(late)
    ttft = late.first_token_at - late.submitted_at
    assert sum(stages[s] for s in ("inbox", "place", "prefill",
                                   "first_fetch")) == pytest.approx(
        ttft, abs=1e-4)
    (record,) = _admitted(run)
    # `place` ends where the burst that carries the prompt begins, and the
    # same burst's fetch brings the first token
    assert late.prefill_at == pytest.approx(record["t0_s"], abs=50e-6)
    assert late.prefill_seq == record["seq"] and late.prefill_chunks == 1
    (entry,) = record["first_tokens"]
    assert entry["prefill_seq"] == record["seq"] and entry["chunks"] == 1
    # `prefill` is the burst, dispatch to fetch; `first_fetch` what is left
    # to the emit
    assert record["t0_s"] < late.activated_at <= late.first_token_at
    events = [e["event"] for e in core.flightrec.timeline(
        late.request_id)["events"]]
    assert events.index("prefill_chunk") < events.index("first_token")


# ------------------------------------------------ (f) built before it is used


def test_a_started_engine_builds_the_program_of_a_window_it_has_decoded_in():
    """The prewarm thread builds the mixed program of each window the loop
    has dispatched a burst in, the loop calls it once at an empty house (on
    a mesh of several devices the thread's lowering may land under another
    cache key than a dispatch's: the call builds what is left), and an
    arrival that rides a burst of that window finds it built: NOTHING is
    built between two bursts, on any thread. A window nobody has decoded in
    has no program yet."""
    core = _core(slot_capacity=512)
    assert core._window_buckets == (256, 512) and core.mixed_width == 32
    core.start()
    try:
        # nothing is wanted before the loop's first burst
        time.sleep(0.3)
        assert not core._mixed_wanted and not core._mixed_ready
        collect_events(core.submit(_request(3, 5, 6)), timeout=60)
        deadline = time.time() + 120
        while time.time() < deadline and (not core._mixed_ready
                                          or core._mixed_uncalled):
            time.sleep(0.05)
        time.sleep(0.2)  # the call's own end
        assert core._mixed_ready == {256} and core._mixed_wanted == [256]
        first, second = _request(0, 6, 40), _request(1, 7, 40, seed=4)
        core.submit(first)
        core.submit(second)
        while first.first_token_at is None:
            time.sleep(0.01)
        built = compilelog.counters()
        late = _request(2, 19, 9)
        core.submit(late)
        tokens, finish, _ = collect_events(late, timeout=60)
        assert (len(tokens), finish) == (9, "length")
        assert core.metrics.summary()["mixed_admissions_total"] == 1
        assert not [b["fun_name"] for b in compilelog.recent(since=built)]
        for r in (first, second):
            collect_events(r, timeout=60)
        assert ("admit_many", BURST, 512, False) not in core.programs.cache
    finally:
        core.stop()


def test_the_lowering_lands_under_the_key_a_dispatch_has():
    """StepPrograms.prewarm_mixed with the operands as the loop holds them
    behind its first burst — the donated ones as placed shapes, the unplaced
    block tables and the key themselves — builds what the riding dispatch
    finds: a ride behind it builds no `admit_many`. (With a page pool no
    program has returned yet it would not: the placement differs.)"""
    core = _core()
    run = Inline(core)
    core._mixed_ready.clear()
    late = _request(2, 11, 6)
    core.pending.put(_request(0, 6, 40))
    core.pending.put(_request(1, 7, 37, seed=21))
    built = {}

    def lower():
        assert core.programs.prewarm_mixed(
            128, core._decode_operands(core._key), core.mixed_width)
        core._mixed_ready.add(128)
        built["at"] = compilelog.counters()

    run.during[2] = [lower]
    run.during[3] = [lambda: core.pending.put(late)]
    run.run()
    assert len(collect_events(late, timeout=None)[0]) == 6
    assert len(_admitted(run)) == 1
    assert "jit(admit_many)" not in [
        b["fun_name"] for b in compilelog.recent(since=built["at"])]


def test_a_lowering_that_fails_leaves_its_window_unridden(monkeypatch):
    """The prewarm is best effort: a window whose program could not be
    built is no window an arrival rides (it is prefilled ahead)."""
    core = _core()
    monkeypatch.setattr(core.programs, "admit_many", lambda window: 1 / 0)
    assert not core.programs.prewarm_mixed(
        128, core._decode_operands(core._key), core.mixed_width)
    monkeypatch.undo()
    run = Inline(core)
    core._mixed_ready.clear()  # what _prewarm_mixed leaves behind a failure
    late = _request(2, 9, 5)
    core.pending.put(_request(0, 6, 40))
    core.pending.put(_request(1, 7, 37, seed=21))
    run.during[3] = [lambda: core.pending.put(late)]
    run.run()
    assert not _admitted(run)
    assert len(collect_events(late, timeout=None)[0]) == 5
    assert run.records("prefill")[1]["dispatched_ahead"]


def test_the_building_call_writes_to_no_page_a_freed_row_held():
    """A finished request's slot is freed on the host at once and on the
    device at the next sync of the block tables, and the prompt's head may
    stay pinned as a prefix donor: the building call syncs first, so what it
    writes goes to the trash page and a later hit reads what was cached."""
    core = _core(prefix_cache=True, min_prefix_len=8)
    run = Inline(core)
    first = _request(0, 24, 6)
    core.pending.put(first)
    run.run()
    want = collect_events(first, timeout=None)[0]
    assert len(core.prefix_cache) == 1
    pool = [np.asarray(x).copy() for x in (core.cache_k, core.cache_v)]
    core._mixed_uncalled.append(128)
    core._build_mixed_program()
    for before, after in zip(pool, (core.cache_k, core.cache_v)):
        assert (np.asarray(after)[:, 1:] == before[:, 1:]).all()  # 0: trash
    again = _request(0, 24, 6)
    core.pending.put(again)
    run.run()
    assert collect_events(again, timeout=None)[0] == want
    assert core.metrics.summary()["prefix_hits_total"] == 1


def test_the_building_call_leaves_the_key_and_counts_as_prewarm():
    core = _core()
    key = np.asarray(core._key).copy()
    core._mixed_uncalled.append(128)
    assert core._house_is_empty()
    built = compilelog.counters()
    core._build_mixed_program()
    assert not core._mixed_uncalled
    assert (np.asarray(core._key) == key).all()
    assert ("admit_many", BURST, 128, False) in core.programs.cache
    by_thread = compilelog.summary(built)["by_thread"]
    assert by_thread["prewarm"]["programs_total"] >= 1
    assert by_thread["loop"]["programs_total"] == 0
    # and the house serves as it did
    streams, run = _house(core, [_request(2, 11, 6)])
    assert [len(t) for t, _, _ in streams] == [40, 37, 6]
    assert len(_admitted(run)) == 1
