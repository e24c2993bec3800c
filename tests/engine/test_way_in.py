"""A request's way in (docs/tracing.md): time to first token cut into stages,
each stamped where it ends, on the step loop's clock, and joined to the step
records. On the CPU debug engine: the stages of every path to a first token
sum to the engine's own `ttft_s`, carry the seq of the prefill and of the
fetch that served them, and go to the three places that read them — the
`first_token` flight-recorder event, the `first_tokens` of the step record
whose fetch brought the token, and the cumulative `.metrics.way_in`."""

import asyncio
import glob
import itertools
import os
import time

import pytest

from llmlb_tpu.engine import stepstats
from llmlb_tpu.engine.flightrec import EVENTS
from llmlb_tpu.engine.scheduler import Request, SamplingParams
from llmlb_tpu.engine.stepstats import PREFILL_CUT, WAY_IN, way_in_stages
from llmlb_tpu.engine.streamstats import EventQueue, StreamStats
from tests.support import InlineLoop, collect

ENGINE_STAGES = ("inbox", "place", "prefill", "first_fetch")


@pytest.fixture(scope="module")
def engine():
    from llmlb_tpu.engine.service import Engine

    eng = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=128, prefill_buckets=(16,),
        kv_page_size=16, min_prefix_len=16)
    yield eng
    eng.shutdown()


def _records(core, since: int) -> list[dict]:
    snap = core.step_stats.snapshot(limit=core.step_stats.capacity)
    return sorted((r for r in snap["records"] if r["seq"] > since),
                  key=lambda r: r["seq"])


def _events(core, rid: str, name: str) -> list[dict]:
    tl = core.flightrec.timeline(rid)
    return [e for e in (tl or {}).get("events", ()) if e["event"] == name]


def _served(core, rid: str, since: int):
    """(the request's `first_token` attrs, its step-record entry, the record
    that carried it, every record since), the sum of the stages checked
    against the `finished` event's `ttft_s`."""
    first = _events(core, rid, "first_token")
    assert len(first) == 1
    attrs = first[0]["attrs"]
    assert all(attrs[s] >= 0 for s in ENGINE_STAGES)
    ttft = _events(core, rid, "finished")[0]["attrs"]["ttft_s"]
    assert sum(attrs[s] for s in ENGINE_STAGES) == pytest.approx(
        ttft, abs=1e-3)
    records = _records(core, since)
    carriers = [(r, e) for r in records for e in r.get("first_tokens", ())
                if e["id"] == rid]
    assert len(carriers) == 1
    record, entry = carriers[0]
    assert record["kind"] in ("decode", "verify")
    assert attrs["fetch_seq"] == record["seq"]
    assert entry["prefill_seq"] == attrs["prefill_seq"]
    assert entry["chunks"] == attrs["chunks"]
    for stage in ENGINE_STAGES:
        assert entry[stage] == attrs[stage]
    # the first prefill dispatch named is a prefill record of this request
    # — or, where its prompt RODE a burst's first step, that burst's record,
    # whose own fetch brought the token (docs/tracing.md)
    prefill = next(r for r in records if r["seq"] == attrs["prefill_seq"])
    if prefill.get("admitted"):
        assert prefill is record and entry["chunks"] == 1
    else:
        assert prefill["kind"] == "prefill"
        assert prefill["seq"] < record["seq"]
    return attrs, entry, record, records


async def _complete(engine, prompt, rid, max_tokens=6):
    return await engine.complete(
        prompt, SamplingParams(temperature=0.0, max_tokens=max_tokens),
        request_id=rid)


# ------------------------------------------------------------------- the unit


class _Stamps:
    received_at = None
    submitted_at = 10.0
    taken_at = 10.25
    prefill_at = None
    activated_at = 11.0
    first_token_at = 11.5


def test_a_stage_is_the_difference_of_two_stamps_and_absent_without_both():
    assert WAY_IN == ("accept",) + ENGINE_STAGES
    # no handler (accept) and no prefill (place, prefill): a restored request
    assert way_in_stages(_Stamps) == {"inbox": 0.25, "first_fetch": 0.5}
    full = type("R", (), dict(vars(_Stamps), received_at=9.5, prefill_at=10.5))
    assert way_in_stages(full) == {"accept": 0.5, "inbox": 0.25,
                                   "place": 0.25, "prefill": 0.5,
                                   "first_fetch": 0.5}


def test_a_request_is_stamped_on_the_clock_of_the_spans(monkeypatch):
    monkeypatch.setattr(stepstats, "_now", lambda: 1234.5)
    r = Request(prompt_ids=[1], sampling=SamplingParams())
    assert r.submitted_at == 1234.5
    assert (r.received_at, r.taken_at, r.prefill_at, r.activated_at,
            r.first_token_at) == (None,) * 5
    assert r.deadline_expired() is False
    r.sampling.deadline_ms = 100
    assert r.deadline_expired() is False and r.deadline_expired(1234.7)


def test_the_taxonomy_knows_the_first_token_event():
    assert "first_token" in EVENTS


def test_the_first_frame_is_counted_once_a_stream(monkeypatch):
    now = iter([5.0, 5.5, 6.0, 7.0])
    monkeypatch.setattr(stepstats, "_now", lambda: next(now))
    stats, q = StreamStats(), EventQueue()
    q.put(("tokens", [1]))          # put at 5.0
    stats.frame(5.25, first_put=5.0)    # written at 5.5
    stats.frame(5.75)                   # a later frame, at 6.0
    snap = stats.snapshot()
    assert snap["frames_total"] == 2
    assert snap["first_frames_total"] == 1
    assert snap["first_frame_seconds_total"] == pytest.approx(0.5)
    assert snap["frame_seconds_total"] == pytest.approx(0.25 + 0.25)


# ------------------------------------------------------------ the paths (CPU)


async def test_a_one_shot_prompt_in_todays_order(engine):
    core = engine.core
    since = core.step_stats.seq
    await _complete(engine, [3, 1, 4, 1, 5], "one-shot")
    attrs, entry, record, records = _served(core, "one-shot", since)
    assert attrs["chunks"] == 1 and entry["cached_tokens"] == 0
    # an idle engine: nothing was in flight, so the burst did not leave
    # ahead, and the first token rode the first decode record
    assert record["dispatched_ahead"] is False
    assert record["seq"] == min(r["seq"] for r in records
                                if r["kind"] == "decode")
    assert "accept" not in attrs  # no HTTP handler in front of this request


async def test_a_chunked_prompt_holds_every_chunk(engine):
    core = engine.core
    since = core.step_stats.seq
    await _complete(engine, list(range(1, 41)), "chunked")
    attrs, entry, _record, records = _served(core, "chunked", since)
    assert attrs["chunks"] == 3  # 16 + 16 + 8
    chunks = [r for r in records if r["kind"] == "prefill"]
    assert len(chunks) == 3 and attrs["prefill_seq"] == chunks[0]["seq"]
    # the span runs from the first chunk's dispatch past the last one's end
    assert attrs["prefill"] >= chunks[-1]["t1_s"] - chunks[0]["t0_s"] - 1e-3


async def test_a_cache_hit_prefills_the_suffix_alone(engine):
    core = engine.core
    prompt = [7] * 32 + [9, 8, 7, 6]
    await _complete(engine, prompt, "donor")
    since = core.step_stats.seq
    await _complete(engine, prompt[:-2] + [5, 5], "hit")
    attrs, entry, _record, records = _served(core, "hit", since)
    assert entry["cached_tokens"] >= 16
    # `place` ends at the first DISPATCH (the suffix's chunk), not at the
    # zero-token `prefill_chunk` event of the hit
    chunks = [r for r in records if r["kind"] == "prefill"]
    assert attrs["chunks"] == len(chunks) >= 1
    assert attrs["prefill_seq"] == chunks[0]["seq"]


async def test_an_arrival_placed_ahead(engine, monkeypatch):
    """A request that comes while another decodes and a slot is free: its
    prefill leaves ahead of the fetched burst's emit, its first token rides
    the burst dispatched right behind, and `prefill` ends where the host
    learns the prefill done (with that burst in flight). (An engine with no
    mixed step, what a family whose record offers none reads: with one, a
    lone short arrival rides the burst, next test.)"""
    core = engine.core
    monkeypatch.setattr(core, "mixed_width", 0)
    since = core.step_stats.seq
    late = Request(prompt_ids=[8, 2, 3, 4, 5], request_id="ahead",
                   sampling=SamplingParams(temperature=0.0, max_tokens=8))
    prepare, pending = core._prepare_burst, [late]

    def prepare_and_submit(rows, k):
        if pending:
            core.submit(pending.pop())
        return prepare(rows, k)

    core._prepare_burst = prepare_and_submit
    try:
        await _complete(engine, [9, 2, 3, 4, 5], "in-front", max_tokens=56)
        assert len(collect(late)[0]) == 8
    finally:
        core._prepare_burst = prepare
    attrs, _entry, record, records = _served(core, "ahead", since)
    prefill = next(r for r in records if r["seq"] == attrs["prefill_seq"])
    assert prefill["dispatched_ahead"] is True
    # the burst behind the prefill brought the token, and it left ahead
    assert record["seq"] == prefill["seq"] + 1
    assert record["dispatched_ahead"] is True
    # activated where the prefill was known done: inside the burst behind
    assert late.activated_at >= record["t0_s"]
    assert late.first_token_at <= _records(core, record["seq"])[0]["t1_s"]
    _served(core, "in-front", since)


async def test_an_arrival_that_rides_a_burst(engine):
    """The same arrival where the mixed program of the burst's window
    stands: no prefill record, its six stamps telescope all the same, and
    the burst that admitted it is the one whose fetch brought its token."""
    core = engine.core
    core._mixed_ready.update(core._window_buckets)
    since = core.step_stats.seq
    late = Request(prompt_ids=[8, 2, 3, 4, 5], request_id="rides",
                   sampling=SamplingParams(temperature=0.0, max_tokens=8))
    prepare, pending = core._prepare_burst, [late]

    def prepare_and_submit(rows, k):
        if pending:
            core.submit(pending.pop())
        return prepare(rows, k)

    core._prepare_burst = prepare_and_submit
    try:
        await _complete(engine, [9, 2, 3, 4, 5], "in-front-of-it",
                        max_tokens=56)
        assert len(collect(late)[0]) == 8
    finally:
        core._prepare_burst = prepare
    attrs, _entry, record, records = _served(core, "rides", since)
    assert record["admitted"]["prompt_tokens"] == 5
    assert attrs["prefill_seq"] == attrs["fetch_seq"] == record["seq"]
    assert [r["tokens"] for r in records if r["kind"] == "prefill"] == [5]
    # `prefill` is that burst, dispatch to fetch; `first_fetch` the emit
    assert record["t0_s"] == pytest.approx(late.prefill_at, abs=5e-6)
    assert record["t0_s"] < late.activated_at <= late.first_token_at
    assert late.first_token_at <= _records(core, record["seq"])[0]["t1_s"]


async def test_first_tokens_are_absent_where_a_fetch_brought_none(engine):
    core = engine.core
    since = core.step_stats.seq
    before = core.metrics.summary()["way_in"]
    await asyncio.gather(*[
        _complete(engine, [2 + i, 7, 1], f"count-{i}", max_tokens=20)
        for i in range(3)])
    records = _records(core, since)
    carried = [e["id"] for r in records for e in r.get("first_tokens", ())]
    assert sorted(carried) == ["count-0", "count-1", "count-2"]
    assert any(r["kind"] == "decode" and "first_tokens" not in r
               for r in records)
    assert all("first_tokens" not in r for r in records
               if r["kind"] == "prefill")
    after = core.metrics.summary()["way_in"]
    assert after["requests_total"] == before["requests_total"] + 3
    for stage in ENGINE_STAGES:
        want = sum(e[stage] for r in records
                   for e in r.get("first_tokens", ()))
        assert (after["seconds_total"][stage]
                - before["seconds_total"][stage]) == pytest.approx(
                    want, abs=1e-4)


async def test_a_request_cancelled_before_its_first_token_leaves_nothing(
        engine):
    core = engine.core
    before = core.metrics.summary()["way_in"]["requests_total"]
    since = core.step_stats.seq
    request = Request(prompt_ids=[4, 4, 4], request_id="gone",
                      sampling=SamplingParams(max_tokens=8))
    request.cancel()
    core.submit(request)
    assert collect(request) == ([], "cancelled")
    await _complete(engine, [1, 2, 3], "after-gone")
    assert _events(core, "gone", "first_token") == []
    assert request.first_token_at is None
    carried = [e for r in _records(core, since)
               for e in r.get("first_tokens", ())]
    assert [e["id"] for e in carried] == ["after-gone"]
    assert all(v is not None for e in carried for v in e.values())
    assert (core.metrics.summary()["way_in"]["requests_total"]
            == before + 1)


def test_a_block_familys_row_has_every_stage():
    """A family that generates by diffusion over blocks samples no first
    token at activation: its first token comes with its first committed
    block, through the same stamps."""
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore

    core = EngineCore(get_preset("debug-sdar-tiny"), None, eos_id=-1,
                      num_slots=2, slot_capacity=64, prefill_buckets=(16,),
                      kv_page_size=16)
    core.start()
    try:
        request = Request(prompt_ids=list(range(1, 11)), request_id="blocks",
                          sampling=SamplingParams(temperature=0.0,
                                                  max_tokens=8))
        core.submit(request)
        assert len(collect(request)[0]) == 8
        _attrs, entry, record, _ = _served(core, "blocks", 0)
        assert set(ENGINE_STAGES) <= set(entry)
        assert all(v is not None for v in entry.values())
        assert record["block_passes"] > 0
    finally:
        core.stop()


def test_a_parked_and_resumed_request_keeps_the_stamps_it_has():
    """A preempted request prefills its committed tokens again on resume:
    its way in is the one of its first token, counted once."""
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore

    core = EngineCore(get_preset("debug-tiny"), None, eos_id=-1, num_slots=1,
                      slot_capacity=128, prefill_buckets=(16,),
                      kv_page_size=16, decode_burst=1)
    core.start()
    try:
        victim = Request(
            prompt_ids=[5, 6, 7, 8], request_id="victim",
            sampling=SamplingParams(temperature=0.0, max_tokens=40,
                                    priority=2))
        core.submit(victim)
        deadline = time.monotonic() + 60
        while core.slots[0].generated < 4 and time.monotonic() < deadline:
            time.sleep(0.002)
        stamps = (victim.prefill_at, victim.prefill_seq,
                  victim.prefill_chunks, victim.activated_at,
                  victim.first_token_at)
        other = Request(
            prompt_ids=[1, 2, 3], request_id="other",
            sampling=SamplingParams(temperature=0.0, max_tokens=4,
                                    priority=0))
        core.submit(other)
        assert len(collect(other)[0]) == 4
        assert len(collect(victim)[0]) == 40
        assert core.metrics.preemptions_total >= 1
        assert len(_events(core, "victim", "resumed")) >= 1
        assert len(_events(core, "victim", "first_token")) == 1
        assert stamps == (victim.prefill_at, victim.prefill_seq,
                          victim.prefill_chunks, victim.activated_at,
                          victim.first_token_at)
        assert core.metrics.summary()["way_in"]["requests_total"] == 2
    finally:
        core.stop()


# ------------------------------- the `prefill` stage, cut by what it waited for


class _Served:
    """One inline engine (tests.support.InlineLoop) under a made-up clock
    that advances a millisecond a read, so every stamp differs and every
    sum is exact: a row that decodes (`front`, a one-shot prompt on an idle
    engine), `arrivals` put on the inbox while its second burst is in
    flight, all served to their ends."""

    def __init__(self, arrivals, preset="debug-tiny", rides=True):
        from llmlb_tpu.engine.presets import get_preset
        from llmlb_tpu.engine.scheduler import EngineCore

        with pytest.MonkeyPatch.context() as patch:
            ticks = itertools.count(1)
            patch.setattr(stepstats, "_now", lambda: next(ticks) * 0.001)
            core = self.core = EngineCore(
                get_preset(preset), num_slots=4, slot_capacity=256,
                prefill_buckets=(16,), kv_page_size=16, seed=0,
                decode_burst=4, prefix_cache=False)
            loop = InlineLoop(core, rides=rides)
            self.front = Request(
                prompt_ids=[5, 6, 7], request_id="front",
                sampling=SamplingParams(temperature=0.0, max_tokens=120))
            self.arrivals = arrivals
            core.pending.put(self.front)
            loop.during[2] = [lambda: [core.pending.put(r) for r in arrivals]]
            self.before = core.metrics.summary()["way_in"]
            loop.run(iterations=2000)
            self.after = core.metrics.summary()["way_in"]
            self.records = loop.records()
        for r in (self.front, *arrivals):
            assert collect(r, None)[1] == "length"
        self.entries = {e["id"]: e for r in self.records
                        for e in r.get("first_tokens", ())}

    def inside(self, request, kind):
        """The records of `kind` that lie inside `request`'s `prefill`
        stage (records of one loop never overlap: inside or outside)."""
        return [r for r in self.records if r["kind"] == kind
                and r["t0_s"] >= request.prefill_at - 1e-9
                and r["t1_s"] <= request.activated_at + 1e-9]

    def steps_of(self, request):
        return [r for r in self.records if r["kind"] == "prefill"
                and request.request_id in r["request_ids"].values()]


def _long(name: str) -> Request:
    return Request(prompt_ids=[ord(name[0])] * 40, request_id=name,
                   sampling=SamplingParams(temperature=0.0, max_tokens=6))


def _short(name: str) -> Request:
    return Request(prompt_ids=[8, 2, 3, 4, 5], request_id=name,
                   sampling=SamplingParams(temperature=0.0, max_tokens=8))


@pytest.fixture(scope="module")
def rotation():
    """Three prompts of three chunks each (16 + 16 + 8), in rotation beside
    a decoding row."""
    return _Served([_long("a"), _long("b"), _long("c")])


@pytest.fixture(scope="module")
def placed_ahead():
    """A short arrival placed AHEAD of the fetched burst's emit (an engine
    with no mixed step)."""
    return _Served([_short("ahead")], rides=False)


@pytest.fixture(scope="module")
def rode():
    """The same arrival where its prompt rides the next burst."""
    return _Served([_short("rides")])


def test_the_cut_is_a_closed_set_of_four():
    assert PREFILL_CUT == ("own", "others", "decode", "loop")


@pytest.mark.parametrize("name", ["a", "b", "c"])
def test_a_chunked_prompts_cut_sums_to_its_prefill_stage(rotation, name):
    entry = rotation.entries[name]
    cut = entry["prefill_cut"]
    assert tuple(cut) == PREFILL_CUT and entry["chunks"] == 3
    assert all(v >= 0 for v in cut.values())
    assert sum(cut.values()) == pytest.approx(entry["prefill"], abs=1e-6)
    request = next(r for r in rotation.arrivals if r.request_id == name)
    assert request.prefill_cut.parts == cut
    # the flight recorder's event carries the same cut
    first = _events(rotation.core, name, "first_token")[0]["attrs"]
    assert first["prefill_cut"] == cut and first["chunks"] == 3


def test_others_is_the_rotation_and_decode_the_bursts_between(rotation):
    first = min(rotation.arrivals, key=lambda r: r.prefill_at)
    cut = rotation.entries[first.request_id]["prefill_cut"]
    mine = rotation.steps_of(first)
    theirs = [r for r in rotation.inside(first, "prefill") if r not in mine]
    # the other two prompts' chunks, between this one's first and last
    assert {i for r in theirs for i in r["request_ids"].values()} == {
        r.request_id for r in rotation.arrivals if r is not first}
    assert len(theirs) >= 4
    assert cut["others"] == pytest.approx(
        sum(r["wall_s"] for r in theirs), abs=1e-6)
    bursts = rotation.inside(first, "decode")
    assert len(bursts) >= 6  # one a loop iteration, behind each chunk
    assert cut["decode"] == pytest.approx(
        sum(r["wall_s"] for r in bursts), abs=1e-6)
    # its own chunks, the last one up to the stamp that ends the stage
    assert len(mine) == 3
    assert cut["own"] == pytest.approx(
        sum(r["wall_s"] for r in mine[:2])
        + first.activated_at - mine[2]["t0_s"], abs=1e-6)
    # what is left is the loop between the records
    gaps = sum(sum(r["since_prev"].values()) for r in rotation.records
               if first.prefill_at < r["t0_s"] <= first.activated_at)
    assert cut["loop"] == pytest.approx(gaps, abs=1e-6) and gaps > 0


def test_a_one_shot_prompts_cut_is_its_own_step(rotation):
    entry = rotation.entries["front"]
    assert entry["chunks"] == 1
    assert entry["prefill_cut"] == {"own": entry["prefill"], "others": 0.0,
                                    "decode": 0.0, "loop": 0.0}


def test_a_prompt_placed_ahead_ends_its_stage_inside_the_burst_behind(
        placed_ahead):
    request, = placed_ahead.arrivals
    entry = placed_ahead.entries["ahead"]
    prefill, = placed_ahead.steps_of(request)
    assert prefill["dispatched_ahead"] is True
    cut = entry["prefill_cut"]
    assert sum(cut.values()) == pytest.approx(entry["prefill"], abs=1e-6)
    # the whole prefill record is its own; the rest of the stage is the
    # burst behind it, in flight where the host learns the prefill done
    assert cut["own"] == pytest.approx(prefill["wall_s"], abs=1e-6)
    assert cut["decode"] == pytest.approx(
        request.activated_at - prefill["t1_s"], abs=1e-6)
    assert cut["decode"] > 0 and cut["others"] == 0 and cut["loop"] == 0


def test_a_prompt_that_rode_a_burst_has_no_cut(rode):
    request, = rode.arrivals
    entry = rode.entries["rides"]
    assert "prefill" in entry and "prefill_cut" not in entry
    assert request.prefill_cut is None
    assert "prefill_cut" not in _events(
        rode.core, "rides", "first_token")[0]["attrs"]
    # the one cut of the run is the row's in front, a one-shot prompt
    assert rode.after["prefill_cut_requests_total"] == 1
    assert rode.after["prefill_cut_chunks_total"] == 1
    assert rode.after["requests_total"] == 2


def test_a_restored_request_has_no_cut_and_moves_no_counter(rode):
    """Its KV came as bytes: `_insert_restored` stamps `activated_at` alone,
    so there is no `prefill` stage to cut."""
    core = rode.core
    restored = Request(prompt_ids=[1, 2, 3], request_id="restored",
                       sampling=SamplingParams(max_tokens=4))
    restored.taken_at = stepstats._now()
    before = core.metrics.summary()["way_in"]
    core._stamp_activated((restored,), stepstats._now())
    core._begin_delivery(type("Step", (), {"seq": 0}))
    core._first_token(restored, stepstats._now())
    entry, = core._first_tokens
    core._first_tokens = []
    assert "prefill" not in entry and "prefill_cut" not in entry
    after = core.metrics.summary()["way_in"]
    assert after["requests_total"] == before["requests_total"] + 1
    for key in ("prefill_cut_requests_total", "prefill_cut_chunks_total",
                "prefill_cut_seconds_total"):
        assert after[key] == before[key]


def test_the_cumulative_cut_is_the_sum_of_the_entries(rotation):
    before, after = rotation.before, rotation.after
    assert before["prefill_cut_requests_total"] == 0
    entries = rotation.entries.values()
    assert after["prefill_cut_requests_total"] == len(entries) == 4
    assert after["prefill_cut_chunks_total"] == 1 + 3 * 3
    for part in PREFILL_CUT:
        assert (after["prefill_cut_seconds_total"][part]
                - before["prefill_cut_seconds_total"][part]
                ) == pytest.approx(
                    sum(e["prefill_cut"][part] for e in entries), abs=1e-6)
    # ... and the four parts together are the stage's own counter
    assert sum(after["prefill_cut_seconds_total"].values()) == pytest.approx(
        after["seconds_total"]["prefill"], abs=1e-5)


def test_the_cut_is_served_on_metrics(rotation):
    text = rotation.core.metrics.render(queue_depth=0, active_slots=0,
                                        num_slots=4)
    assert "llmlb_engine_prefill_cut_requests_total 4" in text
    assert "llmlb_engine_prefill_cut_chunks_total 10" in text
    for part in PREFILL_CUT:
        assert f'llmlb_engine_prefill_cut_seconds_total{{part="{part}"}} ' \
            in text


@pytest.mark.parametrize("name", ["a", "b", "c"])
def test_a_chunks_record_says_which_chunk_it_is(rotation, name):
    request = next(r for r in rotation.arrivals if r.request_id == name)
    steps = rotation.steps_of(request)
    assert [r["chunk"] for r in steps] == [
        {"index": 0, "pos": 0, "of": 40}, {"index": 1, "pos": 16, "of": 40},
        {"index": 2, "pos": 32, "of": 40}]
    assert [r["tokens"] for r in steps] == [16, 16, 8]
    assert all(list(r["request_ids"].values()) == [name] for r in steps)
    # a one-shot group's record gains nothing
    front, = rotation.steps_of(rotation.front)
    assert "chunk" not in front


def _span_names(record) -> list[str]:
    return [name for name, _at, _dur in record["spans"]]


@pytest.fixture(scope="module")
def counted():
    """A family whose calls return step counters (a mixture's expert load):
    a one-shot prompt and a chunked one."""
    return _Served([_long("chunked")], preset="debug-mla-tiny")


@pytest.fixture(scope="module")
def counted_ahead():
    """The same family, a one-shot prompt placed ahead."""
    return _Served([_short("ahead")], preset="debug-mla-tiny", rides=False)


def test_reading_a_prefills_counters_is_a_span_of_its_own(counted):
    front, = counted.steps_of(counted.front)
    chunks = counted.steps_of(counted.arrivals[0])
    assert len(chunks) == 3
    for record in (front, *chunks):
        names = _span_names(record)
        # the last thing of the step, behind the activation where there is one
        assert names.count("counters") == 1 and names[-1] == "counters"
        assert record["experts_touched"] > 0
        # in the legacy phases it is `emit`, as the activation is
        host = sum(dur for name, _at, dur in record["spans"]
                   if name in ("emit", "activate", "counters"))
        assert record["phases_s"]["emit"] == pytest.approx(host, abs=2e-6)
        assert record["total_s"] == pytest.approx(
            record["wall_s"] + record["since_prev"]["admit_s"], abs=2e-6)
    assert _span_names(chunks[0]) == ["dispatch", "compute", "emit",
                                      "counters"]
    assert _span_names(chunks[2]) == ["dispatch", "compute", "emit",
                                      "activate", "counters"]


def test_a_prefill_recorded_behind_a_burst_reads_its_counters_unmarked(
        counted_ahead):
    """It left ahead and is recorded inside its successor, with the device
    busy: the reads hide there, and the record has the counters all the
    same."""
    ahead, = counted_ahead.steps_of(counted_ahead.arrivals[0])
    assert ahead["dispatched_ahead"] is True
    assert "counters" not in _span_names(ahead)
    assert ahead["experts_touched"] > 0


def test_a_family_without_counters_has_no_such_span(rotation):
    prefills = [r for r in rotation.records if r["kind"] == "prefill"]
    assert len(prefills) == 10
    assert all("counters" not in _span_names(r) for r in prefills)


# ------------------------------------------------- over HTTP, and in a capture


async def test_the_timeline_health_and_metrics_serve_the_way_in(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app

    client = TestClient(TestServer(create_engine_app(engine,
                                                     owns_engine=False)))
    await client.start_server()
    try:
        h0 = (await (await client.get("/api/health")).json())["metrics"]
        resp = await client.post(
            "/v1/chat/completions", headers={"X-Request-Id": "over-http"},
            json={"model": "debug-tiny", "stream": True, "max_tokens": 12,
                  "temperature": 0,
                  "messages": [{"role": "user", "content": "hello there"}]})
        assert resp.status == 200
        await resp.read()
        tl = await (await client.get(
            "/api/requests/over-http/timeline")).json()
        first = [e for e in tl["events"] if e["event"] == "first_token"]
        assert len(first) == 1
        attrs = first[0]["attrs"]
        finished = next(e for e in tl["events"] if e["event"] == "finished")
        assert sum(attrs[s] for s in ENGINE_STAGES) == pytest.approx(
            finished["attrs"]["ttft_s"], abs=1e-3)
        # the handler's entry is stamped: template, tokenisation, validation
        assert 0 < attrs["accept"] < 5
        names = [e["event"] for e in tl["events"]]
        assert names.index("prefill_chunk") < names.index("first_token") \
            < names.index("finished")
        h1 = (await (await client.get("/api/health")).json())["metrics"]
        assert (h1["way_in"]["requests_total"]
                == h0["way_in"]["requests_total"] + 1)
        assert set(h1["way_in"]["seconds_total"]) == set(WAY_IN)
        assert (h1["way_in"]["seconds_total"]["accept"]
                - h0["way_in"]["seconds_total"]["accept"]) == pytest.approx(
                    attrs["accept"], abs=1e-5)
        text = await (await client.get("/metrics")).text()
        assert "llmlb_engine_way_in_requests_total " in text
        for stage in WAY_IN:
            assert f'llmlb_engine_way_in_seconds_total{{stage="{stage}"}}' \
                in text
        assert "llmlb_engine_stream_first_frames_total " in text
        assert "llmlb_engine_stream_first_frame_seconds_total " in text
        assert (h1["stream"]["first_frames_total"]
                - h0["stream"]["first_frames_total"]) in (0, 1)
    finally:
        await client.close()


async def test_the_first_frame_of_a_word_stream_is_counted():
    """Behind the benchmark's tokenizer every token is a word, so the first
    content event is the first frame: one a stream, its lag put -> written."""
    from tests.support import word_engine

    eng = word_engine(4, num_slots=2, slot_capacity=64, prefill_buckets=(16,))
    try:
        stats = eng.core.metrics.stream
        for i in range(2):
            frames = 0
            async for delta in eng.stream(
                    [1 + i, 2, 3], SamplingParams(temperature=0.0,
                                                  max_tokens=9)):
                frames += bool(delta.text)
            assert frames >= 2
        snap = stats.snapshot()
        assert snap["first_frames_total"] == 2
        assert 0 < snap["first_frame_seconds_total"] < snap[
            "frame_seconds_total"] + snap["event_wait_seconds_total"]
    finally:
        eng.shutdown()


async def test_a_capture_shows_the_first_token_inside_a_step(engine, tmp_path):
    import jax

    from llmlb_tpu.engine.profiling import ProfileManager

    core = engine.core
    mgr = ProfileManager(trace_root=str(tmp_path))
    mgr.start(30)
    since = core.step_stats.seq
    await _complete(engine, [6, 5, 4], "captured")
    done = mgr.stop()
    found = glob.glob(os.path.join(done["trace_dir"], "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    profile = jax.profiler.ProfileData.from_file(found[0])
    steps, firsts = [], []
    for plane in profile.planes:
        if not (plane.name or "").startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name == "llmlb.step":
                    steps.append((event.start_ns,
                                  event.start_ns + event.duration_ns,
                                  int(dict(event.stats)["seq"])))
                elif event.name == "llmlb.first_token":
                    firsts.append((event.start_ns, dict(event.stats)))
    mine = [(t, s) for t, s in firsts if s["request_id"] == "captured"]
    assert len(mine) == 1
    at, stats = mine[0]
    attrs, _entry, record, _ = _served(core, "captured", since)
    assert int(stats["fetch_seq"]) == record["seq"] == attrs["fetch_seq"]
    inside = [seq for t0, t1, seq in steps if t0 <= at <= t1]
    # it lies in ONE step: the one that fetched it or, where the next burst
    # left ahead, that one (the emit runs under its `emit_inflight`) — which
    # is why the annotation names the fetch itself
    assert len(inside) == 1
    assert inside[0] in (record["seq"], record["seq"] + 1)


# ------------------------------------------------------------------- the cost


async def test_the_per_request_cost_is_inside_the_overhead_guarantee(engine):
    """What a first token costs beyond the stamps (six clock reads): the
    stages, the entry, the flight-recorder event, the counters. Once a
    REQUEST, and a request rides a prefill and a decode step at the least:
    against 1% of those two, as tests/engine/test_step_introspection.py
    holds the per-step recording to 1% of a step."""
    from llmlb_tpu.engine.flightrec import FlightRecorder
    from llmlb_tpu.engine.metrics import EngineMetrics

    await _complete(engine, [1, 2, 3], "warm", max_tokens=16)
    m = engine.core.metrics
    steps_s = (m.decode_step.total / m.decode_step.n
               + m.prefill_step.total / m.prefill_step.n)

    class Core:
        metrics, flightrec = EngineMetrics(), FlightRecorder(enabled=True)
        _first_tokens, _fetch_seq = [], 7
        _fr_emit = type(engine.core)._fr_emit
        _first_token = type(engine.core)._first_token

    core = Core()
    requests = []
    for i in range(1000):
        r = Request(prompt_ids=[1], sampling=SamplingParams(),
                    request_id=f"cost-{i}")
        r.taken_at = r.prefill_at = r.activated_at = stepstats._now()
        r.prefill_seq, r.prefill_chunks = 3, 1
        requests.append(r)
    t0 = time.perf_counter()
    for r in requests:
        core._first_token(r, stepstats._now())
    per_request = (time.perf_counter() - t0) / len(requests)
    assert len(core._first_tokens) == 1000
    assert core.metrics.way_in_requests_total == 1000
    assert per_request < 0.01 * steps_s, (
        f"{per_request * 1e6:.1f} us a request against "
        f"{steps_s * 1e3:.3f} ms of steps")
