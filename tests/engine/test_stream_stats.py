"""A token's way out of the engine, counted (engine/streamstats.py): the
stamped event queue with made-up stamps, the cumulative stream block and its
conservation on a served engine, a consumer that quits mid-stream, the timed
SSE write, and the < 1% guarantee of test_step_introspection.py with the
stream path's stamps added."""

import asyncio
import queue
import time

import pytest

from llmlb_tpu.engine import stepstats
from llmlb_tpu.engine.streamstats import EventQueue, StreamStats


def _stamps(monkeypatch, stamps):
    it = iter(stamps)
    monkeypatch.setattr(stepstats, "_now", lambda: next(it))


# --------------------------------------------------------- the stamped queue


@pytest.mark.parametrize("event", [("tokens", [7]), ("tokens", [7, 8, 9, 10]),
                                   ("done", "length"), ("error", "boom")])
def test_the_queue_hands_every_event_over_as_it_was_put(monkeypatch, event):
    _stamps(monkeypatch, [1.0, 2.0])
    q = EventQueue()
    q.put(event)
    assert q.qsize() == 1 and not q.empty()
    assert q.get() == event  # every consumer but the service's
    q.put_nowait(event)
    assert q.taker()() == (2.0, event)  # the service's: stamp beside it
    assert q.n_put == 2 and q.empty()
    with pytest.raises(queue.Empty):
        q.get_nowait()
    with pytest.raises(queue.Empty):
        q.get(timeout=0.01)
    with pytest.raises(queue.Empty):
        q.taker()(False)


def test_residence_and_backlog_with_made_up_stamps(monkeypatch):
    """Three puts at 10.0, 10.1, 10.2; the consumer resumes with them at
    10.5, 10.6 and 10.7: each waited 0.5 s, and 2, 1 and 0 lay behind."""
    _stamps(monkeypatch, [10.0, 10.1, 10.2, 10.5, 10.6, 10.7])
    stats, q = StreamStats(), EventQueue()
    stats.open(q)
    q.put(("tokens", [1]))
    q.put(("tokens", [2, 3, 4, 5]))
    q.put(("done", "stop"))
    take = q.taker()
    for tokens, behind, at in ((1, 2, 10.5), (4, 1, 10.6), (0, 0, 10.7)):
        stamp, (_kind, _value) = take()
        assert q.qsize() == behind
        assert stats.got(stamp, q, tokens) == at
    snap = stats.snapshot()
    assert snap["events_total"] == 3 and snap["tokens_total"] == 5
    assert snap["event_wait_seconds_total"] == pytest.approx(1.5)
    assert snap["event_backlog_max"] == 2
    assert (snap["events_put_total"], snap["events_queued"],
            snap["streams_live"]) == (3, 0, 1)


def test_the_block_sums_frames_waits_and_what_was_left_unread(monkeypatch):
    _stamps(monkeypatch, [
        2.5,   # frame(2.0): the event arrived at 2.0, its frame was out at 2.5
        4.5,   # write_waited(4.0): a write on a paused connection took 0.5
        5.0,   # one more put, never taken
    ])
    stats, q = StreamStats(), EventQueue()
    stats.open(q)
    stats.frame(2.0)
    stats.write_waited(4.0)
    snap = stats.snapshot()
    assert (snap["frames_total"], snap["frame_seconds_total"]) == (
        1, pytest.approx(0.5))
    assert (snap["write_waits_total"], snap["write_wait_seconds_total"]) == (
        1, pytest.approx(0.5))
    q.put(("done", "stop"))  # left unread by a consumer that quits
    stats.close(q)
    snap = stats.snapshot()
    assert (snap["events_put_total"], snap["events_unread_total"],
            snap["events_queued"], snap["streams_live"]) == (1, 1, 0, 0)


def test_a_stream_the_scheduler_did_not_finish_counts_no_durations():
    class Req:
        submitted_at = time.monotonic() - 2.0
        finished_at = None

    stats = StreamStats()
    stats.finished(Req)  # a stop hit: the scheduler has not said done yet
    assert stats.streams_finished_total == 0
    Req.finished_at = Req.submitted_at + 1.5
    stats.finished(Req)
    assert stats.streams_finished_total == 1
    assert stats.made_seconds_total == pytest.approx(1.5)
    assert stats.stream_seconds_total >= 2.0


# ------------------------------------------------------- on a served engine


@pytest.fixture(scope="module")
def engine():
    from llmlb_tpu.engine.service import Engine

    eng = Engine.from_preset("debug-tiny", num_slots=4, slot_capacity=64,
                             prefill_buckets=(16,))
    yield eng
    eng.shutdown()


def _sampling(max_tokens):
    from llmlb_tpu.engine.scheduler import SamplingParams

    return SamplingParams(temperature=0.0, max_tokens=max_tokens)


def _conserved(snap):
    """Events put = events taken + queued + left unread (no stream is in
    the hop once every consumer has returned)."""
    return (snap["events_put_total"] == snap["events_total"]
            + snap["events_queued"] + snap["events_unread_total"])


async def test_conservation_over_whole_streams(engine):
    stats = engine.core.metrics.stream
    before, made0 = stats.snapshot(), engine.core.stats().total_tokens

    async def one(i):
        n = 0
        async for delta in engine.stream([1 + i, 2, 3, 4], _sampling(12)):
            n += len(delta.token_ids)
        return n

    got = await asyncio.gather(*(one(i) for i in range(6)))
    assert got == [12] * 6
    snap = stats.snapshot()
    d = {k: snap[k] - before[k] for k in snap
         if k.endswith("_total")}
    # every stream: a content event a fetch (this engine's step makes one
    # token a row, and the first fetch brings the activation's token with
    # it: 2, then 10 of 1) and the done — events are counted, not tokens
    assert d["events_total"] == d["events_put_total"] == 6 * 12
    assert d["events_unread_total"] == 0 and _conserved(snap)
    assert snap["events_queued"] == 0 and snap["streams_live"] == 0
    # the tokens the consumers took are the tokens the scheduler made
    assert d["tokens_total"] == 6 * 12
    assert d["tokens_total"] == engine.core.stats().total_tokens - made0
    assert d["streams_finished_total"] == 6
    assert d["stream_seconds_total"] - d["made_seconds_total"] >= 0
    assert 0 < d["frames_total"] <= d["events_total"]
    assert d["frame_seconds_total"] > 0 and d["event_wait_seconds_total"] > 0


@pytest.fixture(scope="module")
def burst_engine():
    """A dense engine whose fetch brings a row 8 tokens and whose every
    token is text."""
    from tests.support import word_engine

    eng = word_engine(8)
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("max_tokens, sizes", [
    (33, [9, 8, 8, 8]),  # the activation's token rides the first burst
    (20, [9, 8, 3]),     # ended inside a burst: what came before the end
    (1, [1]),
])
async def test_a_dense_burst_is_one_event_and_one_frame_a_row(
        burst_engine, max_tokens, sizes):
    """tokens_total / frames_total of a dense stream reads the burst, not
    1.0, and events_put_total counts events, not tokens."""
    stats = burst_engine.core.metrics.stream
    before = stats.snapshot()

    async def one(i):
        got = []
        async for delta in burst_engine.stream([1 + i, 2, 3, 4],
                                               _sampling(max_tokens)):
            if delta.token_ids:
                got.append(len(delta.token_ids))
        return got

    assert await asyncio.gather(*(one(i) for i in range(4))) == [sizes] * 4
    snap = stats.snapshot()
    d = {k: snap[k] - before[k] for k in snap if k.endswith("_total")}
    assert d["tokens_total"] == 4 * max_tokens
    assert d["frames_total"] == 4 * len(sizes)
    assert d["events_put_total"] == d["events_total"] == 4 * (len(sizes) + 1)
    assert _conserved(snap) and d["events_unread_total"] == 0


@pytest.mark.parametrize("stop", [None, ["never-in-the-text"]],
                         ids=["no-stop", "a-stop-string"])
async def test_an_event_is_decoded_once_not_once_a_token(burst_engine, stop,
                                                         monkeypatch):
    """A decode walks the whole answer: with no stop string to look for
    between two tokens the service decodes ONCE an event (what one fetch
    brought the row: 9, 8, 8, 8 tokens), and the text is what a decode a
    token gives — which a stop string still gets."""
    tokenizer = burst_engine.tokenizer
    decodes = []
    decode = tokenizer.decode
    monkeypatch.setattr(tokenizer, "decode",
                        lambda ids: decodes.append(len(ids)) or decode(ids))
    texts, ids = [], []
    async for delta in burst_engine.stream([5, 2, 3, 4], _sampling(33),
                                           stop=stop):
        texts.append(delta.text)
        ids += delta.token_ids
    assert len(ids) == 33 and "".join(texts) == decode(ids)
    if stop is None:
        # four content events and the flush at `done`
        assert decodes == [9, 17, 25, 33, 33]
    else:
        assert decodes == list(range(1, 34)) + [33]


@pytest.mark.parametrize("ids, sizes", [
    (list(b"plain ascii"), [3, 8]),
    # a two-byte and a three-byte character split across events: the head
    # of a sequence is held back until its tail comes, in either way
    (list("aé€b".encode()), [2, 1, 2, 1, 1]),
    (list("aé€b".encode()), [7]),
    (list("é".encode())[:1], [1]),  # never completed: flush's to emit
])
def test_extend_is_the_pushes_joined(ids, sizes):
    from llmlb_tpu.engine.tokenizer import (ByteTokenizer,
                                            IncrementalDetokenizer)

    tokenizer = ByteTokenizer(512)
    one, many = (IncrementalDetokenizer(tokenizer) for _ in range(2))
    at = 0
    for size in sizes:
        event = ids[at:at + size]
        at += size
        assert many.extend(event) == "".join(one.push(t) for t in event)
    assert at == len(ids)
    assert many.flush() == one.flush()


async def test_a_consumer_that_quits_mid_stream(engine):
    stats = engine.core.metrics.stream
    before = stats.snapshot()
    agen = engine.stream([9, 8, 7, 6], _sampling(48))
    seen = 0
    async for delta in agen:
        seen += len(delta.token_ids)
        if seen >= 3:
            break
    await agen.aclose()  # the client went away: the finally cancels
    snap = stats.snapshot()
    assert snap["streams_live"] == 0 and snap["events_queued"] == 0
    assert _conserved(snap)
    # the three tokens came in two events (the first fetch brought two)
    assert snap["events_total"] - before["events_total"] >= 2
    # a stream that did not run to its end counts under no duration
    assert snap["streams_finished_total"] == before["streams_finished_total"]
    # and the engine still serves, conserving
    out = await engine.complete([1, 2, 3], _sampling(4))
    assert out.completion_tokens == 4 and _conserved(stats.snapshot())


async def test_the_block_is_served_and_a_paused_write_is_timed(engine):
    """/api/health .metrics.stream and the llmlb_engine_stream_* lines; the
    handlers' frames go through _sse_send, which on_response_prepare hands
    the block and the connection's protocol: a write is timed only where
    the connection is paused."""
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine import server

    stats = engine.core.metrics.stream
    client = TestClient(TestServer(server.create_engine_app(
        engine, owns_engine=False)))
    await client.start_server()
    try:
        frames0 = stats.frames_total
        resp = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny", "stream": True, "max_tokens": 8,
            "temperature": 0, "messages": [{"role": "user", "content": "hi"}]})
        assert resp.status == 200
        body = await resp.text()
        assert body.rstrip().endswith("data: [DONE]")
        assert stats.frames_total >= frames0  # ids over 255 carry no text
        assert stats.write_waits_total == 0  # no reader was behind
        health = await (await client.get("/api/health")).json()
        block = health["metrics"]["stream"]
        assert block["events_total"] == stats.events_total
        assert set(block) >= {
            "events_total", "tokens_total", "event_wait_seconds_total",
            "event_backlog_max", "frames_total", "frame_seconds_total",
            "write_wait_seconds_total", "write_waits_total",
            "streams_finished_total", "stream_seconds_total",
            "made_seconds_total"}
        text = await (await client.get("/metrics")).text()
        for key, value in block.items():
            kind = "counter" if key.endswith("_total") else "gauge"
            assert f"# TYPE llmlb_engine_stream_{key} {kind}" in text
        assert f"llmlb_engine_stream_tokens_total {block['tokens_total']}" \
            in text
    finally:
        await client.close()

    class Paused:
        writing_paused = True

    class Resp(dict):
        written = b""

        async def write(self, data):
            self.written += data

    resp = Resp({server._WAY_OUT: (stats, Paused)})
    await server._sse_send(resp, {"a": 1})
    assert resp.written == b'data: {"a":1}\n\n'
    assert stats.write_waits_total == 1
    assert stats.write_wait_seconds_total >= 0
    bare = Resp()  # a response no engine app prepared: written, not timed
    await server._sse_send(bare, "x")
    assert bare.written == b"data: x\n\n" and stats.write_waits_total == 1
    stats.write_waits_total = 0  # leave the module's engine as it was


async def test_stream_stamps_stay_under_one_percent_of_a_step(engine):
    """The guarantee of test_step_introspection.py with this PR's stamps
    added: what a step's tokens pay on their way out (the stamped put, the
    unwrapped take, got(), frame() and the inactive frame annotation) plus
    the step's four CPU-clock reads, against the
    CPU debug engine's mean decode step — a real TPU step is orders of
    magnitude longer."""
    from llmlb_tpu.engine.streamstats import frame_annotation

    await engine.complete([1, 2, 3, 4, 5], _sampling(16))
    hist = engine.core.metrics.decode_step
    mean_step_s = hist.total / hist.n
    rows = 2  # tokens a step of this engine's test traffic emits

    def path(q, stats, plain):
        take = q.get if plain else q.taker()
        for _ in range(rows):
            q.put(("tokens", [1]))
            if plain:
                take()
                continue
            stamp, _event = take()
            with frame_annotation():
                stats.frame(stats.got(stamp, q, 1))

    n = 3000
    stats = StreamStats()

    def timed(q, plain):
        t0 = time.perf_counter()
        for _ in range(n):
            path(q, stats, plain)
        return (time.perf_counter() - t0) / n

    added = min(timed(EventQueue(), False) for _ in range(3)) - min(
        timed(queue.SimpleQueue(), True) for _ in range(3))
    # the step's four thread_time reads, measured by themselves
    t0 = time.perf_counter()
    for _ in range(n):
        for _ in range(4):
            stepstats._cpu()
    added += (time.perf_counter() - t0) / n
    assert added < 0.01 * mean_step_s, (
        f"the way out's stamps cost {added * 1e6:.2f} us a step of {rows} "
        f"tokens vs a mean step of {mean_step_s * 1e3:.3f} ms")


def test_the_frame_annotation_exists_only_inside_a_capture(tmp_path):
    """Outside a capture a frame builds no object at all; inside one the
    interval is a TraceAnnotation of the name the capture is read by."""
    import jax
    from jax.profiler import TraceAnnotation

    from llmlb_tpu.engine import streamstats

    assert streamstats.frame_annotation() is streamstats._NO_FRAME
    jax.profiler.start_trace(str(tmp_path))
    try:
        inside = streamstats.frame_annotation()
        assert isinstance(inside, TraceAnnotation)
        with inside:
            pass
    finally:
        jax.profiler.stop_trace()
    assert streamstats.frame_annotation() is streamstats._NO_FRAME
