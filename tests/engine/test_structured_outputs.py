"""Engine-level structured outputs: constrained decoding through the real
scheduler + service on CPU JAX, seed reproducibility, violation accounting,
and the service.stream stop-sequence holdback edge at detok.flush()."""

import asyncio
import json
import queue

import jsonschema
import pytest

from llmlb_tpu.engine.scheduler import SamplingParams
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.engine.tokenizer import ByteTokenizer

SCHEMA = {
    "type": "object",
    "properties": {
        "ok": {"type": "boolean"},
        "tag": {"enum": ["alpha", "beta"]},
    },
    "required": ["ok", "tag"],
}


@pytest.fixture(scope="module")
def engine():
    eng = Engine.from_preset(
        "debug-tiny", model_id="tpu-structured", num_slots=4,
        slot_capacity=128, prefill_buckets=(16, 32, 64),
    )
    yield eng
    eng.shutdown()


def _chat_ids(engine, text="produce json"):
    return engine.encode_chat([{"role": "user", "content": text}])


def test_constrained_greedy_emits_schema_valid_json(engine):
    async def run():
        result = await engine.complete(
            _chat_ids(engine),
            SamplingParams(temperature=0.0, max_tokens=64,
                           constraint={"type": "json_schema",
                                       "schema": SCHEMA}),
        )
        assert result.finish_reason == "stop"
        jsonschema.validate(json.loads(result.text), SCHEMA)
    asyncio.run(run())


def test_constrained_stochastic_and_concurrent_mixed_batch(engine):
    """Constrained + free-form requests share the batch; every constrained
    stream must still be schema-valid with finish 'stop'."""
    async def run():
        constrained = [
            engine.complete(
                _chat_ids(engine, f"req {i}"),
                SamplingParams(temperature=1.0, max_tokens=64,
                               constraint={"type": "json_schema",
                                           "schema": SCHEMA}),
            )
            for i in range(3)
        ]
        free = [
            engine.complete(_chat_ids(engine, f"free {i}"),
                            SamplingParams(temperature=1.0, max_tokens=8))
            for i in range(3)
        ]
        results = await asyncio.gather(*constrained, *free)
        for r in results[:3]:
            assert r.finish_reason == "stop"
            jsonschema.validate(json.loads(r.text), SCHEMA)
        for r in results[3:]:
            assert r.finish_reason in ("stop", "length")
    asyncio.run(run())
    assert engine.core.metrics.structured_requests_total >= 3
    assert engine.core.metrics.masked_decode_steps_total > 0


def test_json_object_mode(engine):
    async def run():
        result = await engine.complete(
            _chat_ids(engine),
            SamplingParams(temperature=0.8, max_tokens=96,
                           constraint={"type": "json_object"}),
        )
        if result.finish_reason == "stop":
            assert isinstance(json.loads(result.text), dict)
        else:  # free-form object mode may hit max_tokens mid-string
            assert result.finish_reason == "length"
    asyncio.run(run())


def test_max_tokens_cut_counts_violation(engine):
    before = engine.core.metrics.constraint_violations_total

    async def run():
        result = await engine.complete(
            _chat_ids(engine),
            SamplingParams(temperature=0.9, max_tokens=2,
                           constraint={"type": "json_schema",
                                       "schema": SCHEMA}),
        )
        assert result.finish_reason == "length"
    asyncio.run(run())
    assert engine.core.metrics.constraint_violations_total > before


def test_invalid_constraint_rejected_before_submit(engine):
    async def run():
        with pytest.raises(ValueError) as exc:
            await engine.complete(
                _chat_ids(engine),
                SamplingParams(constraint={"type": "json_schema",
                                           "schema": {"allOf": []}}),
            )
        assert "allOf" in str(exc.value)
    asyncio.run(run())


def test_seed_reproducible_across_batches(engine):
    async def run():
        ids = _chat_ids(engine, "seeded run")
        params = SamplingParams(temperature=0.9, max_tokens=8, seed=1234)
        a = await engine.complete(ids, params)
        # same seed inside a busy batch must reproduce token for token
        noise = [
            engine.complete(_chat_ids(engine, f"noise {i}"),
                            SamplingParams(temperature=1.0, max_tokens=8))
            for i in range(3)
        ]
        b, *_ = await asyncio.gather(engine.complete(ids, params), *noise)
        c = await engine.complete(
            ids, SamplingParams(temperature=0.9, max_tokens=8, seed=77)
        )
        assert a.text == b.text
        assert a.text != c.text or a.text == ""  # different seed, new stream
    asyncio.run(run())


def test_constrained_compile_cache_reused(engine):
    info_before = engine.core.structured_info()

    async def run():
        for _ in range(2):
            await engine.complete(
                _chat_ids(engine),
                SamplingParams(temperature=0.0, max_tokens=64,
                               constraint={"type": "json_schema",
                                           "schema": SCHEMA}),
            )
    asyncio.run(run())
    info = engine.core.structured_info()
    assert info["compile_cache_hits"] > info_before["compile_cache_hits"]
    assert info["mask_cache_bytes"] > 0


# ------------------------------------------------- stop-holdback flush edge


class _ScriptedCore:
    """Stands in for EngineCore: plays a fixed token script into the request
    event queue so service.stream's holdback logic is tested byte-exactly."""

    num_slots = 2
    metrics = None
    constraint_compiler = None

    class cfg:
        vocab_size = 512

    def __init__(self, tokens):
        self._tokens = tokens

    def stop(self):
        pass

    def submit(self, request):
        for t in self._tokens:
            request.events.put(("tokens", [t]))
        request.events.put(("done", "stop"))
        return request


def _scripted_engine(tokens):
    return Engine("scripted", _ScriptedCore(tokens), ByteTokenizer(512))


def test_stop_completing_only_in_final_flush_truncates(monkeypatch):
    """A stop string whose last character only materializes in
    detok.flush() (a held-back split-UTF-8 byte decoding to U+FFFD) must
    still truncate, and nothing past the hit may ever be emitted."""
    # tokens: "ab" then "X" then a lone UTF-8 continuation head (0xC3).
    # push(0xC3) emits nothing (trailing U+FFFD held back); flush() emits
    # the replacement char, completing the stop "X�" only at flush.
    eng = _scripted_engine([ord("a"), ord("b"), ord("X"), 0xC3])

    async def run():
        deltas = []
        final = None
        async for delta in eng.stream([1], SamplingParams(max_tokens=8),
                                      stop=["X�"]):
            deltas.append(delta.text)
            if delta.finish_reason is not None:
                final = delta
        assert final is not None and final.finish_reason == "stop"
        text = "".join(deltas)
        assert text == "ab", repr(text)
        # holdback: no intermediate delta may have leaked the stop head "X"
        assert all("X" not in d for d in deltas), deltas
    asyncio.run(run())
    eng.shutdown()


def test_stop_at_position_zero_in_flush_emits_nothing():
    eng = _scripted_engine([ord("X"), 0xC3])

    async def run():
        collected = ""
        final = None
        async for delta in eng.stream([1], SamplingParams(max_tokens=8),
                                      stop=["X�"]):
            collected += delta.text
            if delta.finish_reason is not None:
                final = delta
        assert final is not None and final.finish_reason == "stop"
        assert collected == ""
    asyncio.run(run())
    eng.shutdown()


def test_stop_straddling_tokens_still_truncates_mid_stream():
    # control case: the classic straddle (no flush involvement) still works
    eng = _scripted_engine([ord("h"), ord("i"), ord("S"), ord("T"),
                            ord("z"), ord("z")])

    async def run():
        collected = ""
        final = None
        async for delta in eng.stream([1], SamplingParams(max_tokens=16),
                                      stop=["ST"]):
            collected += delta.text
            if delta.finish_reason is not None:
                final = delta
        assert final.finish_reason == "stop"
        assert collected == "hi"
    asyncio.run(run())
    eng.shutdown()
