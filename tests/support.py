"""Test support: in-process gateway + mock upstream endpoints.

Port of the reference's test harness pattern (tests/support/lb.rs:16-110 test
AppState builder, support/ollama.rs + node.rs mock endpoints, support/http.rs
ephemeral-port spawner): register N mock endpoint URLs and exercise selection /
health / failover / streaming entirely in-process, no TPUs required.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import queue

from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from llmlb_tpu.gateway.app import create_app
from llmlb_tpu.gateway.app_state import build_app_state
from llmlb_tpu.gateway.config import ServerConfig
from llmlb_tpu.gateway.db import Database
from llmlb_tpu.gateway.registry import EndpointRegistry  # noqa: F401
from llmlb_tpu.gateway.types import (
    Capability,
    Endpoint,
    EndpointModel,
    EndpointStatus,
    EndpointType,
)

TEST_JWT_SECRET = "test-jwt-secret"
ADMIN_PASSWORD = "adminpass1"


# ------------------------------------------------- a request's events
#
# The one reader of `Request.events` for the engine's tests: a content event
# is ("tokens", [ids]) and carries what one fetch brought the request
# (scheduler.event_tokens), so a test that counts tokens counts ids, never
# events.

def collect_events(request, timeout: float | None = 120.0):
    """Read a request's events to its `done`: (tokens, finish reason,
    tokens per content event). `timeout=None` takes only what is queued,
    and the finish reason is None where the request has not ended. An
    `error` event raises."""
    from llmlb_tpu.engine.scheduler import event_tokens

    tokens: list[int] = []
    sizes: list[int] = []
    while True:
        try:
            kind, value = (request.events.get_nowait() if timeout is None
                           else request.events.get(timeout=timeout))
        except queue.Empty:
            if timeout is None:
                return tokens, None, sizes
            raise
        if kind == "done":
            return tokens, value, sizes
        assert kind == "tokens", f"engine {kind}: {value}"
        ids = event_tokens(kind, value)
        tokens.extend(ids)
        sizes.append(len(ids))


def collect(request, timeout: float | None = 120.0):
    """(tokens, finish reason) of `collect_events`."""
    tokens, finish, _ = collect_events(request, timeout)
    return tokens, finish


def take_tokens(request, n: int, timeout: float = 120.0) -> list[int]:
    """The content events of a request that is still generating, until
    they carry `n` tokens or more: all of their tokens."""
    from llmlb_tpu.engine.scheduler import event_tokens

    tokens: list[int] = []
    while len(tokens) < n:
        kind, value = request.events.get(timeout=timeout)
        assert kind == "tokens", (kind, value)
        tokens.extend(event_tokens(kind, value))
    return tokens


def word_engine(decode_burst: int, **core_kwargs):
    """A started debug-tiny engine that fetches `decode_burst` tokens a row
    at a time, behind the benchmark's tokenizer, whose every id is a word:
    `Engine.stream` writes a frame only where the text grew, and
    ByteTokenizer decodes most sampled ids to nothing."""
    from benchmark.tokenizer import WordTokenizer
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore
    from llmlb_tpu.engine.service import Engine

    cfg = get_preset("debug-tiny")
    tokenizer = WordTokenizer(cfg.vocab_size)
    core_kwargs = {"num_slots": 4, "slot_capacity": 128,
                   "prefill_buckets": (16, 32), "seed": 0, **core_kwargs}
    core = EngineCore(cfg, decode_burst=decode_burst,
                      eos_id=tokenizer.eos_id, **core_kwargs)
    core.start()
    return Engine("debug-tiny", core, tokenizer)


# InlineLoop(queued_run=...): no bound on a run of queued bursts
RUN_LIFTED = 1 << 30


class InlineLoop:
    """An engine core's step loop on the test's thread, so that the steps
    and their order are the test's: `run` calls the loop's own iteration
    (`EngineCore._loop_once`) until one does no work, with `_running` set so
    that the loop's own state decides the order of a decode cycle, as in a
    started engine (docs/scheduling.md "The five orders of a decode cycle").
    `during[n]` is a list of calls made while the n-th burst is in
    flight — `_prepare_burst`, which every burst calls between its
    dispatch and the wait for it. `todays_order` patches the predicate to
    "not now": the parent's cycle, step for step. `admission_ahead=False`
    patches the other predicate to "no arrival can be placed ahead": a burst
    may leave before its predecessor's emit (PR 39's order), an arrival is
    served behind that emit. `queued_behind=False` patches the third to "no
    burst leaves before its predecessor's fetch": PR 49's orders.
    `queued_run` sets the core's `QUEUED_RUN`, the most bursts in a row that
    may queue (None: the engine's own; the bound is there for the trace
    reader, not for the order, so the order's tests lift it). `rides=False`
    leaves the engine no mixed step (`mixed_width` 0, what a family whose
    record does not offer one reads): an arrival placed ahead has its
    prefill and its activation dispatched, PR 49's order, where it would
    ride the next burst's first step."""

    def __init__(self, core, *, todays_order: bool = False,
                 admission_ahead: bool = True, queued_behind: bool = True,
                 queued_run: int | None = None, rides: bool = True):
        self.core = core
        self.bursts = 0
        self.during: dict[int, list] = {}
        core._running = True
        prepare = core._prepare_burst

        def prepare_and_tell(rows, k):
            self.bursts += 1
            assert core._in_flight is not None
            for act in self.during.pop(self.bursts, ()):
                act()
            return prepare(rows, k)

        core._prepare_burst = prepare_and_tell
        if todays_order:
            core._ahead_blocker = lambda plan: "control"
        if not admission_ahead:
            core._arrivals_ahead = lambda plan, k: None
        if not queued_behind:
            core._queues_behind = lambda plan: False
        if queued_run is not None:
            core.QUEUED_RUN = queued_run
        if not rides:
            core.mixed_width = 0
        # as a started engine's prewarm thread leaves them: the mixed
        # program of every window stands (here it is built at its first use)
        core._mixed_ready.update(core._window_buckets)

    def run(self, iterations: int = 400) -> None:
        core = self.core
        clock = core._clock()
        for _ in range(iterations):
            did_work = core._loop_once(clock)
            assert core._in_flight is None  # no burst outlives an iteration
            if not did_work:
                return
        raise AssertionError("the requests did not finish")

    def records(self, kind: str | None = None) -> list[dict]:
        """The step records, oldest first; of one kind if given."""
        records = self.core.step_stats.snapshot(limit=512)["records"][::-1]
        return [r for r in records if kind in (None, r["kind"])]

    def decode_records(self) -> list[dict]:
        return self.records("decode")


# ------------------------------------------------- family-level KV fixtures


def identity_kv_pages(family, cfg, batch: int, capacity: int,
                      page_size: int = 8, **kw):
    """A fresh page pool and the identity block table over it, for tests
    that call a family's paged entry points without an engine: row b owns
    pages 1 + b*ppn .. (b+1)*ppn in order (page 0 is the trash page), so
    logical position p of row b is cell [1 + b*ppn + p // page_size,
    p % page_size]. Returns (cache_k, cache_v, tables [batch, ppn])."""
    import jax.numpy as jnp

    ppn = -(-capacity // page_size)
    ck, cv = family.init_kv_pages(cfg, batch * ppn + 1, page_size, **kw)
    tables = jnp.arange(1, batch * ppn + 1, dtype=jnp.int32)
    return ck, cv, tables.reshape(batch, ppn)


@contextlib.contextmanager
def assert_hit_is_zero_copy(core, suffix_tokens: int):
    """Around ONE request that hits the prefix cache of a started
    EngineCore whose cold path already ran every program the hit's suffix
    needs (same chunk and decode shapes): the hit builds NO program on the
    loop threads — a copy of the shared head would be a new one — and
    dispatches exactly one prefill, the `suffix_tokens`-token suffix chunk;
    every other step it records is a decode."""
    from llmlb_tpu.engine import compilelog

    built = compilelog.counters()
    prefills = sum(core.prefill_dispatch_by_loop.values())
    seq = core.step_stats.snapshot(limit=1)["records"][0]["seq"]
    yield
    loop = compilelog.summary(built)["by_thread"]["loop"]
    assert loop["programs_total"] == 0, (
        "a prefix hit built a program: "
        f"{[b['fun_name'] for b in compilelog.recent(since=built)]}"
    )
    assert sum(core.prefill_dispatch_by_loop.values()) - prefills == 1
    steps = [r for r in core.step_stats.snapshot(limit=256)["records"]
             if r["seq"] > seq]
    assert [r["tokens"] for r in steps if r["kind"] == "prefill"] == [
        suffix_tokens]
    assert {r["kind"] for r in steps} == {"prefill", "decode"}


def kv_rows(pool, tables, n: int):
    """The first `n` logical KV rows of every table row, gathered out of a
    bf16/f32 pool [L, P, PS, K, D] -> [L, B, n, K, D]."""
    import jax

    from llmlb_tpu.ops.attention import gather_kv_pages

    return jax.vmap(lambda layer: gather_kv_pages(layer, tables))(pool)[:, :, :n]


# --------------------------------------------------- SSE protocol invariants


def parse_sse_frames(body: bytes) -> list[dict]:
    """Split a raw SSE body into frames: [{"event": str|None, "data": [raw
    data strings]}]. Frames are terminated by a blank line; a trailing
    partial frame (no terminator — a cut stream) is included as-is."""
    frames: list[dict] = []
    for block in body.split(b"\n\n"):
        if not block.strip():
            continue
        frame = {"event": None, "data": []}
        for line in block.split(b"\n"):
            line = line.strip()
            if line.startswith(b"event:"):
                frame["event"] = line[len(b"event:"):].strip().decode()
            elif line.startswith(b"data:"):
                frame["data"].append(line[len(b"data:"):].strip().decode())
        if frame["event"] is not None or frame["data"]:
            frames.append(frame)
    return frames


def assert_sse_protocol(body: bytes, dialect: str = "openai",
                        allow_error: bool = False) -> None:
    """Protocol-invariant checker for gateway SSE streams (applied to every
    gateway stream test, not just the resume tests):

    - exactly one role delta (OpenAI) / exactly one message_start
      (Anthropic) — a spliced resume must never re-open the message;
    - monotone indices (OpenAI choice index non-decreasing; Anthropic
      content_block indices strictly increasing, deltas only to the open
      block);
    - exactly one terminal frame (``[DONE]`` / ``message_stop``) and no
      frames after it; with ``allow_error`` an ``event: error`` frame may
      terminate instead (optionally followed by one ``[DONE]``);
    - no gateway-internal ``llmlb.replay`` frames leak to the client.
    """
    frames = parse_sse_frames(body)
    assert frames, "stream produced no SSE frames"
    if dialect == "openai":
        _assert_openai_stream(frames, allow_error)
    elif dialect == "anthropic":
        _assert_anthropic_stream(frames, allow_error)
    else:  # pragma: no cover - test-author error
        raise ValueError(f"unknown dialect {dialect!r}")


def _assert_openai_stream(frames: list[dict], allow_error: bool) -> None:
    done_seen = 0
    error_seen = 0
    role_deltas = 0
    last_choice_index = -1
    terminal_at: int | None = None
    for i, frame in enumerate(frames):
        if terminal_at is not None and frame["data"] != []:
            raise AssertionError(
                f"frame after terminal [DONE]: {frame!r}"
            )
        if frame["event"] == "error":
            error_seen += 1
            assert allow_error, f"unexpected error frame: {frame!r}"
            continue
        for raw in frame["data"]:
            if raw == "[DONE]":
                done_seen += 1
                terminal_at = i
                continue
            try:
                obj = json.loads(raw)
            except ValueError:
                if allow_error:
                    # an interrupted byte-passthrough stream may end with a
                    # truncated partial frame before the error frame — the
                    # one shape a cut legitimately produces
                    continue
                raise AssertionError(f"unparseable data frame: {raw!r}")
            if not isinstance(obj, dict):
                continue
            assert obj.get("object") != "llmlb.replay", (
                "gateway-internal llmlb.replay frame leaked to the client"
            )
            if "error" in obj and "choices" not in obj:
                error_seen += 1
                assert allow_error, f"unexpected error payload: {raw!r}"
                continue
            for choice in obj.get("choices") or []:
                idx = choice.get("index", 0)
                assert idx >= last_choice_index, (
                    f"choice index went backwards: {idx} after "
                    f"{last_choice_index}"
                )
                last_choice_index = max(last_choice_index, idx)
                delta = choice.get("delta") or {}
                if delta.get("role"):
                    role_deltas += 1
    assert done_seen <= 1, f"{done_seen} [DONE] frames (expected exactly 1)"
    if error_seen == 0:
        assert done_seen == 1, "completed stream must end with one [DONE]"
    assert role_deltas <= 1, (
        f"{role_deltas} role deltas (a resumed stream must not re-open "
        "the message)"
    )


def _assert_anthropic_stream(frames: list[dict], allow_error: bool) -> None:
    starts = 0
    stops = 0
    open_block: int | None = None
    last_block_index = -1
    terminal = False
    for frame in frames:
        assert not terminal, f"frame after message_stop: {frame!r}"
        if frame["event"] == "error":
            assert allow_error, f"unexpected error event: {frame!r}"
            terminal = True
            continue
        for raw in frame["data"]:
            try:
                obj = json.loads(raw)
            except ValueError:
                if allow_error:
                    continue  # truncated partial frame on a cut stream
                raise AssertionError(f"unparseable data frame: {raw!r}")
            etype = obj.get("type")
            if etype == "message_start":
                starts += 1
                assert starts == 1, "second message_start on one stream"
            elif etype == "content_block_start":
                idx = obj.get("index")
                assert open_block is None, (
                    f"content_block_start for {idx} while block "
                    f"{open_block} is open"
                )
                assert idx > last_block_index, (
                    f"content_block index not increasing: {idx} after "
                    f"{last_block_index}"
                )
                open_block = idx
                last_block_index = idx
            elif etype == "content_block_delta":
                assert obj.get("index") == open_block, (
                    f"delta for block {obj.get('index')} but open block "
                    f"is {open_block}"
                )
            elif etype == "content_block_stop":
                assert obj.get("index") == open_block, (
                    f"stop for block {obj.get('index')} but open block "
                    f"is {open_block}"
                )
                open_block = None
            elif etype == "message_stop":
                stops += 1
                terminal = True
            elif etype == "error":
                assert allow_error, f"unexpected error payload: {raw!r}"
                terminal = True
    assert starts == 1 or (allow_error and starts == 0), (
        "stream must carry exactly one message_start"
    )
    if not allow_error:
        assert stops == 1, "stream must end with exactly one message_stop"
    assert stops <= 1, f"{stops} message_stop events"


class MockOpenAIEndpoint:
    """A fake OpenAI-compatible runtime with configurable behavior."""

    def __init__(self, *, model="mock-model", tokens_per_reply=5,
                 reply_delay_s=0.0, inter_chunk_delay_s=0.0,
                 fail_with: int | None = None,
                 include_usage=True):
        self.model = model
        self.tokens_per_reply = tokens_per_reply
        self.reply_delay_s = reply_delay_s
        # stream mode: sleep between SSE chunks so the proxy sees them as
        # separate reads (a local TestServer otherwise delivers the whole
        # body in one iter_any chunk)
        self.inter_chunk_delay_s = inter_chunk_delay_s
        self.fail_with = fail_with
        self.include_usage = include_usage
        self.requests_seen: list[dict] = []
        self.headers_seen: list[dict] = []  # per-request inbound headers
        self.server: TestServer | None = None

    @property
    def url(self) -> str:
        assert self.server is not None
        return f"http://127.0.0.1:{self.server.port}"

    async def start(self) -> "MockOpenAIEndpoint":
        app = web.Application()
        app.router.add_get("/v1/models", self._models)
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_post("/v1/completions", self._chat)
        app.router.add_post("/v1/responses", self._chat)
        app.router.add_post("/v1/embeddings", self._embeddings)
        self.server = TestServer(app)
        await self.server.start_server()
        return self

    async def stop(self) -> None:
        if self.server:
            await self.server.close()

    async def _models(self, request):
        return web.json_response(
            {"object": "list", "data": [{"id": self.model, "object": "model"}]}
        )

    async def _chat(self, request):
        body = await request.json()
        self.requests_seen.append(body)
        self.headers_seen.append(dict(request.headers))
        if self.fail_with:
            return web.json_response({"error": "induced"}, status=self.fail_with)
        if self.reply_delay_s:
            await asyncio.sleep(self.reply_delay_s)
        n = self.tokens_per_reply
        if body.get("stream"):
            resp = web.StreamResponse(
                headers={"Content-Type": "text/event-stream"}
            )
            await resp.prepare(request)
            for i in range(n):
                chunk = {
                    "id": "chatcmpl-mock", "object": "chat.completion.chunk",
                    "model": body.get("model"),
                    "choices": [{"index": 0, "delta": {"content": f"tok{i} "},
                                 "finish_reason": None}],
                }
                await resp.write(
                    b"data: " + json.dumps(chunk).encode() + b"\n\n"
                )
                if self.inter_chunk_delay_s:
                    await asyncio.sleep(self.inter_chunk_delay_s)
            final = {
                "id": "chatcmpl-mock", "object": "chat.completion.chunk",
                "model": body.get("model"),
                "choices": [{"index": 0, "delta": {}, "finish_reason": "stop"}],
            }
            await resp.write(b"data: " + json.dumps(final).encode() + b"\n\n")
            if self.include_usage:
                usage_chunk = {
                    "id": "chatcmpl-mock", "object": "chat.completion.chunk",
                    "choices": [],
                    "usage": {"prompt_tokens": 7, "completion_tokens": n,
                              "total_tokens": 7 + n},
                }
                await resp.write(
                    b"data: " + json.dumps(usage_chunk).encode() + b"\n\n"
                )
            await resp.write(b"data: [DONE]\n\n")
            return resp
        payload = {
            "id": "chatcmpl-mock", "object": "chat.completion",
            "model": body.get("model"),
            "choices": [{
                "index": 0,
                "message": {"role": "assistant",
                            "content": " ".join(f"tok{i}" for i in range(n))},
                "finish_reason": "stop",
            }],
        }
        if self.include_usage:
            payload["usage"] = {
                "prompt_tokens": 7, "completion_tokens": n, "total_tokens": 7 + n,
            }
        return web.json_response(payload)

    async def _embeddings(self, request):
        body = await request.json()
        self.requests_seen.append(body)
        return web.json_response({
            "object": "list",
            "data": [{"object": "embedding", "index": 0,
                      "embedding": [0.1, 0.2, 0.3]}],
            "model": body.get("model"),
            "usage": {"prompt_tokens": 4, "total_tokens": 4},
        })


class MockResumableEndpoint(MockOpenAIEndpoint):
    """A mock tpu:// engine for durable-stream tests: streams a scripted
    token sequence with gateway-internal ``llmlb.replay`` frames when the
    request is armed (``llmlb_replay: true``), and adopts cut streams on
    ``/v1/resume`` — replaying the committed ids and emitting the FULL text
    exactly as a real engine's adopt path does (token i renders as
    ``t<i> ``, deterministic across instances, so splice identity is
    checkable byte for byte)."""

    def __init__(self, *, model="mock-model", script=None,
                 tokens_per_chunk=1, inter_chunk_delay_s=0.002,
                 resume_fail_with: int | None = None):
        super().__init__(model=model,
                         inter_chunk_delay_s=inter_chunk_delay_s)
        # the full token sequence every instance of this "model" generates
        self.script = list(script if script is not None else range(100, 112))
        self.tokens_per_chunk = max(1, tokens_per_chunk)
        self.resume_fail_with = resume_fail_with
        self.resume_calls: list[dict] = []
        # /v1/kv/export behavior (proactive migration tests): None = serve
        # an opaque kv_pages payload; an int = refuse with that status
        # (an origin that cannot park right now, or an old build 404ing)
        self.export_fail_with: int | None = None
        self.export_calls: list[dict] = []
        # graceful-drain advertisement (flip from tests; the gateway's
        # health probe re-parses it every cycle)
        self.draining = False
        self.drain_remaining_s = 0.0

    @staticmethod
    def text_of(token_id: int) -> str:
        return f"t{token_id} "

    async def start(self) -> "MockResumableEndpoint":
        app = web.Application()
        app.router.add_get("/v1/models", self._models)
        app.router.add_get("/api/health", self._health)
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_post("/v1/resume", self._resume)
        app.router.add_post("/v1/kv/export", self._kv_export)
        self.server = TestServer(app)
        await self.server.start_server()
        return self

    async def _health(self, request):
        return web.json_response({
            "status": "draining" if self.draining else "ok",
            "tpu": {"accelerator": "tpu", "chip_count": 1},
            "engine": {"num_slots": 4, "active_slots": 0, "queued": 0},
            "draining": {"draining": self.draining, "grace_s": 30.0,
                         "remaining_s": self.drain_remaining_s},
        })

    async def _stream_script(self, request, body, start_token: int):
        """Stream self.script[start:] as chat chunks; with llmlb_replay,
        each chunk's ids ship first as an llmlb.replay frame (the engine
        contract: tokens always cover every character already sent)."""
        armed = bool(body.get("llmlb_replay"))
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"}
        )
        await resp.prepare(request)

        async def send(obj) -> None:
            await resp.write(
                b"data: " + json.dumps(obj).encode() + b"\n\n"
            )

        def chunk(delta, finish=None):
            return {
                "id": "chatcmpl-mockresume", "object": "chat.completion.chunk",
                "created": 1700000000, "model": body.get("model"),
                "choices": [{"index": 0, "delta": delta,
                             "finish_reason": finish}],
            }

        await send(chunk({"role": "assistant", "content": ""}))
        toks = self.script[start_token:]
        for i in range(0, len(toks), self.tokens_per_chunk):
            group = toks[i:i + self.tokens_per_chunk]
            if armed:
                await send({"object": "llmlb.replay", "tokens": group})
            await send(chunk(
                {"content": "".join(self.text_of(t) for t in group)}
            ))
            if self.inter_chunk_delay_s:
                await asyncio.sleep(self.inter_chunk_delay_s)
        await send(chunk({}, "stop"))
        await send({
            "id": "chatcmpl-mockresume", "object": "chat.completion.chunk",
            "choices": [],
            "usage": {"prompt_tokens": 7,
                      "completion_tokens": len(self.script),
                      "total_tokens": 7 + len(self.script)},
        })
        await resp.write(b"data: [DONE]\n\n")
        return resp

    async def _chat(self, request):
        body = await request.json()
        self.requests_seen.append(body)
        self.headers_seen.append(dict(request.headers))
        if self.fail_with:
            return web.json_response({"error": "induced"},
                                     status=self.fail_with)
        if not body.get("stream"):
            return await super()._chat(request)
        return await self._stream_script(request, body, 0)

    async def _kv_export(self, request):
        body = await request.json()
        self.export_calls.append(body)
        if self.export_fail_with:
            return web.json_response({"error": "induced"},
                                     status=self.export_fail_with)
        # opaque payload: the gateway forwards it verbatim to /v1/resume
        # (a real engine would refuse a mismatched payload and replay)
        return web.json_response({
            "request_id": body.get("request_id"),
            "kv_pages": {"mock": True, "park": bool(body.get("park"))},
        })

    async def _resume(self, request):
        body = await request.json()
        self.resume_calls.append(body)
        if self.resume_fail_with:
            return web.json_response({"error": "induced"},
                                     status=self.resume_fail_with)
        committed = body.get("committed_ids") or []
        # a real engine replays prompt+committed then CONTINUES — committed
        # ids must be a prefix of what this model deterministically generates
        assert committed == self.script[:len(committed)], (
            f"committed ids {committed} are not a prefix of {self.script}"
        )
        # full text from token 0: the adopt path re-emits committed text and
        # the gateway splices off what its client already holds
        return await self._stream_script(request, body, 0)


class MockDisaggEndpoint(MockOpenAIEndpoint):
    """A mock tpu:// engine with a disaggregation role: advertises the role
    on /v1/models capabilities and /api/health, answers /v1/handoff/prefill
    with a real wire payload (prefill role), and adopts payloads on
    /v1/handoff (decode role). The adopt reply's content embeds what
    arrived on the wire so tests can assert fields survived."""

    def __init__(self, *, role="both", model="mock-model",
                 tokens_per_reply=5, handoff_fail_with=None):
        super().__init__(model=model, tokens_per_reply=tokens_per_reply)
        self.role = role
        self.handoff_fail_with = handoff_fail_with
        self.prefill_calls: list[dict] = []  # /v1/handoff/prefill bodies
        self.adopt_calls: list[dict] = []  # /v1/handoff bodies
        self.adopt_headers: list[dict] = []

    async def start(self) -> "MockDisaggEndpoint":
        app = web.Application()
        app.router.add_get("/v1/models", self._models)
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_get("/api/health", self._health)
        app.router.add_post("/v1/handoff/prefill", self._prefill)
        app.router.add_post("/v1/handoff", self._adopt)
        self.server = TestServer(app)
        await self.server.start_server()
        return self

    async def _models(self, request):
        caps = ["chat_completion"]
        if self.role in ("both", "split", "prefill"):
            caps.append("prefill")
        if self.role in ("both", "split", "decode"):
            caps.append("decode")
        return web.json_response({
            "object": "list",
            "data": [{"id": self.model, "object": "model",
                      "capabilities": caps, "role": self.role}],
        })

    async def _health(self, request):
        return web.json_response({
            "status": "ok",
            "tpu": {"accelerator": "tpu", "chip_count": 1},
            "engine": {"num_slots": 4, "active_slots": 0, "queued": 0},
            "disagg": {"role": self.role, "split": self.role == "split",
                       "handoff_total": {}, "handoff_backlog": 0},
        })

    async def _prefill(self, request):
        from llmlb_tpu.disagg import handoff_payload
        from llmlb_tpu.engine.scheduler import SamplingParams

        body = await request.json()
        self.prefill_calls.append(
            {"body": body, "headers": dict(request.headers)}
        )
        if self.handoff_fail_with:
            return web.json_response({"error": "induced"},
                                     status=self.handoff_fail_with)
        deadline = request.headers.get("X-Request-Deadline-Ms")
        sampling = SamplingParams(
            temperature=float(body.get("temperature") or 1.0),
            max_tokens=int(body.get("max_tokens") or 16),
            priority={"high": 0, "normal": 1, "low": 2}.get(
                body.get("priority"), body.get("priority") or 1
            ) if body.get("priority") is not None else 1,
            deadline_ms=float(deadline) if deadline else None,
        )
        payload = handoff_payload(
            [1, 2, 3], [7], sampling,
            request_id=request.headers.get("X-Request-Id"),
        )
        return web.json_response({
            "object": "llmlb.handoff", "model": self.model,
            "handoff": payload, "finish": None, "tool_name": None,
            "usage": {"prompt_tokens": 3, "completion_tokens": 1,
                      "total_tokens": 4},
        })

    async def _adopt(self, request):
        body = await request.json()
        self.adopt_calls.append(body)
        self.adopt_headers.append(dict(request.headers))
        handoff = body.get("handoff") or {}
        sampling = handoff.get("sampling") or {}
        content = json.dumps({
            "adopted_by": self.role,
            "committed": handoff.get("committed_ids"),
            "priority": sampling.get("priority"),
            "deadline_ms": sampling.get("deadline_ms"),
        })
        if body.get("stream"):
            resp = web.StreamResponse(
                headers={"Content-Type": "text/event-stream"}
            )
            await resp.prepare(request)
            chunk = {
                "id": "chatcmpl-adopt", "object": "chat.completion.chunk",
                "model": body.get("model"),
                "choices": [{"index": 0, "delta": {"content": content},
                             "finish_reason": None}],
            }
            await resp.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
            final = {
                "id": "chatcmpl-adopt", "object": "chat.completion.chunk",
                "model": body.get("model"),
                "choices": [{"index": 0, "delta": {},
                             "finish_reason": "stop"}],
                "usage": {"prompt_tokens": 3, "completion_tokens": 5,
                          "total_tokens": 8},
            }
            await resp.write(b"data: " + json.dumps(final).encode() + b"\n\n")
            await resp.write(b"data: [DONE]\n\n")
            return resp
        return web.json_response({
            "id": "chatcmpl-adopt", "object": "chat.completion",
            "model": body.get("model"),
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": content},
                "finish_reason": "stop",
            }],
            "usage": {"prompt_tokens": 3, "completion_tokens": 5,
                      "total_tokens": 8},
        })


class MockOllamaEndpoint:
    """Speaks Ollama's discovery surface (/api/tags) for detection/sync tests."""

    def __init__(self, models=("llama3:8b",)):
        self.models = list(models)
        self.server: TestServer | None = None

    @property
    def url(self) -> str:
        assert self.server is not None
        return f"http://127.0.0.1:{self.server.port}"

    async def start(self) -> "MockOllamaEndpoint":
        app = web.Application()
        app.router.add_get("/api/tags", self._tags)
        app.router.add_get("/v1/models", self._models)
        app.router.add_post("/api/show", self._show)
        self.server = TestServer(app)
        await self.server.start_server()
        return self

    async def _show(self, request):
        body = await request.json()
        if body.get("name") not in self.models:
            return web.json_response({"error": "model not found"}, status=404)
        return web.json_response({
            "details": {"family": "llama"},
            "model_info": {"llama.context_length": 8192},
        })

    async def stop(self) -> None:
        if self.server:
            await self.server.close()

    async def _tags(self, request):
        return web.json_response(
            {"models": [{"name": m} for m in self.models]}
        )

    async def _models(self, request):
        return web.json_response(
            {"object": "list", "data": [{"id": m} for m in self.models]}
        )


class GatewayHarness:
    """In-process gateway with real middlewares over an in-memory DB."""

    def __init__(self, state, client: TestClient):
        self.state = state
        self.client = client
        self._admin_token: str | None = None
        self._api_key: str | None = None

    @classmethod
    async def create(cls, *, start_background=False) -> "GatewayHarness":
        import os

        os.environ["LLMLB_ADMIN_PASSWORD"] = ADMIN_PASSWORD
        os.environ["LLMLB_JWT_SECRET"] = TEST_JWT_SECRET
        config = ServerConfig.from_env()
        state = await build_app_state(
            config, db=Database(":memory:"), start_background=start_background
        )
        app = create_app(state)
        client = TestClient(TestServer(app))
        await client.start_server()
        return cls(state, client)

    async def close(self) -> None:
        await self.client.close()

    # ------------------------------------------------------------ auth helpers

    async def admin_token(self) -> str:
        if self._admin_token is None:
            resp = await self.client.post("/api/auth/login", json={
                "username": "admin", "password": ADMIN_PASSWORD,
            })
            assert resp.status == 200, await resp.text()
            self._admin_token = (await resp.json())["token"]
        return self._admin_token

    async def admin_headers(self) -> dict:
        return {"Authorization": f"Bearer {await self.admin_token()}"}

    async def inference_key(self) -> str:
        if self._api_key is None:
            resp = await self.client.post(
                "/api/api-keys",
                json={"name": "test", "permissions": [
                    "openai.inference", "openai.models.read"]},
                headers=await self.admin_headers(),
            )
            assert resp.status == 201, await resp.text()
            self._api_key = (await resp.json())["api_key"]
        return self._api_key

    async def inference_headers(self) -> dict:
        return {"Authorization": f"Bearer {await self.inference_key()}"}

    # -------------------------------------------------------------- endpoints

    def register_mock(
        self, url: str, models: list[str],
        endpoint_type=EndpointType.OPENAI_COMPATIBLE,
        capabilities=None, name=None,
    ) -> Endpoint:
        """Register an endpoint directly in the registry, already ONLINE."""
        ep = Endpoint(
            name=name or url, base_url=url, endpoint_type=endpoint_type,
            status=EndpointStatus.ONLINE,
        )
        self.state.registry.add(ep)
        self.state.registry.sync_models(ep.id, [
            EndpointModel(
                endpoint_id=ep.id, model_id=m, canonical_name=m,
                capabilities=capabilities or [Capability.CHAT_COMPLETION],
            )
            for m in models
        ])
        return ep
