"""The tpu:// endpoint HTTP server — the contract the gateway routes to.

Implements the runtime-side API the reference gateway expects of any endpoint
(SURVEY.md §7 stance): OpenAI `/v1/models`, `/v1/chat/completions`,
`/v1/completions`, `/v1/responses` (SSE streams end with a usage-bearing
payload — the gateway's TPS tracker depends on it, reference
llmlb/src/api/proxy.rs:118-241), plus `/api/health` with TPU chip/HBM telemetry
in place of the GPU fields (endpoint_checker.rs:515) and `/api/system` carrying
the `tpu_engine` marker the gateway's type detection probes first.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import re
import time
import uuid

from aiohttp import web

from llmlb_tpu import __version__
from llmlb_tpu.disagg import HandoffError, handoff_payload, parse_handoff
from llmlb_tpu.engine import stepstats
from llmlb_tpu.engine.profiling import ProfileError, ProfileManager
from llmlb_tpu.engine.scheduler import SamplingParams
from llmlb_tpu.engine.service import RECEIVED_AT, Engine, EngineError
from llmlb_tpu.structured import inspect_request, parse_seed

log = logging.getLogger("llmlb_tpu.engine.server")

# Echoed as `system_fingerprint` on chat completions: one serving-stack
# identity per engine build, so clients pairing it with `seed` can tell
# "same fingerprint + same seed => same tokens" apart from a stack change.
SYSTEM_FINGERPRINT = f"fp_llmlb_tpu_{__version__}"

MAX_BODY_BYTES = 20 * 1024 * 1024  # parity: reference caps /v1/* at 20 MiB
# Handoff/resume envelopes may carry a serialized KV page payload
# (engine/kv_transfer.py) — base64 over tens of MiB for long contexts on
# real configs — so the aiohttp body cap sits above the plain-JSON limit.
# Plain chat bodies stay bounded by prompt length long before this.
KV_BODY_BYTES = 256 * 1024 * 1024


# The gateway forwards its trace id on proxied calls; it becomes the prefix
# of the scheduler request_id (service.py appends a unique suffix), joining
# engine-side events to the gateway trace. Shape is enforced — the id
# reaches logs and response headers.
_REQUEST_ID_RE = re.compile(r"^[A-Za-z0-9_.:\-]{1,128}$")


def _request_id_from(request: web.Request) -> str | None:
    rid = request.headers.get("X-Request-Id")
    if rid and _REQUEST_ID_RE.match(rid):
        return rid
    return None


def _rid_headers(rid: str | None) -> dict:
    return {"X-Request-Id": rid} if rid else {}


def _error(status: int, message: str, err_type: str = "invalid_request_error"):
    return web.json_response(
        {"error": {"message": message, "type": err_type, "code": None}},
        status=status,
    )


def _sampling_from(body: dict, default_max: int = 256) -> SamplingParams:
    def pick(*names, default):
        for n in names:
            if body.get(n) is not None:
                return body[n]
        return default

    temperature = float(pick("temperature", default=1.0))
    top_p = float(pick("top_p", default=1.0))
    top_k = int(pick("top_k", default=0))
    max_tokens = int(
        pick("max_tokens", "max_completion_tokens", "max_output_tokens",
             default=default_max)
    )
    if temperature < 0:
        raise ValueError("'temperature' must be >= 0")
    if not 0 < top_p <= 1:
        raise ValueError("'top_p' must be in (0, 1]")
    if top_k < 0:
        raise ValueError("'top_k' must be >= 0")
    if max_tokens < 1:
        raise ValueError("'max_tokens' must be >= 1")
    return SamplingParams(
        temperature=temperature, top_p=top_p, top_k=top_k,
        max_tokens=max_tokens, speculative=_speculative_from(body),
        priority=_priority_from(body), **_block_params_from(body),
    )


def _block_params_from(body: dict) -> dict:
    """Per-request parameters of generation by diffusion over blocks (an
    OpenAI-dialect extension, docs/block-diffusion.md): `block_length`,
    `denoising_steps`, `remasking_strategy`, `confidence_threshold`. Absent
    -> the model configuration's. Types are checked here; whether the model
    generates by blocks at all, and whether the values fit its block, is
    the scheduler's to say at submission (a 400 either way)."""
    out: dict = {}
    for name in ("block_length", "denoising_steps"):
        v = body.get(name)
        if v is None:
            continue
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            raise ValueError(f"'{name}' must be a positive integer")
        out[name] = v
    v = body.get("remasking_strategy")
    if v is not None:
        if not isinstance(v, str):
            raise ValueError("'remasking_strategy' must be a string")
        out["remasking_strategy"] = v
    v = body.get("confidence_threshold")
    if v is not None:
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            raise ValueError("'confidence_threshold' must be a number")
        out["confidence_threshold"] = float(v)
    return out


_PRIORITY_NAMES = {"high": 0, "normal": 1, "low": 2}


def _priority_from(body: dict) -> int:
    """Per-request priority class (docs/scheduling.md), accepted on both
    the OpenAI and Anthropic dialects: "high"/"normal"/"low" or 0/1/2.
    Lower value = more important; default "normal"."""
    p = body.get("priority")
    if p is None:
        return 1
    if isinstance(p, str):
        if p not in _PRIORITY_NAMES:
            raise ValueError(
                "'priority' must be one of high, normal, low (or 0..2)"
            )
        return _PRIORITY_NAMES[p]
    if isinstance(p, bool) or not isinstance(p, int) or not 0 <= p <= 2:
        raise ValueError(
            "'priority' must be one of high, normal, low (or 0..2)"
        )
    return p


def _deadline_from(request: web.Request) -> float | None:
    """Remaining request deadline in milliseconds, propagated by the gateway
    (or set by a direct client) via the X-Request-Deadline-Ms header. The
    scheduler sheds the request if it is still queued when this budget runs
    out — work that cannot meet its deadline must not burn a prefill."""
    raw = request.headers.get("X-Request-Deadline-Ms")
    if not raw:
        return None
    try:
        ms = float(raw)
    except ValueError:
        raise ValueError("X-Request-Deadline-Ms must be a number")
    if ms <= 0:
        raise ValueError("X-Request-Deadline-Ms must be positive")
    return ms


def _speculative_from(body: dict) -> dict | None:
    """Per-request speculative-decoding knobs (an OpenAI-dialect extension,
    also carried through the Anthropic adapter): `speculative: {enabled,
    max_draft_tokens}`. Absent → engine defaults (--spec-decode /
    LLMLB_SPEC_*). Validated here so a malformed knob 400s instead of being
    silently ignored at the scheduler."""
    spec = body.get("speculative")
    if spec is None:
        return None
    if not isinstance(spec, dict):
        raise ValueError("'speculative' must be an object")
    out: dict = {}
    if "enabled" in spec:
        if not isinstance(spec["enabled"], bool):
            raise ValueError("'speculative.enabled' must be a boolean")
        out["enabled"] = spec["enabled"]
    if spec.get("max_draft_tokens") is not None:
        k = spec["max_draft_tokens"]
        if not isinstance(k, int) or isinstance(k, bool) or k < 1:
            raise ValueError(
                "'speculative.max_draft_tokens' must be a positive integer"
            )
        out["max_draft_tokens"] = k
    return out or None


def _handoff_tokens_from(body: dict) -> int:
    """Tokens the prefill side commits before handing off (the committed
    window the decode engine replays). Per-request `handoff_tokens`
    overrides LLMLB_DISAGG_HANDOFF_TOKENS (default 1 — prefill + first
    token, the smallest window that proves the stream is live). Clamped to
    64: the window rides the wire and is replayed by the adopter, so an
    absurd value just moves decode work back onto the prefill pool."""
    import os

    raw = body.get("handoff_tokens")
    if raw is None:
        raw = os.environ.get("LLMLB_DISAGG_HANDOFF_TOKENS", 1)
    try:
        k = int(raw)
    except (TypeError, ValueError):
        raise ValueError("'handoff_tokens' must be an integer")
    if isinstance(body.get("handoff_tokens"), bool) or not 1 <= k <= 64:
        raise ValueError("'handoff_tokens' must be between 1 and 64")
    return k


def _stops_from(body: dict) -> list[str]:
    stop = body.get("stop") or body.get("stop_sequences") or []
    if isinstance(stop, str):
        return [stop]
    return [s for s in stop if isinstance(s, str)]


def _usage(prompt_tokens: int, completion_tokens: int) -> dict:
    return {
        "prompt_tokens": prompt_tokens,
        "completion_tokens": completion_tokens,
        "total_tokens": prompt_tokens + completion_tokens,
    }


# Where a prepared response keeps (the engine's StreamStats, its
# connection's protocol): create_engine_app's on_response_prepare puts them
# there, so _sse_send can time its write without a word from the handlers.
_WAY_OUT = "llmlb.way_out"


async def _sse_send(resp: web.StreamResponse, payload: dict | str) -> None:
    if isinstance(payload, str):
        data = payload
    else:
        data = json.dumps(payload, separators=(",", ":"))
    frame = f"data: {data}\n\n".encode()
    way_out = resp.get(_WAY_OUT)
    # aiohttp appends to the transport's buffer and awaits only while the
    # transport holds writing paused (the reader downstream is behind by
    # more than the socket buffers): such a write is timed, as a wait
    if way_out is None or not way_out[1].writing_paused:
        await resp.write(frame)
        return
    t0 = stepstats._now()
    await resp.write(frame)
    way_out[0].write_waited(t0)


def _drain_grace_from_env() -> float:
    import os

    raw = os.environ.get("LLMLB_DRAIN_GRACE_S")
    if raw:
        try:
            return max(0.0, float(raw))
        except ValueError:
            log.warning("LLMLB_DRAIN_GRACE_S=%r is not a number; using 30",
                        raw)
    return 30.0


class DrainController:
    """Graceful engine drain (docs/deployment.md rolling-restart runbook).

    SIGTERM (via the aiohttp shutdown hook) and ``POST /api/drain`` both land
    here: the server flips to draining — new /v1 admissions 503 with an
    honest Retry-After, /api/health advertises ``draining`` so the gateway's
    health checker re-routes within one probe — while in-flight decodes get
    ``LLMLB_DRAIN_GRACE_S`` to finish. Anything still running when the grace
    expires is parked through the PR 10 park path (pages freed, resume state
    captured, counted in llmlb_engine_drain_parked_total) and its client
    connection hard-aborted, so the GATEWAY's mid-stream resume replays the
    committed tokens onto another engine. Drain is one-way: the process is
    expected to exit (SIGTERM) or be restarted by its supervisor."""

    def __init__(self, engine: Engine, grace_s: float | None = None):
        self.engine = engine
        self.grace_s = (_drain_grace_from_env()
                        if grace_s is None else max(0.0, float(grace_s)))
        self.draining = False
        self.started_at = 0.0
        self.parked = 0
        self.aborted_connections = 0
        # transports of in-flight POST /v1/* requests (the drain middleware
        # maintains this); aborting them after the grace is what turns a
        # straggler into a gateway-visible cut the resume path picks up
        self._streams: set = set()
        self._task: "asyncio.Task | None" = None

    # ------------------------------------------------------------- middleware

    def track(self, transport) -> None:
        if transport is not None:
            self._streams.add(transport)

    def untrack(self, transport) -> None:
        self._streams.discard(transport)

    def remaining_s(self) -> float:
        if not self.draining:
            return self.grace_s
        return max(0.0, self.started_at + self.grace_s - time.monotonic())

    def retry_after_s(self) -> int:
        """Honest Retry-After for a refused admission: the drain grace still
        remaining — after that this process is gone and its replacement (or
        the rest of the fleet) is the right target."""
        return max(1, int(self.remaining_s() + 0.999))

    def info(self) -> dict:
        return {
            "draining": self.draining,
            "grace_s": self.grace_s,
            "remaining_s": round(self.remaining_s(), 3),
            "active_streams": len(self._streams),
            "parked": self.parked,
            "aborted_connections": self.aborted_connections,
        }

    # ------------------------------------------------------------------ drain

    def start(self, grace_s: float | None = None) -> dict:
        """Begin draining (idempotent). Returns the current drain info."""
        if not self.draining:
            if grace_s is not None:
                self.grace_s = max(0.0, float(grace_s))
            self.draining = True
            self.started_at = time.monotonic()
            core = self.engine.core
            core.begin_drain()
            core.metrics.set_drain_state(1)
            log.info("drain started: %d in-flight stream(s), grace %.1fs",
                     len(self._streams), self.grace_s)
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="engine-drain"
            )
        return self.info()

    async def wait(self) -> None:
        if self._task is not None:
            await self._task

    async def _run(self) -> None:
        core = self.engine.core
        deadline = self.started_at + self.grace_s
        while time.monotonic() < deadline:
            if not self._streams and core.stats().active_slots == 0:
                log.info("drain complete: all in-flight work finished "
                         "within the grace")
                return
            await asyncio.sleep(0.05)
        # Grace spent: park what is still decoding (the step loop executes
        # the parks — slot state is loop-thread-owned) so the committed
        # tokens are accounted, then hard-abort the surviving connections.
        # The gateway sees each abort as a mid-stream cut and resumes the
        # stream on another engine from its own replay ledger.
        before = core.metrics.drain_parked_total
        core.request_drain_park()
        # wait for the step loop to CONSUME the park request (it may be
        # inside a long dispatch/compile), then briefly for the parks to
        # settle — a fixed short wait here under-reported `parked` whenever
        # a dispatch outlived it. Bounded: a wedged loop must not stall the
        # aborts (and the shutdown behind them) forever.
        flag_deadline = time.monotonic() + 30.0
        while (core._drain_park_requested
               and time.monotonic() < flag_deadline):
            await asyncio.sleep(0.02)
        settle_deadline = time.monotonic() + 2.0
        while (time.monotonic() < settle_deadline
               and core.stats().active_slots > 0):
            await asyncio.sleep(0.02)
        self.parked = core.metrics.drain_parked_total - before
        stragglers = list(self._streams)
        for transport in stragglers:
            try:
                transport.abort()
            except Exception:  # allow-silent: best-effort teardown of a
                # transport that may already be closing under us
                pass
        self.aborted_connections = len(stragglers)
        # AFTER the aborts: terminal-error everything still queued (parked
        # work included) so the handlers blocked on those event queues
        # unblock — their farewell frames can no longer reach a client (the
        # sockets are gone), and the gateway resumes from its own ledger.
        core.request_drain_flush()
        if stragglers or self.parked:
            log.warning(
                "drain grace expired: parked %d slot(s), aborted %d "
                "connection(s) for gateway-side resume",
                self.parked, len(stragglers),
            )


class EngineAPI:
    def __init__(self, engine: Engine, *, asr=None, tts=None, image=None):
        self.engine = engine
        self.asr = asr  # engine.asr.AsrEngine | None
        self.tts = tts  # engine.tts.TtsEngine | None
        self.image = image  # engine.image.ImageEngine | None
        # one capture at a time: the manager guards the global jax tracer
        self.profiles = ProfileManager()
        # graceful drain (SIGTERM / POST /api/drain): admission gate +
        # in-flight connection ledger (docs/deployment.md)
        self.drain = DrainController(engine)

    # ------------------------------------------------------------- inventory

    async def list_models(self, request: web.Request) -> web.Response:
        # structured_outputs: grammar-constrained decoding is a property of
        # the engine (llmlb_tpu/structured), advertised so the gateway's
        # capability routing steers constrained requests here and away from
        # endpoints that would ignore response_format.
        caps = ["chat_completion", "structured_outputs"]
        if self.engine.supports_embeddings():
            caps.append("embeddings")
        # Disaggregation roles ride the capability list (the structured-
        # outputs advertisement is the template): the gateway's role-aware
        # balancer steers prefill-heavy requests toward "prefill"-capable
        # endpoints and handoff adoption toward "decode"-capable ones
        # (docs/disaggregation.md).
        role = self.engine.core.role
        if role in ("both", "split", "prefill"):
            caps.append("prefill")
        if role in ("both", "split", "decode"):
            caps.append("decode")
        # Multi-LoRA (docs/lora.md): "lora" on the BASE entry means "this
        # endpoint can hot-load any adapter in its store"; each RESIDENT
        # adapter additionally advertises as its own model entry
        # `base:adapter`, so the gateway's model sync routes adapter
        # traffic to endpoints where it is already hot and falls back to
        # any lora-capable endpoint (triggering a hot-load) before 404ing.
        lora_mgr = self.engine.core.lora
        if lora_mgr is not None:
            caps.append("lora")

        def entry(model_id: str, caps: list[str]) -> dict:
            return {
                "id": model_id,
                "object": "model",
                "created": 0,
                "owned_by": "llmlb_tpu",
                # advertised so the gateway's model sync can assign
                # capabilities without name heuristics
                "capabilities": caps,
            }

        main_entry = entry(self.engine.model_id, caps)
        main_entry["role"] = role
        data = [main_entry]
        if lora_mgr is not None:
            for name in lora_mgr.resident_names():
                adapter_entry = entry(
                    f"{self.engine.model_id}:{name}",
                    [c for c in caps if c != "embeddings"],
                )
                adapter_entry["role"] = role
                adapter_entry["lora"] = name
                data.append(adapter_entry)
        if self.asr is not None:
            data.append(entry(self.asr.model_id, ["audio_transcription"]))
        if self.tts is not None:
            data.append(entry(self.tts.model_id, ["audio_speech"]))
        if self.image is not None:
            data.append(entry(self.image.model_id, ["image_generation"]))
        return web.json_response({"object": "list", "data": data})

    # ------------------------------------------------------------ multimodal

    async def audio_transcriptions(self, request: web.Request) -> web.Response:
        """OpenAI /v1/audio/transcriptions: multipart form with `file` (WAV)."""
        if self.asr is None:
            return _error(404, "no transcription model is loaded on this engine")
        if not (request.content_type or "").startswith("multipart/"):
            return _error(400, "multipart/form-data body required")
        file_bytes = None
        async for part in await request.multipart():
            if part.name == "file":
                file_bytes = await part.read(decode=False)
            else:
                await part.read(decode=False)  # drain model/language/etc.
        if not file_bytes:
            return _error(400, "'file' part is required")
        loop = asyncio.get_running_loop()
        try:
            text = await loop.run_in_executor(
                None, self.asr.transcribe_wav_bytes, file_bytes
            )
        except (ValueError, EOFError) as e:
            return _error(400, f"could not decode audio: {e}")
        return web.json_response({"text": text})

    async def audio_speech(self, request: web.Request) -> web.Response:
        """OpenAI /v1/audio/speech: JSON {input, voice, speed} -> WAV bytes."""
        if self.tts is None:
            return _error(404, "no speech model is loaded on this engine")
        body = await request.json()
        text = body.get("input")
        if not isinstance(text, str) or not text:
            return _error(400, "'input' is required")
        voice = str(body.get("voice", "alloy"))
        speed = float(body.get("speed", 1.0))
        loop = asyncio.get_running_loop()
        try:
            wav = await loop.run_in_executor(
                None, self.tts.synthesize, text, voice, speed
            )
        except ValueError as e:
            return _error(400, str(e))
        return web.Response(body=wav, content_type="audio/wav")

    async def images_generations(self, request: web.Request) -> web.Response:
        """OpenAI /v1/images/generations: JSON {prompt, n} -> b64 PNGs."""
        if self.image is None:
            return _error(404, "no image model is loaded on this engine")
        body = await request.json()
        prompt = body.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            return _error(400, "'prompt' is required")
        n = body.get("n", 1)
        if not isinstance(n, int) or not 1 <= n <= 10:
            return _error(400, "'n' must be between 1 and 10")
        loop = asyncio.get_running_loop()
        try:
            images = await loop.run_in_executor(
                None, self.image.generate_b64, prompt, n
            )
        except ValueError as e:
            return _error(400, str(e))
        return web.json_response({
            "created": int(time.time()),
            "data": [{"b64_json": b} for b in images],
        })

    async def embeddings(self, request: web.Request) -> web.Response:
        """OpenAI /v1/embeddings: input may be a string, list of strings, or
        list of token-id lists."""
        body = await request.json()
        raw = body.get("input")
        if raw is None:
            return _error(400, "'input' is required")
        if isinstance(raw, str):
            inputs = [raw]
        elif isinstance(raw, list) and raw and all(
            isinstance(x, int) for x in raw
        ):
            inputs = [raw]  # single pre-tokenized input
        elif isinstance(raw, list) and raw:
            inputs = raw
        else:
            return _error(400, "'input' must be a non-empty string or array")

        batch_ids: list[list[int]] = []
        for item in inputs:
            if isinstance(item, str):
                batch_ids.append(self.engine.tokenizer.encode(item))
            elif isinstance(item, list) and all(isinstance(x, int) for x in item):
                batch_ids.append([int(x) for x in item])
            else:
                return _error(400, "each input must be a string or token array")
        try:
            vectors = await self.engine.embed(batch_ids)
        except ValueError as e:
            return _error(400, str(e))
        prompt_tokens = sum(len(x) for x in batch_ids)
        return web.json_response(
            {
                "object": "list",
                "model": body.get("model", self.engine.model_id),
                "data": [
                    {"object": "embedding", "index": i, "embedding": vec}
                    for i, vec in enumerate(vectors)
                ],
                "usage": {
                    "prompt_tokens": prompt_tokens,
                    "total_tokens": prompt_tokens,
                },
            }
        )

    async def health(self, request: web.Request) -> web.Response:
        body = self.engine.health(current="http_loop")
        if self.drain.draining:
            # the gateway's health checker re-parses this on EVERY probe and
            # flips the endpoint out of selection within one interval
            body["status"] = "draining"
        body["draining"] = self.drain.info()
        # KV page shipping + host-RAM offload tier (docs/kv-cache.md)
        body["kv_transfer"] = self.engine.core.kv_transfer_info()
        return web.json_response(body)

    async def kv_export(self, request: web.Request) -> web.Response:
        """POST /v1/kv/export {"request_id": <gateway id>} — hand over a
        DRAINING engine's parked-stream KV pages (docs/kv-cache.md). The
        gateway fetches this between drain-park and /v1/resume on the
        adopter, so the mid-stream failover moves bytes instead of
        re-prefilling. One-shot: the payload is consumed by the fetch. 404
        when there is nothing for that id (never an error path for the
        resume — the gateway just falls back to plain replay).

        With {"park": true} (the rebalancer's proactive migration,
        docs/resilience.md) the engine is asked to park that ONE stream
        first: the step loop spills its KV at the next iteration and this
        handler polls briefly for the payload. 404 past the poll window
        means the stream was unparkable (mid-prefill, already finished) —
        the migration aborts with the origin stream untouched."""
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        rid = body.get("request_id") if isinstance(body, dict) else None
        if not isinstance(rid, str) or not rid:
            return _error(400, "'request_id' must be a non-empty string")
        core = self.engine.core
        if body.get("park"):
            core.request_park(rid)
            deadline = time.monotonic() + 2.0
            payload = core.take_kv_export(rid)
            while payload is None and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
                payload = core.take_kv_export(rid)
        else:
            payload = core.take_kv_export(rid)
        if payload is None:
            return _error(404, f"no KV export held for request {rid!r}")
        return web.json_response(
            {"object": "llmlb.kv_export", "request_id": rid,
             "kv_pages": payload}
        )

    async def drain_control(self, request: web.Request) -> web.Response:
        """POST /api/drain — begin a graceful drain (docs/deployment.md):
        new admissions 503 with Retry-After, in-flight decodes get the grace
        (optional body {"grace_s": N} overrides LLMLB_DRAIN_GRACE_S), then
        stragglers are parked and their connections closed so the gateway's
        mid-stream resume moves them to another engine. Idempotent; poll
        GET /api/health for progress."""
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        grace = body.get("grace_s")
        if grace is not None and (isinstance(grace, bool)
                                  or not isinstance(grace, (int, float))
                                  or grace < 0):
            return _error(400, "'grace_s' must be a non-negative number")
        return web.json_response(self.drain.start(grace))

    async def prometheus_metrics(self, request: web.Request) -> web.Response:
        """GET /metrics — Prometheus exposition of the serving loop
        (TTFT/ITL histograms, token/request counters, queue depth)."""
        core = self.engine.core
        stats = core.stats()
        text = core.metrics.render(
            queue_depth=stats.queued, active_slots=stats.active_slots,
            num_slots=stats.num_slots, prefix_cache=core.prefix_cache_info(),
            kv_cache=core.kv_cache_info(), structured=core.structured_info(),
            perf=core.perf_info(), quant=core.quant_info(),
            sched=core.sched_info(), lora=core.lora_info(),
            flightrec=core.flightrec.counters(),
            kv_offload=core.kv_transfer_info()["offload"],
            current="http_loop",
        )
        return web.Response(
            text=text, content_type="text/plain", charset="utf-8"
        )

    async def system(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "name": "llmlb_tpu-engine",
                "version": __version__,
                "tpu_engine": True,
                "model": self.engine.model_id,
                "prefix_cache": self.engine.core.prefix_cache_info(),
                # live page-pool utilization
                "kv_cache": self.engine.core.kv_cache_info(),
                # int8 quantization knobs + honest byte footprints
                "quant": self.engine.core.quant_info(),
                "structured": self.engine.core.structured_info(),
                # speculative decoding: config + live acceptance figures
                "spec": self.engine.core.spec_info(),
                # overload protection: priority queues, preemption counters
                "sched": self.engine.core.sched_info(),
                # disaggregated prefill/decode: role + handoff counters
                "disagg": self.engine.core.disagg_info(),
                # KV page shipping + host-RAM offload tier (docs/kv-cache.md)
                "kv_transfer": self.engine.core.kv_transfer_info(),
                # multi-LoRA adapter pool (docs/lora.md)
                "lora": self.engine.core.lora_info(),
                # graceful drain state (docs/deployment.md)
                "draining": self.drain.info(),
                # live roofline: MFU / HBM-bandwidth utilization against the
                # chip's peak specs (available only on chips in the table
                # and once decode traffic has flowed)
                "perf": self.engine.core.perf_info(),
            }
        )

    async def steps(self, request: web.Request) -> web.Response:
        """GET /api/steps — the step-loop introspection surface: recent
        per-step records (spans on one clock, the account of the time since
        the previous step, the legacy plan / host_sync / dispatch / compute /
        fetch / emit phases), per-kind EMA baselines, slow-step anomalies,
        and the programs built (docs/profiling.md).
        `?limit=N` bounds the record count (default 64, max ring size);
        `?slow=1` returns only anomalous steps."""
        core = self.engine.core
        try:
            limit = int(request.query.get("limit", 64))
        except ValueError:
            return _error(400, "'limit' must be an integer")
        slow_only = request.query.get("slow", "") in ("1", "true", "yes")
        body = core.step_stats.snapshot(limit=limit, slow_only=slow_only)
        # programs built since the engine was made, the newest by name: a
        # record's `builds` says which step paid for one
        body["compile"] = core.metrics.compile_info(builds=64)
        body["perf"] = core.perf_info()
        body["flightrec"] = core.flightrec.counters()
        return web.json_response(body)

    async def request_timeline(self, request: web.Request) -> web.Response:
        """GET /api/requests/{request_id}/timeline — one request's flight
        record: every lifecycle event this engine (plus any spool siblings)
        recorded for the gateway-minted X-Request-Id, sorted causally. The
        gateway's /api/traces/{id}?view=timeline merges this across every
        engine the request touched (docs/tracing.md)."""
        rid = request.match_info["request_id"]
        core = self.engine.core
        if not core.flightrec.enabled:
            return _error(404, "flight recorder disabled (LLMLB_FLIGHTREC=0)")
        body = core.flightrec.timeline(rid)
        if body is None:
            return _error(404, f"no flight record for request '{rid}'")
        return web.json_response(body)

    # ------------------------------------------------------------- profiling

    @staticmethod
    def _profile_authorized(request: web.Request) -> bool:
        """Capture gating: when LLMLB_PROFILE_TOKEN is set, profile control
        and artifact download require `Authorization: Bearer <token>` — the
        admin gate for a port that is otherwise auth-free by design."""
        import os

        token = os.environ.get("LLMLB_PROFILE_TOKEN")
        if not token:
            return True
        authz = request.headers.get("Authorization", "")
        return authz == f"Bearer {token}"

    async def profile_control(self, request: web.Request) -> web.Response:
        """POST /api/profile — on-demand jax.profiler capture of the live
        serving loop. Body: {"action": "start", "seconds": N} begins a
        capture with a bounded auto-stop (max 60s); {"action": "stop"} ends
        it early. The completed capture is downloadable as a zip at
        GET /api/profile/{capture_id} (docs/profiling.md)."""
        if not self._profile_authorized(request):
            return _error(401, "profile capture requires the profile token",
                          "authentication_error")
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        action = body.get("action", "start")
        try:
            if action == "start":
                try:
                    seconds = float(body.get("seconds", 3.0))
                except (TypeError, ValueError):
                    return _error(400, "'seconds' must be a number")
                started = self.profiles.start(seconds)
                return web.json_response({"started": True, **started})
            if action == "stop":
                # stop serializes the whole trace — worker thread, so the
                # event loop (and every in-flight stream) stays responsive
                loop = asyncio.get_running_loop()
                done = await loop.run_in_executor(None, self.profiles.stop)
                return web.json_response({"stopped": True, **done})
        except ProfileError as e:
            return _error(e.status, str(e),
                          "server_error" if e.status >= 500
                          else "invalid_request_error")
        return _error(400, "'action' must be 'start' or 'stop'")

    async def profile_status(self, request: web.Request) -> web.Response:
        """GET /api/profile — capture state + completed-capture ledger."""
        if not self._profile_authorized(request):
            return _error(401, "profile status requires the profile token",
                          "authentication_error")
        return web.json_response(self.profiles.status())

    async def profile_artifact(self, request: web.Request) -> web.StreamResponse:
        """GET /api/profile/{capture_id} — the downloadable trace artifact:
        a zip of the capture's trace directory, unpackable for
        `tensorboard --logdir` / xprof. Built on disk in a worker thread
        (TPU traces run to hundreds of MB) and streamed from the file."""
        if not self._profile_authorized(request):
            return _error(401, "profile download requires the profile token",
                          "authentication_error")
        loop = asyncio.get_running_loop()
        try:
            path, filename = await loop.run_in_executor(
                None, self.profiles.artifact,
                request.match_info["capture_id"],
            )
        except ProfileError as e:
            return _error(e.status, str(e))
        return web.FileResponse(
            path,
            headers={"Content-Type": "application/zip",
                     "Content-Disposition":
                     f'attachment; filename="{filename}"'},
        )

    async def debug_profile(self, request: web.Request) -> web.Response:
        """POST /debug/profile {"seconds": N} — the original one-shot form:
        start a capture, wait out its bounded duration, return the trace
        directory. Kept for operators and scripts that predate the
        start/stop /api/profile surface; both share one ProfileManager, so
        they can never double-start the global tracer."""
        if not self._profile_authorized(request):
            return _error(401, "profile capture requires the profile token",
                          "authentication_error")
        try:
            body = await request.json() if request.can_read_body else {}
        except Exception:
            body = {}
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        try:
            seconds = min(30.0, max(0.1, float(body.get("seconds", 3.0))))
        except (TypeError, ValueError):
            return _error(400, "'seconds' must be a number")
        try:
            started = self.profiles.start(seconds)
        except ProfileError as e:
            return _error(e.status, str(e))
        # the bounded auto-stop ends the capture even if the client leaves;
        # this handler waits for the stop event itself (worker thread — no
        # poll loop, and the event loop stays free for in-flight streams)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, self.profiles.wait_idle, seconds + 30.0
        )
        return web.json_response({
            "trace_dir": started["trace_dir"],
            "seconds": started["seconds"],
            "capture_id": started["capture_id"],
            "hint": "tensorboard --logdir <trace_dir> (profile plugin)",
        })

    def _sampling(self, body: dict, default_max: int = 256) -> SamplingParams:
        """_sampling_from, and what this engine's model cannot serve of the
        body refused by name: per-token `logprobs` of a model that generates
        by diffusion over blocks (a position's sampled probability is of one
        pass among several; none is served wrong)."""
        core = getattr(self.engine, "core", None)
        if getattr(core, "block", 1) > 1 and (
                body.get("logprobs") or body.get("top_logprobs")):
            raise ValueError(
                "'logprobs' are not served by a model that generates by "
                "diffusion over blocks")
        return _sampling_from(body, default_max)

    # ------------------------------------------------------ chat completions

    def _parse_chat(self, request: web.Request, body: dict):
        """Shared chat-request parse (chat_completions + the handoff-prefill
        endpoint, which accepts the same body): returns (prompt_ids,
        sampling, stops, tool_name, model). Raises ValueError for anything
        malformed — callers turn that into a 400 naming the field."""
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            raise ValueError("'messages' must be a non-empty array")
        if int(body.get("n") or 1) != 1:
            raise ValueError("only n=1 is supported")
        model = body.get("model") or self.engine.model_id
        try:
            prompt_ids = self.engine.encode_chat(messages)
        except ValueError:
            raise
        except Exception as e:
            raise ValueError(f"failed to encode messages: {e}")
        # Structured outputs: response_format (json_object / json_schema) or
        # a forced tool_choice compile to a grammar constraint the scheduler
        # enforces token by token. Malformed or uncompilable requests 400
        # here with the offending feature named.
        structured = inspect_request(body)
        sampling = self._sampling(body)
        sampling.seed = parse_seed(body)
        sampling.deadline_ms = _deadline_from(request)
        if structured is not None:
            sampling.constraint = structured.spec
        tool_name = structured.tool_name if structured is not None else None
        # Multi-LoRA (docs/lora.md): adapter via the `lora` field or the
        # `model:adapter` suffix (suffix considered only on LoRA-enabled
        # engines — a colon in a model name stays inert otherwise).
        # Unknown/invalid adapters 400 here with the field named, before a
        # stream response could start.
        adapter, base = self._parse_lora(body)
        if adapter is not None:
            sampling.lora = adapter
            model = base or model
        return prompt_ids, sampling, _stops_from(body), tool_name, model

    def _parse_lora(self, body: dict) -> tuple[str | None, str | None]:
        """(adapter, base_model) from a request body, validated against this
        engine's adapter store. Raises ValueError naming the `lora` field —
        the shared contract with the gateway's inspect path
        (llmlb_tpu/lora/api.py)."""
        from llmlb_tpu.lora import adapter_from_body

        core = self.engine.core
        if body.get("lora") is None and core.lora is None:
            return None, None
        if core.lora is None:
            raise ValueError(
                "'lora' adapters are not enabled on this engine "
                "(start it with --lora-dir)"
            )
        base, adapter = adapter_from_body(body)
        if adapter is None:
            return None, None
        core.lora.validate(adapter)
        return adapter, base

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        try:
            prompt_ids, sampling, stops, tool_name, model = self._parse_chat(
                request, body
            )
        except ValueError as e:
            return _error(400, str(e))

        completion_id = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        rid = _request_id_from(request)

        if body.get("stream"):
            return await self._stream_chat(
                request, completion_id, created, model, prompt_ids, sampling, stops,
                include_usage=bool(
                    (body.get("stream_options") or {}).get("include_usage", True)
                ),
                request_id=rid,
                tool_name=tool_name,
                replay=bool(body.get("llmlb_replay")),
            )

        try:
            result = await self.engine.complete(prompt_ids, sampling, stops,
                                                request_id=rid)
        except EngineError as e:
            return _error(500, str(e), "server_error")
        except ValueError as e:
            return _error(400, str(e))
        return self._chat_response(completion_id, created, model, result,
                                   tool_name, rid)

    async def _stream_chat(
        self, request, completion_id, created, model, prompt_ids, sampling, stops,
        include_usage: bool, request_id: str | None = None,
        tool_name: str | None = None, agen=None, replay: bool = False,
    ) -> web.StreamResponse:
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
                **_rid_headers(request_id),
            },
        )
        await resp.prepare(request)

        def chunk(delta: dict, finish: str | None = None) -> dict:
            return {
                "id": completion_id,
                "object": "chat.completion.chunk",
                "created": created,
                "model": model,
                "system_fingerprint": SYSTEM_FINGERPRINT,
                "choices": [
                    {"index": 0, "delta": delta, "finish_reason": finish}
                ],
            }

        await _sse_send(resp, chunk({"role": "assistant", "content": ""}))
        if tool_name is not None:
            # Forced tool call: open the call in the first tool delta (id +
            # name), then stream the constrained arguments as fragments —
            # the shape OpenAI SDKs and the Anthropic stream re-encoder
            # (gateway/api_anthropic.AnthropicStreamEncoder) both consume.
            await _sse_send(resp, chunk({"tool_calls": [{
                "index": 0,
                "id": f"call_{uuid.uuid4().hex[:24]}",
                "type": "function",
                "function": {"name": tool_name, "arguments": ""},
            }]}))
        usage = _usage(len(prompt_ids), 0)
        finish = "stop"
        if agen is None:
            agen = self.engine.stream(prompt_ids, sampling, stops,
                                      request_id=request_id)
        try:
            async for delta in agen:
                if replay and delta.token_ids:
                    # Durable streams (docs/resilience.md): ship the newly
                    # committed token ids as a gateway-internal frame BEFORE
                    # the text they produced — the gateway strips these and,
                    # on a mid-stream cut, replays them onto another engine's
                    # /v1/resume so the continuation is token-identical.
                    await _sse_send(resp, {
                        "object": "llmlb.replay",
                        "tokens": [int(t) for t in delta.token_ids],
                    })
                if delta.text:
                    if tool_name is not None:
                        await _sse_send(resp, chunk({"tool_calls": [{
                            "index": 0,
                            "function": {"arguments": delta.text},
                        }]}))
                    else:
                        await _sse_send(resp, chunk({"content": delta.text}))
                if delta.finish_reason is not None:
                    finish = delta.finish_reason
                    usage = _usage(delta.prompt_tokens, delta.completion_tokens)
        except (EngineError, ValueError) as e:
            try:
                await _sse_send(resp, {"error": {"message": str(e)}})
                await resp.write(b"data: [DONE]\n\n")
            except OSError:
                # socket already gone (drain aborted it / client left): the
                # farewell has nowhere to go, and failing loudly here would
                # just re-raise into the access log
                pass
            return resp
        if tool_name is not None and finish == "stop":
            finish = "tool_calls"
        await _sse_send(resp, chunk({}, finish))
        if include_usage:
            final = chunk({}, None)
            final["choices"] = []
            final["usage"] = usage
            await _sse_send(resp, final)
        await resp.write(b"data: [DONE]\n\n")
        return resp

    # -------------------------------------------- disaggregated handoff wire

    def _chat_response(self, completion_id: str, created: int, model: str,
                       result, tool_name: str | None,
                       rid: str | None) -> web.Response:
        """Non-streaming chat.completion JSON from a collected result —
        shared by /v1/chat/completions and the handoff surfaces."""
        if tool_name is not None:
            message: dict = {
                "role": "assistant",
                "content": None,
                "tool_calls": [{
                    "id": f"call_{uuid.uuid4().hex[:24]}",
                    "type": "function",
                    "function": {"name": tool_name, "arguments": result.text},
                }],
            }
            finish = ("tool_calls" if result.finish_reason == "stop"
                      else result.finish_reason)
        else:
            message = {"role": "assistant", "content": result.text}
            finish = result.finish_reason
        return web.json_response(
            {
                "id": completion_id,
                "object": "chat.completion",
                "created": created,
                "model": model,
                "system_fingerprint": SYSTEM_FINGERPRINT,
                "choices": [
                    {"index": 0, "message": message, "finish_reason": finish}
                ],
                "usage": _usage(result.prompt_tokens,
                                result.completion_tokens),
            },
            headers=_rid_headers(rid),
        )

    async def _collect_chat_response(self, agen, completion_id: str,
                                     created: int, model: str,
                                     tool_name: str | None,
                                     rid: str | None) -> web.Response:
        """Drain a stream generator into one chat.completion JSON — the
        non-streaming tail shared by /v1/handoff adoption and /v1/resume."""
        import dataclasses as _dc

        text = []
        final = None
        try:
            async for delta in agen:
                text.append(delta.text)
                if delta.finish_reason is not None:
                    final = delta
        except EngineError as e:
            return _error(500, str(e), "server_error")
        except ValueError as e:
            return _error(400, str(e))
        assert final is not None
        result = _dc.replace(final, text="".join(text))
        return self._chat_response(completion_id, created, model, result,
                                   tool_name, rid)

    async def handoff_prefill(self, request: web.Request) -> web.Response:
        """POST /v1/handoff/prefill — the prefill-role half of the
        cross-process handoff (docs/disaggregation.md). Body: a standard
        chat-completions request plus optional `handoff_tokens` (how many
        tokens to commit before handing off; default LLMLB_DISAGG_HANDOFF_TOKENS
        or 1). Responds `{"object": "llmlb.handoff", "handoff": <wire
        payload>, "finish": str|null, ...}` — the caller POSTs the payload
        to a decode-capable engine's /v1/handoff, which streams the FULL
        completion (committed + continuation). `finish` is null while the
        stream has more to generate; when the request completed inside the
        committed window (EOS / max_tokens) it carries the natural finish —
        the adopt replay still reproduces that finish token-identically
        (EOS re-samples at the same absolute position; a spent max_tokens
        budget finishes at adoption without touching the step loop), so
        orchestrators need only one shape."""
        if self.engine.core.role == "decode":
            return _error(
                409, "this engine serves --role decode; it adopts handoffs "
                "(/v1/handoff) but does not originate them",
            )
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        try:
            prompt_ids, sampling, stops, tool_name, model = self._parse_chat(
                request, body
            )
            emit = _handoff_tokens_from(body)
        except ValueError as e:
            return _error(400, str(e))
        rid = _request_id_from(request)
        try:
            committed, finish, kv_pages = await self.engine.prefill_handoff(
                prompt_ids, sampling, emit_tokens=emit, request_id=rid
            )
        except EngineError as e:
            return _error(500, str(e), "server_error")
        except ValueError as e:
            return _error(400, str(e))
        payload = handoff_payload(
            prompt_ids, committed, sampling, stop=stops, request_id=rid,
            kv_pages=kv_pages if finish is None else None,
        )
        return web.json_response(
            {
                "object": "llmlb.handoff",
                "model": model,
                "handoff": payload,
                "finish": finish,
                "tool_name": tool_name,
                "usage": _usage(len(prompt_ids), len(committed)),
            },
            headers=_rid_headers(rid),
        )

    async def handoff_adopt(self, request: web.Request) -> web.StreamResponse:
        """POST /v1/handoff — adopt a stream a prefill engine started. Body:
        `{"handoff": <wire payload>, "stream": bool, "model": str?,
        "tool_name": str?}`. The payload replays as prompt+committed chunk
        prefill (PR 10 park/resume), so the continuation is token-identical
        to an uninterrupted run; the response carries the FULL text
        (committed + continuation) as a normal chat completion / SSE stream.
        Malformed payloads 400 via HandoffError — never a crashed step loop.
        """
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        try:
            prompt_ids, committed, sampling, stops, wire_rid, t0 = (
                parse_handoff(body.get("handoff"))
            )
        except HandoffError as e:
            return _error(400, str(e))
        tool_name = body.get("tool_name")
        if tool_name is not None and not isinstance(tool_name, str):
            return _error(400, "'tool_name' must be a string")
        model = body.get("model") or self.engine.model_id
        rid = _request_id_from(request) or wire_rid
        try:
            # the gateway recomputes the REMAINING deadline budget onto the
            # header; it overrides the wire's original (now partly spent) one
            header_deadline = _deadline_from(request)
        except ValueError as e:
            return _error(400, str(e))
        if header_deadline is not None:
            sampling.deadline_ms = header_deadline
        # pages attachment: rides the handoff envelope itself (wire.py) —
        # anything non-dict is treated as absent and the adoption replays
        kv_pages = body.get("handoff", {}).get("kv_pages")
        if not isinstance(kv_pages, dict):
            kv_pages = None
        agen = self.engine.adopt_stream(
            prompt_ids, committed, sampling, stops,
            request_id=rid, emitted_at=t0, kv_pages=kv_pages,
        )
        completion_id = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        if body.get("stream"):
            return await self._stream_chat(
                request, completion_id, created, model,
                prompt_ids, sampling, stops,
                include_usage=True, request_id=rid, tool_name=tool_name,
                agen=agen, replay=bool(body.get("llmlb_replay")),
            )
        return await self._collect_chat_response(
            agen, completion_id, created, model, tool_name, rid
        )

    async def resume(self, request: web.Request) -> web.StreamResponse:
        """POST /v1/resume — continue a stream another engine started, from
        the ORIGINAL chat body plus the token ids already committed (durable
        streams, docs/resilience.md). This engine re-encodes the prompt with
        its own tokenizer (identical across engines serving one model),
        replays prompt+committed as a chunk prefill (the PR 10/11 park/adopt
        path — KV lands at identical absolute positions, greedy and seeded
        continuations are token-identical), and streams the FULL completion
        (committed + continuation) in the normal chat-completions shape; the
        gateway splices off the prefix its client already holds. Unlike
        /v1/handoff there is no wire sampling block: the chat body is the
        contract, so any tpu:// engine can adopt regardless of role."""
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        committed = body.get("committed_ids")
        if committed is None:
            committed = []
        if not isinstance(committed, list) or any(
            isinstance(t, bool) or not isinstance(t, int) for t in committed
        ):
            return _error(400, "'committed_ids' must be a list of token ids")
        try:
            prompt_ids, sampling, stops, tool_name, model = self._parse_chat(
                request, body
            )
        except ValueError as e:
            return _error(400, str(e))
        rid = _request_id_from(request)
        # optional pages payload pre-fetched by the gateway from the
        # draining origin's /v1/kv/export — lands instead of replaying
        kv_pages = body.get("kv_pages")
        if not isinstance(kv_pages, dict):
            kv_pages = None
        agen = self.engine.adopt_stream(
            prompt_ids, [int(t) for t in committed], sampling, stops,
            request_id=rid, kv_pages=kv_pages,
        )
        completion_id = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        if body.get("stream"):
            return await self._stream_chat(
                request, completion_id, created, model, prompt_ids, sampling,
                stops, include_usage=True, request_id=rid,
                tool_name=tool_name, agen=agen,
                replay=bool(body.get("llmlb_replay")),
            )
        return await self._collect_chat_response(
            agen, completion_id, created, model, tool_name, rid
        )

    # ----------------------------------------------------------- completions

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        prompt = body.get("prompt")
        if isinstance(prompt, list):
            if len(prompt) != 1 or not isinstance(prompt[0], str):
                return _error(400, "only a single string prompt is supported")
            prompt = prompt[0]
        if not isinstance(prompt, str) or not prompt:
            return _error(400, "'prompt' must be a non-empty string")
        model = body.get("model") or self.engine.model_id
        prompt_ids = self.engine.tokenizer.encode(prompt)
        sampling = self._sampling(body, default_max=16)
        sampling.deadline_ms = _deadline_from(request)  # middleware 400s bad values
        adapter, base = self._parse_lora(body)  # middleware 400s bad values
        if adapter is not None:
            sampling.lora = adapter
            model = base or model
        stops = _stops_from(body)
        completion_id = f"cmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        rid = _request_id_from(request)

        if body.get("stream"):
            resp = web.StreamResponse(
                status=200, headers={"Content-Type": "text/event-stream",
                                     **_rid_headers(rid)}
            )
            await resp.prepare(request)
            usage = _usage(len(prompt_ids), 0)
            finish = "stop"
            try:
                async for delta in self.engine.stream(prompt_ids, sampling,
                                                      stops, request_id=rid):
                    if delta.finish_reason is not None:
                        finish = delta.finish_reason
                        usage = _usage(delta.prompt_tokens, delta.completion_tokens)
                    if delta.text:
                        await _sse_send(
                            resp,
                            {
                                "id": completion_id,
                                "object": "text_completion",
                                "created": created,
                                "model": model,
                                "choices": [
                                    {"index": 0, "text": delta.text,
                                     "finish_reason": None}
                                ],
                            },
                        )
            except (EngineError, ValueError) as e:
                await _sse_send(resp, {"error": {"message": str(e)}})
                await resp.write(b"data: [DONE]\n\n")
                return resp
            await _sse_send(
                resp,
                {
                    "id": completion_id,
                    "object": "text_completion",
                    "created": created,
                    "model": model,
                    "choices": [{"index": 0, "text": "", "finish_reason": finish}],
                    "usage": usage,
                },
            )
            await resp.write(b"data: [DONE]\n\n")
            return resp

        result = await self.engine.complete(prompt_ids, sampling, stops,
                                            request_id=rid)
        return web.json_response(
            {
                "id": completion_id,
                "object": "text_completion",
                "created": created,
                "model": model,
                "choices": [
                    {
                        "index": 0,
                        "text": result.text,
                        "finish_reason": result.finish_reason,
                    }
                ],
                "usage": _usage(result.prompt_tokens, result.completion_tokens),
            }
        )

    # ------------------------------------------------------------- responses

    async def responses(self, request: web.Request) -> web.StreamResponse:
        """OpenAI Responses API — the reference's recommended text path."""
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        model = body.get("model") or self.engine.model_id
        input_ = body.get("input")
        if isinstance(input_, str):
            messages = [{"role": "user", "content": input_}]
        elif isinstance(input_, list):
            messages = [
                {"role": m.get("role", "user"), "content": m.get("content", "")}
                for m in input_
                if isinstance(m, dict)
            ]
        else:
            return _error(400, "'input' must be a string or message array")
        if body.get("instructions"):
            messages = [{"role": "system", "content": body["instructions"]}] + messages

        prompt_ids = self.engine.encode_chat(messages)
        sampling = self._sampling(body)
        sampling.deadline_ms = _deadline_from(request)
        response_id = f"resp_{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        rid = _request_id_from(request)

        def envelope(status: str, text: str, usage: dict | None) -> dict:
            return {
                "id": response_id,
                "object": "response",
                "created_at": created,
                "status": status,
                "model": model,
                "output": [
                    {
                        "type": "message",
                        "id": f"msg_{response_id}",
                        "role": "assistant",
                        "status": status,
                        "content": [
                            {"type": "output_text", "text": text, "annotations": []}
                        ],
                    }
                ],
                "usage": usage
                or {"input_tokens": 0, "output_tokens": 0, "total_tokens": 0},
            }

        if body.get("stream"):
            resp = web.StreamResponse(
                status=200, headers={"Content-Type": "text/event-stream",
                                     **_rid_headers(rid)}
            )
            await resp.prepare(request)

            async def event(name: str, payload: dict) -> None:
                data = json.dumps(payload, separators=(",", ":"))
                await resp.write(f"event: {name}\ndata: {data}\n\n".encode())

            await event(
                "response.created",
                {"type": "response.created",
                 "response": envelope("in_progress", "", None)},
            )
            text_parts: list[str] = []
            usage = None
            try:
                async for delta in self.engine.stream(
                    prompt_ids, sampling, _stops_from(body), request_id=rid
                ):
                    if delta.text:
                        text_parts.append(delta.text)
                        await event(
                            "response.output_text.delta",
                            {
                                "type": "response.output_text.delta",
                                "item_id": f"msg_{response_id}",
                                "output_index": 0,
                                "content_index": 0,
                                "delta": delta.text,
                            },
                        )
                    if delta.finish_reason is not None:
                        usage = {
                            "input_tokens": delta.prompt_tokens,
                            "output_tokens": delta.completion_tokens,
                            "total_tokens": (
                                delta.prompt_tokens + delta.completion_tokens
                            ),
                        }
            except (EngineError, ValueError) as e:
                await event(
                    "response.failed",
                    {
                        "type": "response.failed",
                        "response": {
                            "id": response_id,
                            "object": "response",
                            "status": "failed",
                            "error": {"message": str(e)},
                        },
                    },
                )
                return resp
            await event(
                "response.completed",
                {
                    "type": "response.completed",
                    "response": envelope("completed", "".join(text_parts), usage),
                },
            )
            return resp

        result = await self.engine.complete(prompt_ids, sampling,
                                            _stops_from(body), request_id=rid)
        usage = {
            "input_tokens": result.prompt_tokens,
            "output_tokens": result.completion_tokens,
            "total_tokens": result.prompt_tokens + result.completion_tokens,
        }
        return web.json_response(envelope("completed", result.text, usage),
                                 headers=_rid_headers(rid))


@web.middleware
async def error_middleware(request: web.Request, handler):
    """Normalize engine/validation failures to OpenAI-style JSON errors.
    The outermost middleware, so also where a request's way in begins: the
    handler's entry is stamped for `accept` (service.RECEIVED_AT)."""
    RECEIVED_AT.set(stepstats._now())
    try:
        return await handler(request)
    except web.HTTPException:
        raise
    except ValueError as e:
        return _error(400, str(e))
    except EngineError as e:
        return _error(500, str(e), "server_error")
    except Exception:
        log.exception("unhandled error serving %s", request.path)
        return _error(500, "internal server error", "server_error")


def create_engine_app(engine: Engine, *, owns_engine: bool = True,
                      asr=None, tts=None, image=None) -> web.Application:
    api = EngineAPI(engine, asr=asr, tts=tts, image=image)

    @web.middleware
    async def drain_middleware(request: web.Request, handler):
        """Admission gate + in-flight ledger for graceful drain: while
        draining, new /v1 work 503s with an honest Retry-After (the grace
        remaining); accepted /v1 POSTs register their transport so the
        post-grace abort can cut stragglers for gateway-side resume. Read
        surfaces (/api/health, /metrics) always answer — the health checker
        must be able to see the draining advertisement."""
        if (request.method == "POST" and request.path.startswith("/v1/")
                and request.path != "/v1/kv/export"):
            # /v1/kv/export is exempt on purpose: it exists FOR the drain
            # window — the gateway collects parked KV pages from a draining
            # engine before resuming the stream elsewhere
            drain = api.drain
            if drain.draining:
                return web.json_response(
                    {"error": {
                        "message": "engine is draining; retry on another "
                                   "endpoint",
                        "type": "overloaded_error", "code": "draining",
                    }},
                    status=503,
                    headers={"Retry-After": str(drain.retry_after_s())},
                )
            drain.track(request.transport)
            try:
                return await handler(request)
            finally:
                drain.untrack(request.transport)
        return await handler(request)

    app = web.Application(client_max_size=KV_BODY_BYTES,
                          middlewares=[error_middleware, drain_middleware])
    stream_stats = engine.core.metrics.stream

    async def note_way_out(request: web.Request, response) -> None:
        response[_WAY_OUT] = (stream_stats, request.protocol)

    app.on_response_prepare.append(note_way_out)
    app.router.add_get("/v1/models", api.list_models)
    app.router.add_post("/v1/chat/completions", api.chat_completions)
    app.router.add_post("/v1/handoff", api.handoff_adopt)
    app.router.add_post("/v1/handoff/prefill", api.handoff_prefill)
    app.router.add_post("/v1/resume", api.resume)
    app.router.add_post("/v1/kv/export", api.kv_export)
    app.router.add_post("/v1/completions", api.completions)
    app.router.add_post("/v1/responses", api.responses)
    app.router.add_post("/v1/embeddings", api.embeddings)
    app.router.add_post("/v1/audio/transcriptions", api.audio_transcriptions)
    app.router.add_post("/v1/audio/speech", api.audio_speech)
    app.router.add_post("/v1/images/generations", api.images_generations)
    app.router.add_get("/api/health", api.health)
    app.router.add_post("/api/drain", api.drain_control)
    app.router.add_get("/metrics", api.prometheus_metrics)
    app.router.add_get("/api/system", api.system)
    app.router.add_get("/api/steps", api.steps)
    app.router.add_get("/api/requests/{request_id}/timeline",
                       api.request_timeline)
    app.router.add_post("/api/profile", api.profile_control)
    app.router.add_get("/api/profile", api.profile_status)
    app.router.add_get("/api/profile/{capture_id}", api.profile_artifact)
    app.router.add_post("/debug/profile", api.debug_profile)

    if owns_engine:
        async def on_shutdown(app):
            # Graceful path first (SIGTERM lands here through aiohttp's
            # shutdown hooks): drain — wait the grace for in-flight decodes,
            # park the rest, abort their connections for gateway-side
            # resume — and only THEN tear the engine core down.
            # engine.shutdown() is no longer the first move.
            api.drain.start()
            await api.drain.wait()
            engine.shutdown()

        app.on_shutdown.append(on_shutdown)
    return app


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description="llmlb_tpu inference engine")
    parser.add_argument("--preset", default="debug-tiny")
    parser.add_argument("--checkpoint", default=None)
    parser.add_argument("--model-id", default=None)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8100)
    parser.add_argument("--num-slots", type=int, default=8)
    # Default sized so a 4k-token prompt serves out of the box via chunked
    # prefill. Memory math: scheduler.kv_pool_bytes —
    # 8 slots x 4096 is 4.3 GiB for llama-3-8b, 1.5 GiB for tinyllama-1.1b.
    # EngineCore clamps to the model's max_position_embeddings.
    parser.add_argument("--slot-capacity", type=int, default=4096)
    parser.add_argument(
        "--prefill-buckets", default=None,
        help="comma-separated one-shot prefill lengths (default 32..512); "
             "prompts beyond the largest run through chunked prefill",
    )
    parser.add_argument(
        "--prefill-chunk-budget", type=int, default=None,
        help="max prompt tokens prefilled per step-loop iteration while "
             "other slots are decoding (default 0 = uncapped; also via "
             "LLMLB_PREFILL_CHUNK_BUDGET) — bounds decoder inter-token "
             "latency regardless of arriving prompt sizes "
             "(docs/scheduling.md)",
    )
    parser.add_argument(
        "--decode-burst", type=int, default=None,
        help="decode+sample steps fused per device dispatch (default: "
             "8 on TPU, 1 elsewhere; also via LLMLB_DECODE_BURST)",
    )
    parser.add_argument(
        "--init-timeout", type=float, default=None,
        help="bound on the first device touch: if jax.devices() has not "
             "returned after this many seconds, dump every thread's stack "
             "to stderr and exit instead of hanging silently (default 600; "
             "0 disables; also via LLMLB_INIT_TIMEOUT)",
    )
    parser.add_argument(
        "--kv-page-size", type=int, default=None,
        help="tokens per KV page (default 128; see "
             "docs/kv-cache.md for the waste-vs-overhead tradeoff)",
    )
    parser.add_argument(
        "--kv-pages", type=int, default=None,
        help="total pages in the KV pool (default: num_slots x "
             "slot_capacity worth; raise num_slots "
             "against the same pool to serve more concurrent short "
             "requests)",
    )
    parser.add_argument(
        "--quantize", choices=("off", "weights", "kv", "all"), default=None,
        help="int8 quantization (default off; also via LLMLB_QUANTIZE): "
             "'weights' = per-output-channel int8 projection matrices, "
             "'kv' = int8 KV pages + per-vector scales, "
             "'all' = both — halves the HBM bytes each covers "
             "(docs/quantization.md); bf16 output is bit-identical when off",
    )
    parser.add_argument(
        "--spec-decode", choices=("on", "off"), default=None,
        help="speculative decoding default for requests without their own "
             "'speculative' knob (default off; also via LLMLB_SPEC_DECODE): "
             "prompt-lookup drafting + batched K+1-token verification "
             "(docs/speculative.md)",
    )
    parser.add_argument(
        "--spec-max-draft", type=int, default=None,
        help="max draft tokens per verify step (default 4, cap 16; also via "
             "LLMLB_SPEC_MAX_DRAFT) — the verify chunk width, one compile "
             "per context-window bucket",
    )
    parser.add_argument(
        "--spec-ngram", type=int, default=None,
        help="longest n-gram the prompt-lookup drafter matches on (default "
             "3; also via LLMLB_SPEC_NGRAM)",
    )
    parser.add_argument(
        "--role", choices=("both", "split", "prefill", "decode"),
        default=None,
        help="serving role (default both; also via LLMLB_ROLE): 'split' "
             "runs an in-process prefill pool + decode pool over one paged "
             "KV pool with page-id handoff; 'prefill'/'decode' advertise a "
             "cross-process role to the gateway, which steers prefill-heavy "
             "requests to prefill engines and hands the stream to a decode "
             "engine over the /v1/handoff wire (docs/disaggregation.md)",
    )
    parser.add_argument(
        "--disagg-prefill-slots", type=int, default=None,
        help="slots in the prefill pool under --role split (default "
             "num_slots // 4, min 1; also via LLMLB_DISAGG_PREFILL_SLOTS); "
             "the remaining slots form the decode pool",
    )
    parser.add_argument(
        "--lora-dir", default=None,
        help="directory of LoRA adapters (one PEFT-layout subdirectory per "
             "adapter; also via LLMLB_LORA_DIR). Enables multi-LoRA "
             "serving: per-request adapters via the 'lora' field or a "
             "'model:adapter' name, batched mixed-adapter decode, LRU "
             "hot-load/evict (docs/lora.md). Default off",
    )
    parser.add_argument(
        "--lora-max-adapters", type=int, default=None,
        help="device-resident adapter pool slots (default 8; also via "
             "LLMLB_LORA_MAX_ADAPTERS) — adapters beyond this LRU-evict "
             "when idle; HBM cost scales linearly (docs/lora.md)",
    )
    parser.add_argument(
        "--lora-rank-cap", type=int, default=None,
        help="max adapter rank the pool holds (default 16; also via "
             "LLMLB_LORA_RANK_CAP) — higher-rank adapters are refused "
             "with a 400; lower ranks zero-pad exactly",
    )
    parser.add_argument(
        "--prefix-cache", choices=("on", "off"), default=None,
        help="radix-tree prefix KV reuse across requests (default on; "
             "also via LLMLB_PREFIX_CACHE=0)",
    )
    parser.add_argument(
        "--prefix-cache-slots", type=int, default=None,
        help="max cached prefixes (prefix-cache entries; donors pin pages, "
             "not slots; default num_slots // 2, capped at num_slots - 1)",
    )
    parser.add_argument(
        "--min-prefix-len", type=int, default=None,
        help="shortest prompt prefix worth caching, in tokens "
             "(default: the smallest prefill bucket)",
    )
    # modality services (checkpoint dir, or "random" for test weights)
    parser.add_argument("--asr", default=None,
                        help="whisper checkpoint dir or 'random'")
    parser.add_argument("--tts", default=None,
                        help="TTS checkpoint dir or 'random'")
    parser.add_argument("--image", default=None,
                        help="diffusion checkpoint dir or 'random'")
    args = parser.parse_args(argv)
    extra = {}
    if args.prefill_buckets:
        try:
            buckets = tuple(
                int(b) for b in args.prefill_buckets.split(",") if b.strip()
            )
        except ValueError:
            parser.error(
                f"--prefill-buckets must be comma-separated integers, "
                f"got {args.prefill_buckets!r}"
            )
        if not buckets:
            parser.error("--prefill-buckets must name at least one length")
        extra["prefill_buckets"] = buckets
    if args.decode_burst is not None:
        extra["decode_burst"] = max(1, args.decode_burst)
    if args.prefill_chunk_budget is not None:
        extra["prefill_chunk_budget"] = max(0, args.prefill_chunk_budget)
    if args.kv_page_size is not None:
        extra["kv_page_size"] = max(1, args.kv_page_size)
    if args.kv_pages is not None:
        extra["kv_pages"] = max(2, args.kv_pages)
    if args.quantize is not None:
        extra["quantize"] = args.quantize
    if args.spec_decode is not None:
        extra["spec_decode"] = args.spec_decode == "on"
    if args.spec_max_draft is not None:
        extra["spec_max_draft"] = max(1, args.spec_max_draft)
    if args.spec_ngram is not None:
        extra["spec_ngram"] = max(1, args.spec_ngram)
    if args.role is not None:
        extra["role"] = args.role
    if args.disagg_prefill_slots is not None:
        extra["disagg_prefill_slots"] = max(1, args.disagg_prefill_slots)
    if args.lora_dir is not None:
        extra["lora_dir"] = args.lora_dir
    if args.lora_max_adapters is not None:
        extra["lora_max_adapters"] = max(1, args.lora_max_adapters)
    if args.lora_rank_cap is not None:
        extra["lora_rank_cap"] = max(1, args.lora_rank_cap)
    if args.prefix_cache is not None:
        extra["prefix_cache"] = args.prefix_cache == "on"
    if args.prefix_cache_slots is not None:
        extra["prefix_cache_slots"] = max(0, args.prefix_cache_slots)
    if args.min_prefix_len is not None:
        extra["min_prefix_len"] = max(1, args.min_prefix_len)

    # Shared logging subsystem (gateway/logging_setup.py): level/format
    # knobs + the worker-id field apply to engine processes too. No file
    # sink here — engines run under their own supervisors that capture
    # stderr.
    from llmlb_tpu.gateway.logging_setup import init_logging

    init_logging(file_sink=False)
    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    cache_dir = configure_compile_cache()
    # Multi-host bring-up must precede the first jax backend use. No-op
    # unless LLMLB_COORDINATOR/LLMLB_NUM_HOSTS or LLMLB_DISTRIBUTED are set.
    from llmlb_tpu.parallel.distributed import init_from_env

    init_from_env()
    devices = resolve_backend(args.init_timeout)
    from llmlb_tpu.native import ensure_native_built

    ensure_native_built()  # build before serving; loader itself never builds
    if args.checkpoint:
        engine = Engine.from_checkpoint(
            args.checkpoint, model_id=args.model_id,
            num_slots=args.num_slots, slot_capacity=args.slot_capacity,
            **extra,
        )
    else:
        engine = Engine.from_preset(
            args.preset, model_id=args.model_id,
            num_slots=args.num_slots, slot_capacity=args.slot_capacity,
            **extra,
        )

    import jax

    from llmlb_tpu.ops.attention import attention_mode

    log.info(
        "serving %s on %s: %d x %s, mesh %s, attention %s, compile cache %s",
        engine.model_id, devices[0].platform, len(devices),
        devices[0].device_kind, dict(engine.core.mesh.shape),
        attention_mode(), cache_dir,
    )
    if jax.process_count() > 1 and jax.process_index() != 0:
        # Follower host of a multi-host engine: the step thread runs the
        # lockstep loop (engine/multihost.py) dispatching the same collective
        # programs the leader plans; HTTP (and the modality engines, which
        # only HTTP reaches) belong to the leader.
        log.info("multihost follower: serving loop only (leader owns HTTP)")
        engine.core._thread.join()
        return

    asr = tts = image = None
    if args.asr:
        from llmlb_tpu.engine.asr import AsrEngine

        asr = (AsrEngine.from_random() if args.asr == "random"
               else AsrEngine.from_checkpoint(args.asr))
    if args.tts:
        from llmlb_tpu.engine.tts import TtsEngine

        tts = (TtsEngine.from_random() if args.tts == "random"
               else TtsEngine.from_checkpoint(args.tts))
    if args.image:
        from llmlb_tpu.engine.image import ImageEngine

        image = (ImageEngine.from_random() if args.image == "random"
                 else ImageEngine.from_checkpoint(args.image))

    web.run_app(
        create_engine_app(engine, asr=asr, tts=tts, image=image),
        host=args.host, port=args.port,
    )


if __name__ == "__main__":
    main()
