"""High-level engine service: text in, streamed text out.

Bridges the HTTP layer to the EngineCore step loop: chat templating, token
encode/decode, stop-sequence handling, usage accounting, and async iteration
over the core's thread-side event queues.
"""

from __future__ import annotations

import asyncio
import contextvars
import dataclasses
import os
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import AsyncIterator


from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import (
    EngineCore,
    Request,
    SamplingParams,
    event_tokens,
)
from llmlb_tpu.engine.streamstats import StreamStats, frame_annotation
from llmlb_tpu.engine.tokenizer import (
    ByteTokenizer,
    HFTokenizer,
    IncrementalDetokenizer,
    Tokenizer,
)


# The instant the HTTP handler of the request being served was entered
# (stepstats._now; server.error_middleware sets it): where `accept`, the
# first stage of a request's way in, begins (docs/tracing.md). A context
# variable, so that every handler's every path to a Request carries it with
# no argument; None for a request that came through no handler.
RECEIVED_AT: contextvars.ContextVar[float | None] = contextvars.ContextVar(
    "llmlb_received_at", default=None)


def _quantize_weights(core_kwargs: dict) -> bool:
    """Resolve whether the construction path should int8-quantize weights
    while streaming the checkpoint (same knob the core itself parses)."""
    from llmlb_tpu.quant import parse_quant_mode

    return parse_quant_mode(core_kwargs.get("quantize")).weights


@dataclasses.dataclass
class StreamDelta:
    text: str = ""
    finish_reason: str | None = None
    prompt_tokens: int = 0
    completion_tokens: int = 0
    ttft_s: float | None = None
    # Token ids newly committed since the previous delta (durable streams,
    # docs/resilience.md): the HTTP layer ships these as gateway-internal
    # `llmlb.replay` SSE frames when the request was armed with
    # `llmlb_replay`, BEFORE the text they produced — so the gateway's
    # replay ledger always covers every character the client has seen.
    token_ids: list[int] = dataclasses.field(default_factory=list)


class Engine:
    """One served model: config + weights + tokenizer + scheduler core."""

    def __init__(
        self,
        model_id: str,
        core: EngineCore,
        tokenizer: Tokenizer,
    ):
        self.model_id = model_id
        self.core = core
        self.tokenizer = tokenizer
        # Event bridging blocks a thread per in-flight stream; size accordingly.
        self._executor = ThreadPoolExecutor(
            max_workers=max(32, core.num_slots * 4),
            thread_name_prefix="engine-events",
        )
        # Grammar-constraint compiler (llmlb_tpu/structured): owned here
        # because it needs the tokenizer, installed on the core so multihost
        # followers (which receive only the JSON spec over the plan wire)
        # can rebuild the token-DFA themselves.
        from llmlb_tpu.structured import ConstraintCompiler

        self.constraint_compiler = ConstraintCompiler(
            tokenizer, core.cfg.vocab_size, metrics=core.metrics
        )
        core.constraint_compiler = self.constraint_compiler
        # the way out's counters (engine/streamstats.py) are the core's, so
        # that /api/health serves them; a core with no metrics (a scripted
        # stand-in) gets a block nobody reads
        self._stream_stats = (core.metrics.stream if core.metrics is not None
                              else StreamStats())

    # ------------------------------------------------------------ construction

    @classmethod
    def from_preset(
        cls,
        preset: str,
        *,
        model_id: str | None = None,
        checkpoint_dir: str | None = None,
        **core_kwargs,
    ) -> "Engine":
        """Build from a named preset; random weights unless checkpoint_dir."""
        cfg = get_preset(preset)
        params = None
        tokenizer: Tokenizer
        if checkpoint_dir:
            from llmlb_tpu.engine.weights import load_checkpoint, load_config

            cfg = load_config(checkpoint_dir, dtype=cfg.dtype)
            tokenizer = HFTokenizer(checkpoint_dir)
            params = load_checkpoint(
                checkpoint_dir, cfg,
                quantize_weights=_quantize_weights(core_kwargs),
            )
        else:
            tokenizer = ByteTokenizer(cfg.vocab_size)
        core = EngineCore(
            cfg, params, eos_id=tokenizer.eos_id, **core_kwargs
        )
        core.start()
        return cls(model_id or preset, core, tokenizer)

    @classmethod
    def from_checkpoint(cls, checkpoint_dir: str, *, model_id: str | None = None,
                        **core_kwargs) -> "Engine":
        from llmlb_tpu.engine.weights import load_checkpoint, load_config

        cfg = load_config(checkpoint_dir)
        tokenizer = HFTokenizer(checkpoint_dir)
        # int8 weight quantization happens per tensor WHILE streaming the
        # shards (host RAM and H2D both move the int8 bytes); the core's
        # own quantize pass is idempotent over the result
        params = load_checkpoint(
            checkpoint_dir, cfg,
            quantize_weights=_quantize_weights(core_kwargs),
        )
        core = EngineCore(cfg, params, eos_id=tokenizer.eos_id, **core_kwargs)
        core.start()
        return cls(
            model_id or os.path.basename(checkpoint_dir.rstrip("/")),
            core,
            tokenizer,
        )

    def shutdown(self) -> None:
        self.core.stop()
        self._executor.shutdown(wait=False, cancel_futures=True)

    # --------------------------------------------------------------- serving

    def encode_chat(self, messages: list[dict]) -> list[int]:
        return self.tokenizer.encode(self.tokenizer.apply_chat_template(messages))

    async def stream(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        stop: list[str] | None = None,
        request_id: str | None = None,
    ) -> AsyncIterator[StreamDelta]:
        """Submit and stream deltas. Final delta carries finish_reason + usage.

        `request_id` (the gateway's X-Request-Id) prefixes the scheduler
        request id, so engine-side logs/events join the gateway trace. A
        unique suffix is always appended: the raw header is client-controlled
        and the scheduler's cancellation bookkeeping is keyed by request_id,
        so two in-flight requests must never share one.

        Stop sequences may straddle token/delta boundaries, so the last
        `max(len(stop)) - 1` characters are held back until the stream resolves;
        a stop hit truncates before anything past it is emitted. Early exit
        (stop hit, client gone) cancels the request so its slot frees promptly.
        """
        if request_id:
            request = Request(
                prompt_ids=prompt_ids, sampling=sampling,
                request_id=f"{request_id}.{uuid.uuid4().hex[:8]}",
            )
        else:
            request = Request(prompt_ids=prompt_ids, sampling=sampling)
        request.received_at = RECEIVED_AT.get()
        loop = asyncio.get_running_loop()
        if sampling.constraint is not None:
            # Compile (or LRU-fetch) the token-DFA BEFORE submit, off the
            # event loop AND off the engine step loop: a cold 128k-vocab
            # compile must stall neither other HTTP requests nor in-flight
            # decode. Invalid specs raise here (ValueError →
            # UnsupportedSchemaError included) and never reach a slot.
            request.compiled_constraint = await loop.run_in_executor(
                self._executor,
                self.constraint_compiler.compile_spec,
                sampling.constraint,
            )
        if sampling.lora:
            # Pin + hot-load the adapter off the event loop (first use reads
            # safetensors from disk) AND off the step loop; submit's own
            # prepare_lora call is then an idempotent lookup.
            await loop.run_in_executor(
                self._executor, self.core.prepare_lora, request
            )
        try:
            self.core.submit(request)
        except BaseException:
            # a pre-pinned adapter must not leak when the submit never
            # reaches a queue (validation refusal, or cancellation landing
            # between the prepare above and here); idempotent no-op when
            # nothing was acquired
            self.core._release_lora(request)
            raise

        detok = IncrementalDetokenizer(self.tokenizer)
        stop = [s for s in (stop or []) if s]
        holdback = max((len(s) for s in stop), default=1) - 1
        acc = ""  # decoded text; [:emitted] has been yielded
        emitted = 0
        completion_tokens = 0
        # ids committed since the last yielded delta: they ride the NEXT
        # delta (durable streams — the gateway's replay ledger)
        pending_ids: list[int] = []
        ttft: float | None = None  # attached to the first yielded delta

        finished = False

        def final(text: str, reason: str) -> StreamDelta:
            return StreamDelta(
                text=text,
                finish_reason=reason,
                prompt_tokens=len(prompt_ids),
                completion_tokens=completion_tokens,
                ttft_s=ttft,
                token_ids=pending_ids,
            )

        # the way out, counted (engine/streamstats.py): every event's wait
        # for this consumer (the queue and the hop to this loop), every
        # frame from the event's arrival to the resumption after its yield,
        # the whole stream at its last frame
        stats = self._stream_stats
        events = request.events
        take = events.taker()
        stats.open(events)
        first_frame = True  # the way in's last stage: the first delta out
        try:
            while True:
                stamp, (kind, value) = await loop.run_in_executor(
                    self._executor, take
                )
                with frame_annotation():
                    tokens = event_tokens(kind, value)
                    t_got = stats.got(stamp, events, len(tokens))
                    if kind == "error":
                        raise EngineError(str(value))
                    if tokens:
                        if not completion_tokens and request.first_token_at:
                            ttft = (request.first_token_at
                                    - request.submitted_at)
                        text, taken = _event_text(detok, tokens, stop, acc)
                        acc += text
                        completion_tokens += taken
                        pending_ids.extend(tokens[:taken])
                    elif kind == "done":
                        acc += detok.flush()

                    hit = _find_stop(acc, stop)
                    if hit is not None:
                        finished = True
                        request.cancel()
                        yield final(acc[emitted:hit], "stop")
                        return
                    if kind == "done":
                        finished = True
                        last = final(acc[emitted:], str(value))
                        yield last
                        if last.text:  # else the handler wrote no frame
                            stats.frame(t_got, stamp if first_frame else None)
                        stats.finished(request)
                        return
                    boundary = max(emitted, len(acc) - holdback)
                    if boundary > emitted:
                        delta = StreamDelta(text=acc[emitted:boundary],
                                            ttft_s=ttft,
                                            token_ids=pending_ids)
                        pending_ids = []
                        ttft = None  # report once
                        emitted = boundary
                        yield delta
                        stats.frame(t_got, stamp if first_frame else None)
                        first_frame = False
        finally:
            stats.close(events)
            if not finished:
                request.cancel()

    # -------------------------------------------------- cross-process handoff

    async def prefill_handoff(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        emit_tokens: int = 1,
        request_id: str | None = None,
    ) -> tuple[list[int], str | None, dict | None]:
        """Prefill-role side of the cross-process handoff
        (docs/disaggregation.md): run admission + prefill and commit the
        first `emit_tokens` tokens, then stop. Returns ``(committed_ids,
        finish_reason, kv_pages)`` — finish_reason is None when the request
        has more to generate (the handoff case: the caller wraps the
        committed ids in a wire payload for a decode engine to adopt), or
        the natural finish ("stop"/"length") when the request completed
        inside the committed window and no handoff is needed. ``kv_pages``
        is the serialized KV page payload (engine/kv_transfer.py) in the
        handoff case when shipping is enabled, else None — the adopter
        lands it instead of re-prefilling.

        Token-level on purpose: the committed ids ride the wire verbatim and
        the ADOPTING engine owns detokenization and stop sequences, so its
        incremental detokenizer sees the exact same token sequence an
        uninterrupted run would have."""
        k = max(1, int(emit_tokens))
        bounded = dataclasses.replace(
            sampling, max_tokens=min(sampling.max_tokens, k)
        )
        request = Request(
            prompt_ids=prompt_ids, sampling=bounded,
            request_id=(f"{request_id}.{uuid.uuid4().hex[:8]}"
                        if request_id else uuid.uuid4().hex),
            received_at=RECEIVED_AT.get(),
        )
        # ask the scheduler to serialize this stream's KV pages at the
        # emit-budget finish, before the pool reclaims them — the payload
        # rides the handoff envelope so the adopter can skip its replay
        # prefill entirely (docs/kv-cache.md)
        request.export_kv = self.core.kv_ship
        loop = asyncio.get_running_loop()
        if sampling.constraint is not None:
            request.compiled_constraint = await loop.run_in_executor(
                self._executor,
                self.constraint_compiler.compile_spec,
                sampling.constraint,
            )
        if sampling.lora:
            await loop.run_in_executor(
                self._executor, self.core.prepare_lora, request
            )
        try:
            self.core.submit(request)
        except BaseException:
            self.core._release_lora(request)  # see stream(): no pin leaks
            raise
        committed: list[int] = []
        finish: str | None = None
        try:
            while True:
                kind, value = await loop.run_in_executor(
                    self._executor, request.events.get
                )
                if kind == "error":
                    raise EngineError(str(value))
                if kind == "done":
                    finish = str(value)
                    break
                committed.extend(event_tokens(kind, value))
        finally:
            if finish is None:
                request.cancel()
        if (finish == "length" and len(committed) >= k
                and sampling.max_tokens > k):
            # the bounded run was cut at the emit budget, not a real finish:
            # this stream continues on whichever engine adopts it
            self.core.metrics.record_handoff("emitted")
            if self.core.flightrec.enabled:
                self.core.flightrec.emit(request.request_id, "handoff_emitted",
                                         tokens=len(committed))
            return committed, None, request.kv_export
        return committed, finish, None

    async def adopt_stream(
        self,
        prompt_ids: list[int],
        committed_ids: list[int],
        sampling: SamplingParams,
        stop: list[str] | None = None,
        request_id: str | None = None,
        emitted_at: float = 0.0,
        kv_pages: dict | None = None,
    ) -> AsyncIterator[StreamDelta]:
        """Decode-pool side of the cross-process handoff: adopt a stream a
        prefill engine started, by replaying prompt + committed tokens as a
        chunk-prefill (the PR 10 park/resume path — KV lands at identical
        absolute positions, so greedy and seeded-stochastic continuations
        are token-identical to an uninterrupted run) and then decoding the
        remainder here.

        When ``kv_pages`` carries a serialized page payload from the origin
        (engine/kv_transfer.py) and it is compatible with THIS pool, the
        replay prefill is skipped entirely: the pages land H2D and the
        stream re-enters decode directly. Any mismatch — version skew,
        dtype, page geometry, shipping disabled here — falls back to the
        replay path with a reason-labeled counter; a bad payload is never a
        client-visible error.

        The full text (committed + continuation) is emitted: the prefill
        side never detokenized, so this engine's incremental detokenizer
        and stop-sequence scan see the stream exactly as `--role both`
        would have."""
        from llmlb_tpu.engine.scheduler import ParkedState
        from llmlb_tpu.structured import ConstraintState

        loop = asyncio.get_running_loop()
        compiled = None
        cursor = None
        if sampling.constraint is not None:
            compiled = await loop.run_in_executor(
                self._executor,
                self.constraint_compiler.compile_spec,
                sampling.constraint,
            )
            # Rebuild the grammar cursor at its handoff position: the FSM
            # re-walks the committed tokens (a fresh start-state cursor
            # would mask the continuation as if at the string beginning —
            # the PR 10 park bug, cross-process edition).
            cursor = ConstraintState(compiled)
            for t in committed_ids:
                cursor.advance(int(t))
        drafter = None
        spec_k = 0
        core = self.core
        if core._spec_available:
            knobs = sampling.speculative
            knobs = knobs if isinstance(knobs, dict) else {}
            if bool(knobs.get("enabled", core.spec.enabled)):
                from llmlb_tpu.spec import PromptLookupDrafter

                try:
                    want = int(knobs.get("max_draft_tokens")
                               or core.spec.max_draft_tokens)
                except (TypeError, ValueError):
                    want = core.spec.max_draft_tokens
                spec_k = max(1, min(want, core.spec.max_draft_tokens))
                # index prompt + committed: exactly the state the prefill
                # engine's drafter held at the handoff point
                drafter = PromptLookupDrafter(
                    prompt_ids, max_ngram=core.spec.max_ngram,
                    min_ngram=core.spec.min_ngram,
                )
                for t in committed_ids:
                    drafter.append(int(t))

        detok = IncrementalDetokenizer(self.tokenizer)
        stop = [s for s in (stop or []) if s]
        holdback = max((len(s) for s in stop), default=1) - 1
        acc = detok.extend([int(t) for t in committed_ids])
        emitted = 0
        completion_tokens = len(committed_ids)
        # replayed ids count as committed here too: a SECOND failover from
        # this engine must replay the full sequence (durable streams)
        pending_ids: list[int] = [int(t) for t in committed_ids]
        ttft: float | None = None
        finished = False

        def final(text: str, reason: str) -> StreamDelta:
            return StreamDelta(
                text=text, finish_reason=reason,
                prompt_tokens=len(prompt_ids),
                completion_tokens=completion_tokens,
                ttft_s=ttft,
                token_ids=pending_ids,
            )

        # the wire stamp is time.time() (wall clock — the only clock two
        # processes share; same-host skew caveat in docs/disaggregation.md),
        # so the latency diff must stay in the same clock domain
        latency = max(0.0, time.time() - emitted_at) if emitted_at else None
        core.metrics.record_handoff("adopted", latency)
        if request_id and core.flightrec.enabled:
            attrs = {"committed": len(committed_ids)}
            if latency is not None:
                attrs["wire_latency_s"] = round(latency, 6)
            core.flightrec.emit(request_id, "adopted", **attrs)

        # A handoff that is already terminal (stop string inside the
        # committed text, or a payload whose committed run used up the
        # whole budget) finishes here without touching the step loop.
        hit = _find_stop(acc, stop)
        if hit is not None:
            # truncation lands at `hit`, before anything flush could append
            yield final(acc[:hit], "stop")
            return
        if completion_tokens >= sampling.max_tokens:
            # terminal without further pushes: drain the detokenizer's
            # held-back bytes exactly like the stream path does on "done"
            acc += detok.flush()
            yield final(acc, "length")
            return

        kv_restore = None
        if kv_pages is not None:
            if not core.kv_ship:
                # this engine cannot land pages (knob off, multihost,
                # split prefill role): replay, with the reason
                core.metrics.record_kv_ship_fallback("disabled")
            elif not committed_ids:
                # zero committed tokens: the faithful continuation is the
                # activation-sample path — replay is already exact there
                core.metrics.record_kv_ship_fallback("capacity")
            else:
                from llmlb_tpu.engine.kv_transfer import (
                    KVTransferError, parse_kv_payload,
                )

                try:
                    parsed = await loop.run_in_executor(
                        self._executor, parse_kv_payload, kv_pages
                    )
                except KVTransferError as e:
                    core.metrics.record_kv_ship_fallback(e.reason)
                except Exception:
                    core.metrics.record_kv_ship_fallback("error")
                else:
                    reason = core.kv_restore_reason(parsed.header)
                    if reason is not None:
                        core.metrics.record_kv_ship_fallback(reason)
                    else:
                        kv_restore = parsed
        elif core.kv_ship:
            # shipping is on but the origin sent nothing (old peer, or a
            # killed engine whose export vanished with it): count it so an
            # operator can see replays that SHOULD have been page moves
            core.metrics.record_kv_ship_fallback("absent")

        request = Request(
            prompt_ids=list(prompt_ids), sampling=sampling,
            request_id=(f"{request_id}.{uuid.uuid4().hex[:8]}"
                        if request_id else uuid.uuid4().hex),
            compiled_constraint=compiled,
            parked=ParkedState(
                generated=len(committed_ids), tokens=list(committed_ids),
                constraint=cursor, drafter=drafter, spec_k=spec_k,
            ),
            kv_restore=kv_restore,
            received_at=RECEIVED_AT.get(),
        )
        if sampling.lora:
            # adoption replays prompt+committed WITH the adapter — the
            # resumed continuation must read the same wq/wk/wv deltas
            await loop.run_in_executor(
                self._executor, core.prepare_lora, request
            )
        try:
            core.submit(request)
        except BaseException:
            core._release_lora(request)  # see stream(): no pin leaks
            raise
        stats = self._stream_stats  # as in stream(): the way out, counted
        events = request.events
        take = events.taker()
        stats.open(events)
        try:
            while True:
                stamp, (kind, value) = await loop.run_in_executor(
                    self._executor, take
                )
                with frame_annotation():
                    tokens = event_tokens(kind, value)
                    t_got = stats.got(stamp, events, len(tokens))
                    if kind == "error":
                        raise EngineError(str(value))
                    if tokens:
                        if ttft is None and request.first_token_at:
                            ttft = (request.first_token_at
                                    - request.submitted_at)
                        text, taken = _event_text(detok, tokens, stop, acc)
                        acc += text
                        completion_tokens += taken
                        pending_ids.extend(tokens[:taken])
                    elif kind == "done":
                        acc += detok.flush()

                    hit = _find_stop(acc, stop)
                    if hit is not None:
                        finished = True
                        request.cancel()
                        yield final(acc[emitted:hit], "stop")
                        return
                    if kind == "done":
                        finished = True
                        last = final(acc[emitted:], str(value))
                        yield last
                        if last.text:  # else the handler wrote no frame
                            stats.frame(t_got)
                        stats.finished(request)
                        return
                    boundary = max(emitted, len(acc) - holdback)
                    if boundary > emitted:
                        delta = StreamDelta(text=acc[emitted:boundary],
                                            ttft_s=ttft,
                                            token_ids=pending_ids)
                        pending_ids = []
                        emitted = boundary
                        yield delta
                        stats.frame(t_got)
        finally:
            stats.close(events)
            if not finished:
                request.cancel()

    async def complete(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        stop: list[str] | None = None,
        request_id: str | None = None,
    ) -> StreamDelta:
        """Non-streaming: collect the full completion."""
        text = []
        final: StreamDelta | None = None
        async for delta in self.stream(prompt_ids, sampling, stop,
                                       request_id=request_id):
            text.append(delta.text)
            if delta.finish_reason is not None:
                final = delta
        assert final is not None
        return dataclasses.replace(final, text="".join(text))

    # One embed forward never exceeds this many rows: keeps a single request
    # from monopolizing HBM/compile time (generation is bounded by num_slots;
    # this is the embedding-path equivalent).
    _EMBED_CHUNK = 64
    MAX_EMBED_INPUTS = 2048  # request-level cap, matches OpenAI's limit

    def _embed_sync(self, batch_ids: list[list[int]]) -> "list[list[float]]":
        import numpy as np

        results: list[list[float]] = []
        for start in range(0, len(batch_ids), self._EMBED_CHUNK):
            chunk = batch_ids[start : start + self._EMBED_CHUNK]
            n = len(chunk)
            longest = max(len(x) for x in chunk)
            # pow2 buckets on BOTH dims keep the compile count logarithmic;
            # padding rows (lens=1 over zero ids) are sliced off below.
            bucket = 16
            while bucket < longest:
                bucket *= 2
            n_bucket = 1
            while n_bucket < n:
                n_bucket *= 2
            ids = np.zeros((n_bucket, bucket), np.int32)
            lens = np.ones((n_bucket,), np.int32)
            for i, toks in enumerate(chunk):
                ids[i, : len(toks)] = toks
                lens[i] = len(toks)
            out = self.core.family.encode(
                self.core.params, self.core.cfg, ids, lens
            )
            results.extend(np.asarray(out)[:n].tolist())
        return results

    def supports_embeddings(self) -> bool:
        """Capability by family contract: a family supports /v1/embeddings iff
        it exports an `encode` forward (the registry is the extension point)."""
        return hasattr(self.core.family, "encode")

    async def embed(self, batch_ids: list[list[int]]) -> "list[list[float]]":
        """Batch of token id lists -> L2-normalized embedding vectors.

        Raises ValueError (a client error) for empty/oversized/out-of-vocab
        inputs and for model families without an embedding forward.
        """
        if not self.supports_embeddings():
            raise ValueError(
                "embeddings are not supported for the "
                f"{self.core.family.__name__.rsplit('.', 1)[-1]} model family"
            )
        if not batch_ids or any(len(x) == 0 for x in batch_ids):
            raise ValueError("each input must contain at least one token")
        if len(batch_ids) > self.MAX_EMBED_INPUTS:
            raise ValueError(
                f"at most {self.MAX_EMBED_INPUTS} inputs per request "
                f"(got {len(batch_ids)})"
            )
        longest = max(len(x) for x in batch_ids)
        if longest > self.core.cfg.max_position_embeddings:
            raise ValueError(
                f"input of {longest} tokens exceeds the model context "
                f"({self.core.cfg.max_position_embeddings})"
            )
        import numpy as np

        vocab = self.core.cfg.vocab_size
        # vectorized range check — this runs on the event loop, so it must
        # stay O(total tokens) in numpy, not a Python per-token loop
        flat = np.fromiter(
            (t for toks in batch_ids for t in toks), np.int64
        )
        if flat.size and (flat.min() < 0 or flat.max() >= vocab):
            bad = int(flat[(flat < 0) | (flat >= vocab)][0])
            raise ValueError(
                f"token id {bad} out of range for vocab size {vocab}"
            )
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._executor, self._embed_sync, batch_ids
        )

    def health(self, current: str | None = None) -> dict:
        """`current` is the calling thread's class for the CPU seconds by
        class (the HTTP handler says "http_loop")."""
        from llmlb_tpu.engine.telemetry import device_telemetry
        from llmlb_tpu.ops.attention import attention_mode, traced_routes

        stats = self.core.stats()
        return {
            "status": "ok",
            "model": self.model_id,
            "engine": {
                "num_slots": stats.num_slots,
                "active_slots": stats.active_slots,
                "queued": stats.queued,
                "total_requests": stats.total_requests,
                "total_tokens": stats.total_tokens,
                "uptime_s": round(stats.uptime_s, 3),
                "mesh": dict(self.core.mesh.shape),
                "num_layers": self.core.cfg.num_layers,
            },
            "tpu": device_telemetry(),
            # which attention path this process dispatches to, and the
            # kernel each op resolved to when its program was traced
            "attention": {"mode": attention_mode(),
                          "traced": traced_routes()},
            "prefix_cache": self.core.prefix_cache_info(),
            "kv_cache": self.core.kv_cache_info(),
            # int8 quantization knobs + honest byte footprints
            "quant": self.core.quant_info(),
            "structured": self.core.structured_info(),
            # speculative decoding config + live acceptance figures
            # (llmlb_tpu/spec, docs/speculative.md)
            "spec": self.core.spec_info(),
            # overload protection: priority-queue depths, preemption and
            # deadline-shed counters (docs/scheduling.md)
            "sched": self.core.sched_info(),
            # disaggregated prefill/decode: served role, split-pool sizes,
            # handoff counters (docs/disaggregation.md) — the gateway's
            # health probe re-reads `role` from here every interval, so a
            # restarted engine that changed role re-routes within one probe
            "disagg": self.core.disagg_info(),
            # multi-LoRA adapter pool: resident/available adapters,
            # load/evict counters (docs/lora.md)
            "lora": self.core.lora_info(),
            # live roofline (MFU / HBM-BW vs chip peaks, docs/profiling.md);
            # the gateway's telemetry-aware placement can read how close to
            # the hardware each engine is running
            "perf": self.core.perf_info(),
            "metrics": self.core.metrics.summary(current),
        }


class EngineError(RuntimeError):
    pass


def _event_text(detok: IncrementalDetokenizer, tokens: list[int],
                stop: list[str], acc: str) -> tuple[str, int]:
    """The text one content event adds behind `acc`, and how many of its
    tokens were taken. With no stop string to look for between two tokens
    the event is decoded ONCE: a decode walks the whole answer, and once a
    token it kept the event loop busy for 1.5 ms a frame of 8 at answers
    of 4,096 tokens — the 32 streams then ran seconds behind the scheduler
    (PERF.md §6 PR 60). With stop strings: a decode a token, and usage
    counts to the hit, no further."""
    if not stop:
        return detok.extend(tokens), len(tokens)
    text = ""
    for taken, token in enumerate(tokens, 1):
        text += detok.push(token)
        if _find_stop(acc + text, stop) is not None:
            return text, taken
    return text, len(tokens)


def _find_stop(text: str, stops: list[str]) -> int | None:
    best: int | None = None
    for s in stops:
        if not s:
            continue
        idx = text.find(s)
        if idx != -1 and (best is None or idx < best):
            best = idx
    return best
