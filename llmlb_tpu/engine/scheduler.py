"""Continuous-batching scheduler: prefill/decode split over a paged KV pool.

JetStream-style serving loop, TPU-first:
- A fixed pool of NUM_SLOTS decode slots. KV lives in a global page pool
  [L, PAGES, PAGE, K, D] plus a per-slot block table (engine/paging.py owns
  the refcounted allocator), so HBM is held per page of tokens actually
  cached and short requests do not strand slot_capacity rows each. One
  compiled decode program serves every mix of requests — raggedness is
  masks and tables, never shapes.
- New requests prefill at bucketed prompt lengths (pow2 buckets ⇒ a handful
  of compiles) and scatter straight into their pages
  (programs.py `prefill`), while other slots keep decoding between prefills.
- Sampling params live in device arrays indexed by slot; updated on insert.
- The step loop runs in a dedicated thread; completions stream to waiters
  through per-request queues (asyncio- and thread-friendly).
- Prefix KV reuse (engine/prefix_cache.py): completed requests donate their
  full pages to a refcounted radix tree keyed on prompt token ids; a later
  request sharing a prefix references those pages in its block table
  (no copy, no recompute) and chunk-prefills only the uncached suffix.
- Speculative decoding (llmlb_tpu/spec, docs/speculative.md): per-slot
  prompt-lookup drafters propose up to K tokens; one batched K+1-token
  verify dispatch through the extend path scores them all, the longest
  prefix matching the model's own samples is accepted (1..K+1 tokens per
  step), rejected suffixes roll back committed length and release
  over-allocated KV pages.

The reference has no equivalent (it proxies to external runtimes, SURVEY.md L0);
this is the in-tree `tpu://` engine of the BASELINE.json north star.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import math
import os
import queue
import threading
import time
import typing
import uuid
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from llmlb_tpu.engine import compilelog, stepstats
from llmlb_tpu.engine.kv_offload import KVOffloadTier
from llmlb_tpu.engine.kv_transfer import (
    KV_WIRE_VERSION, KVPages, KVWireHeader, kv_compat_reason,
    serialize_kv_pages,
)
from llmlb_tpu.engine.metrics import EngineMetrics
from llmlb_tpu.engine.paging import PagePool
from llmlb_tpu.engine.prefix_cache import PrefixCache, PrefixEntry
from llmlb_tpu.engine.programs import StepPrograms
from llmlb_tpu.engine.flightrec import FlightRecorder, gateway_rid
from llmlb_tpu.engine.stepstats import LoopClock, StepRecorder, StepSpan
from llmlb_tpu.engine.streamstats import EventQueue, first_token_annotation
from llmlb_tpu.models import family_for
from llmlb_tpu.models.llama import LlamaConfig, Params
from llmlb_tpu.ops.grammar import GrammarTables
from llmlb_tpu.ops.sampling import sample_tokens, selection_plan
from llmlb_tpu.parallel.mesh import MeshConfig, build_mesh, default_tp
from llmlb_tpu.quant import kv_cell_bytes, parse_quant_mode, quantize_params
from llmlb_tpu.spec import PromptLookupDrafter, SpecConfig
from llmlb_tpu.structured.constraint import ConstraintState, TokenConstraint

log = logging.getLogger("llmlb_tpu.engine")

# Priority classes (docs/scheduling.md): lower value = more important.
# Dialect-facing names map high/normal/low onto 0/1/2 at the HTTP layer.
PRIORITY_HIGH, PRIORITY_NORMAL, PRIORITY_LOW = 0, 1, 2
PRIORITY_CLASSES = (PRIORITY_HIGH, PRIORITY_NORMAL, PRIORITY_LOW)
PRIORITY_NAMES = {PRIORITY_HIGH: "high", PRIORITY_NORMAL: "normal",
                  PRIORITY_LOW: "low"}


def kv_page_bytes(cfg, page_size: int, quantized: bool = False) -> int:
    """HBM bytes ONE page holds across all layers: page_size tokens of the
    family's per-token, per-layer cell (for a GQA family, K and V: the bf16
    cell is D·2 bytes per (token, head); the int8 cell is D·1 plus one f32
    scale, llmlb_tpu/quant.kv_cell_bytes) — the per-page figure the kv
    gauges report so capacity math stays honest under quantization."""
    family = family_for(cfg).FAMILY
    return int(family.kv_pool_layers(cfg) * page_size
               * family.kv_token_layer_bytes(cfg, quantized))


def kv_pool_bytes(cfg, num_pages: int, page_size: int,
                  quantized: bool = False) -> int:
    """HBM footprint of the KV page pool [L, pages, page_size, K, D] ×2
    (K and V; int8 pools add their f32 scale arrays). The serving memory
    budget is weights ≈ 2·n_params bytes (bf16) plus this; the default
    sizing (num_pages = slots · cap/page_size + 1) gives every slot its full
    capacity, e.g. llama-3-8b (L=32, K=8, D=128) at 8×4096: 4.3 GiB — the
    occupancy win comes from admitting MORE slots against the same pool,
    not from a smaller pool. Quantized pools
    hold ~(D+4)/2D of the bf16 bytes per page, so the same HBM budget holds
    nearly twice the pages."""
    return num_pages * kv_page_bytes(cfg, page_size, quantized)


@partial(jax.jit, donate_argnames=("cache_k", "cache_v"))
def _scatter_kv_row_paged(cache_k, cache_v, k_all, v_all, table_row):
    """Land a context-parallel prefill's KV [L, 1, T, K, D] in the pool
    pages named by `table_row` [PPN] (positions past the allocated pages hit
    the trash page — padding garbage past the valid length; the pools are
    donated so no copy of them is made). Quantized pools ({"q","s"} pairs)
    quantize per vector on the way in, scales landing at the same cells."""
    from llmlb_tpu.models.llama import kv_pool_values
    from llmlb_tpu.quant import quantize_kv

    t = k_all.shape[2]
    ps = kv_pool_values(cache_k).shape[2]
    pos = jnp.arange(t, dtype=jnp.int32)
    page = table_row[jnp.minimum(pos // ps, table_row.shape[0] - 1)]
    off = pos % ps

    def scatter(pool, kv_all):
        kv = kv_all[:, 0]  # [L, T, K, D]
        if isinstance(pool, dict):
            q, s = quantize_kv(kv)
            return {"q": pool["q"].at[:, page, off].set(q),
                    "s": pool["s"].at[:, page, off].set(s)}
        return pool.at[:, page, off].set(kv.astype(pool.dtype))

    return scatter(cache_k, k_all), scatter(cache_v, v_all)


@partial(jax.jit, donate_argnames=("cache_k", "cache_v"))
def _write_kv_pages(cache_k, cache_v, k_new, v_new, page_idx):
    """Land shipped/offloaded KV pages [L, P', PS, K, D] into pool pages
    `page_idx` [P'] — the H2D half of the page-transfer path (kv_transfer).
    Quantized pools take pre-quantized {"q","s"} pairs verbatim: the bytes
    on the wire are bit-exact donor pool cells, so no re-quantization (and
    no numerics drift) happens on the way in. Callers pad `page_idx` (and
    the sections) to the next power of two by repeating the last page —
    duplicate scatter of identical data — so the jit cache stays at
    log2(pool) variants."""

    def scatter(pool, new):
        if isinstance(pool, dict):
            return {"q": pool["q"].at[:, page_idx].set(new["q"]),
                    "s": pool["s"].at[:, page_idx].set(new["s"])}
        return pool.at[:, page_idx].set(new.astype(pool.dtype))

    return scatter(cache_k, k_new), scatter(cache_v, v_new)


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    max_tokens: int = 128
    # Per-request deterministic sampling: rows with a seed draw from
    # fold_in(PRNGKey(seed), position) instead of the shared batch key, so
    # the token sequence reproduces regardless of batch composition.
    seed: int | None = None
    # Grammar constraint spec (llmlb_tpu/structured.spec_regex forms) —
    # JSON-safe, so it rides the multihost plan wire as-is. The compiled
    # token-DFA travels separately on Request.compiled_constraint.
    constraint: dict | None = None
    # Speculative decoding knobs (llmlb_tpu/spec): {"enabled": bool,
    # "max_draft_tokens": int} — absent keys fall back to the engine
    # defaults, max_draft_tokens clamps into the engine's verify width.
    # JSON-safe, rides the plan wire like `constraint`.
    speculative: dict | None = None
    # Priority class (docs/scheduling.md): 0=high, 1=normal, 2=low. The
    # scheduler admits strictly by class (FIFO within a class) and may
    # PREEMPT a lower-class decoding slot under slot/page pressure — the
    # parked request resumes later, token-identical (greedy/seeded).
    # Plain int so it rides the multihost plan wire as-is.
    priority: int = 1
    # Relative deadline in milliseconds from submission (None = none). A
    # request still queued past its deadline is shed before it burns a
    # prefill; the gateway propagates client deadlines via the
    # X-Request-Deadline-Ms header.
    deadline_ms: float | None = None
    # LoRA adapter name (docs/lora.md): selected by the `lora` field or the
    # `model:adapter` suffix on both dialects. A plain string so it rides
    # the multihost plan wire, the /v1/handoff disagg wire, and /v1/resume
    # replay for free (test_plan_wire/test_handoff_wire auto-probe it).
    # Resolution to a pool row happens at submit (EngineCore.prepare_lora);
    # park/resume re-prefills with the same adapter so resumed streams stay
    # token-identical.
    lora: str | None = None
    # Generation by diffusion over blocks (docs/block-diffusion.md): the
    # request's own procedure; None = the model configuration's. A family
    # that decodes one token a step refuses them at submission, and
    # `block_length` must equal the model's. JSON-safe: they ride the plan
    # wire and /v1/resume replay as they are.
    block_length: int | None = None
    denoising_steps: int | None = None
    remasking_strategy: str | None = None
    confidence_threshold: float | None = None


@dataclasses.dataclass
class ParkedState:
    """Everything a preempted request needs to resume token-identical: the
    tokens it already committed (prompt KV is rebuilt by a chunk-prefill of
    prompt + these), its generation progress, and the host-side cursors that
    must NOT re-walk from scratch — the grammar FSM cursor (a fresh
    ConstraintState would mask as if at string start) and the prompt-lookup
    drafter index (cheap to rebuild, but reusing it preserves behavior
    exactly). Sampling determinism needs no state here: seeded rows fold
    PRNGKey(seed) by absolute position, so the resumed chunk-prefill's
    activation sample IS the next uninterrupted sample."""

    generated: int
    tokens: list[int]
    constraint: ConstraintState | None = None
    drafter: PromptLookupDrafter | None = None
    spec_k: int = 0


def event_tokens(kind: str, value) -> list[int]:
    """The token ids an item of `Request.events` carries, in order: a
    content event's, and none for `done` and `error`."""
    return [int(t) for t in value] if kind == "tokens" else []


@dataclasses.dataclass
class Request:
    prompt_ids: list[int]
    sampling: SamplingParams
    request_id: str = dataclasses.field(default_factory=lambda: uuid.uuid4().hex)
    # events: ("tokens", [ids]) — the content event: every token ONE fetch
    # brought this request, in order (a burst's k, the deferred first token
    # with them; a speculative step's accepted drafts + 1; a committed
    # block; one where a step makes one) — then ("done", finish_reason) or
    # ("error", msg). event_tokens reads one. Each is stamped at its put
    # (engine/streamstats.py), and handed to the consumer as it was put
    events: EventQueue = dataclasses.field(default_factory=EventQueue)
    # The request's way in (docs/tracing.md "A request's way in"): the
    # instant each stage of its time to first token ENDS, all read from
    # stepstats._now (the clock of every span), each once, where the work
    # happens. stepstats.way_in_stages cuts them into stages; a stage this
    # process never ran (a restored request has no prefill) has no stamp
    # and is absent there. A parked, resumed or adopted request keeps the
    # stamps it has.
    #   received_at    the HTTP handler's entry (service.RECEIVED_AT; None
    #                  for a request that came in no handler)
    #   submitted_at   EngineCore.submit's `admitted` (the construction,
    #                  until then)
    #   taken_at       _drain_pending took it off the inbox (`queued`)
    #   prefill_at     its first prefill dispatch began (that step's t0);
    #                  prefill_seq is the step's seq, prefill_chunks the
    #                  dispatches it took, cached_tokens what a prefix hit
    #                  spared it
    #   activated_at   the host knows its prompt filled and has issued its
    #                  activation (_stamp_activated); prefill_cut is the
    #                  stage `prefill` cut by what it waited for
    #                  (stepstats.PREFILL_CUT), open from prefill_at to here
    #   first_token_at the first token reached the host (_emit)
    received_at: float | None = None
    submitted_at: float = dataclasses.field(
        default_factory=lambda: stepstats._now())
    taken_at: float | None = None
    prefill_at: float | None = None
    prefill_seq: int | None = None
    prefill_chunks: int = 0
    cached_tokens: int = 0
    prefill_cut: "stepstats.PrefillCut | None" = None
    activated_at: float | None = None
    first_token_at: float | None = None
    finished_at: float | None = None
    # Set by the consumer (stop hit / client gone); the step loop frees the slot
    # at its next emit for this request. Plain bool write — atomic under the GIL.
    cancelled: bool = False
    # Compiled token-DFA for sampling.constraint (llmlb_tpu/structured).
    # The service pre-compiles it off the step loop; multihost followers and
    # direct core submitters get it compiled at insert via the core's
    # constraint_compiler. Never serialized — followers rebuild from the spec.
    compiled_constraint: TokenConstraint | None = None
    # Preemption (docs/scheduling.md): set by _park_slot when this request is
    # parked under slot/page pressure, consumed at re-activation. While set,
    # insert paths prefill prompt_ids + parked.tokens and restore the
    # generation cursor instead of starting over. Host-local — never crosses
    # the plan wire (every host parks/resumes its own mirror identically).
    parked: ParkedState | None = None
    # KV page shipping (engine/kv_transfer.py, docs/kv-cache.md). export_kv
    # asks _emit's finish path to serialize this request's KV pages D2H
    # before they are freed (set by the handoff-prefill path); the payload
    # lands in kv_export for the caller. kv_restore carries a parsed
    # inbound payload (wire or offload tier) that _insert_restored lands
    # H2D, activating the slot with zero prefill dispatches; cleared on
    # first use whether or not the restore succeeds (one-shot — a failed
    # restore falls back to chunk-prefill replay). All three are host-local
    # and never cross the plan wire.
    export_kv: bool = False
    kv_export: dict | None = None
    kv_restore: "KVPages | None" = None

    def cancel(self) -> None:
        self.cancelled = True

    def deadline_expired(self, now: float | None = None) -> bool:
        dl = self.sampling.deadline_ms
        if dl is None:
            return False
        return ((now if now is not None else stepstats._now())
                > self.submitted_at + float(dl) / 1000.0)


@dataclasses.dataclass
class _Slot:
    request: Request | None = None
    generated: int = 0
    eos_id: int = -1
    # Chunked-prefill progress: tokens of the prompt already in the KV cache.
    # While prefilling is True the slot is excluded from decode emission and
    # its device seq_len is parked at capacity-1 so the batched decode step's
    # garbage writes land in the (unused) last cell, never inside the region
    # the chunks are filling. The parked length is for the WRITE alone: the
    # row is not among a dispatch's _live_rows, so attention reads none of
    # its pages.
    prefilling: bool = False
    prefill_pos: int = 0
    # Prefix-cache entry this slot is reading (hit path): acquired for the
    # suffix prefill so the donor cannot be evicted mid-copy-window; released
    # on activation, cancellation, or engine failure.
    cache_entry: PrefixEntry | None = None
    last_emit_at: float = 0.0  # inter-token latency tracking
    # The first token is sampled on-device at activation and emitted with the
    # NEXT decode fetch instead of its own host readback — per-insert syncs
    # cost a full host↔device round trip each and serialized TTFT under
    # bursty load.
    first_pending: bool = False
    # Grammar-constraint cursor (llmlb_tpu/structured.ConstraintState),
    # advanced host-side on every emitted token; its bias row is this slot's
    # stripe of the [B, V] decode mask.
    constraint: ConstraintState | None = None
    # Fused decode (ops/grammar.py): absolute row offset of this slot's
    # schema in the device grammar table, -1 when the schema is not
    # device-resident (fused off, or table budget exceeded — the slot then
    # takes the legacy host-mask path). The device cursor for a step is
    # gram_offset + constraint.state; the host FSM stays source of truth.
    gram_offset: int = -1
    # Speculative decoding (llmlb_tpu/spec): the per-request prompt-lookup
    # index, fed every emitted token; None when this request does not
    # speculate. spec_k is the request's draft budget per verify step.
    drafter: PromptLookupDrafter | None = None
    spec_k: int = 0
    # Every token emitted so far, in order (EOS excluded — a finished
    # request is never parked). Preemption needs the committed sequence to
    # rebuild KV via chunk-prefill; bounded by max_tokens per slot.
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    # Split-mode handoff (llmlb_tpu/disagg/split.py): a prefill-pool slot
    # whose prompt KV is fully landed and is waiting for a decode slot to
    # adopt it. `handoff_logits` holds the final prefill dispatch's logits
    # row ([1, V] device array) so the first token samples at adoption.
    handoff_ready: bool = False
    handoff_logits: object | None = None
    handoff_ready_at: float = 0.0
    # A block family's row (set at every activation): the prompt tokens its
    # open FIRST block holds as given — committed with it, never emitted —
    # and the tokens the request may emit in all (max_tokens, or what the
    # slot's capacity leaves), which is what the device's `left` counts.
    given: int = 0
    token_limit: int = 0


class _BurstBounds(typing.NamedTuple):
    """What ONE decode burst may do to a row, in positions past the length
    the row has at the burst's dispatch (EngineCore._burst_bounds): what the
    host plans a burst from, also where it cannot count."""

    # cells the row may write: its pages and the burst's window cover them
    reach: int
    # the most its length grows by: what the host's mirror (_seq_lens,
    # `generated`) may lag by while the burst is in flight. A dense row
    # grows by exactly that; a block row by the blocks it commits, which
    # only the fetch tells
    advance: int
    # the positions its record's `kv_pages_live` counts
    counted: int


@dataclasses.dataclass
class _Burst:
    """A decode burst — dense steps, or a block family's passes — from its
    dispatch to its emit (EngineCore._decode_bursts)."""

    # its record's step; a burst queued behind its predecessor gets it where
    # that predecessor's record ends, at the predecessor's fetch
    step: StepSpan | None
    # (slot, request, whether row 0 of its column is the request's first
    # token) as they stood at the DISPATCH: the burst's tokens are theirs,
    # whoever holds the slot when they are delivered
    rows: list[tuple[int, Request, bool]]
    kv_pages: dict[str, int]
    # why it did not leave before its predecessor was emitted (one of
    # metrics.AHEAD_BLOCKERS); None: it did
    blocked_by: str | None
    # it left before its predecessor was even FETCHED, queued behind it on
    # the device (`blocked_by` is None)
    queued: bool = False
    # the array its one fetch reads, a future from the dispatch on
    toks_dev: object = None
    # what the one fetch brought: [k + 1, SLOTS] tokens (a block family:
    # [k · (B + 2), SLOTS], _build_block_many), and the family's step
    # counters behind them (_pack_step_counters)
    fetched: np.ndarray | None = None
    step_s: float = 0.0  # the cycle's wall time a token
    # the arrival whose prompt rides this burst's first step, its row among
    # `rows` (EngineCore._admit_riding); None: a pure decode burst
    admitted: "_Riding | None" = None

    @property
    def slots(self) -> list[int]:
        return [i for i, _, _ in self.rows]


@dataclasses.dataclass
class _Riding:
    """An arrival placed to RIDE the next decode burst (EngineCore.
    _admit_riding): its prompt is the burst's first step's, so it has no
    prefill and no activation of its own. What StepPrograms.admit_many takes
    for it is filled at the placing, on the host: the one call carries
    every transfer."""

    slot: int
    request: "Request"
    tokens: int  # of its prompt
    prompt_ids: np.ndarray  # [1, T] int32, right-padded
    arrival: np.ndarray  # int32 [4]: slot, prompt tokens, top_k, seed
    arrival_f: np.ndarray  # float32 [2]: temperature, top_p


@dataclasses.dataclass
class _AheadPrefill:
    """A one-shot prefill group dispatched AHEAD (EngineCore._admit_ahead),
    from its dispatch to its record, which is closed with the burst behind
    it in flight (_record_ahead_prefill)."""

    step: StepSpan
    group: list[tuple[int, "Request", int]]  # (slot, request, tokens)
    logits: object  # the dispatch's result: ready when the prefill is done
    stats: list  # its step counters, still on the device


@dataclasses.dataclass(frozen=True)
class EngineStats:
    num_slots: int
    active_slots: int
    queued: int
    total_requests: int
    total_tokens: int
    uptime_s: float


class EngineCore:
    """The compute side of the engine: owns params, cache, and the step loop."""

    def __init__(
        self,
        cfg: LlamaConfig,
        params: Params | None = None,
        *,
        num_slots: int = 8,
        slot_capacity: int = 512,
        prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512),
        mesh_config: MeshConfig | None = None,
        eos_id: int = -1,
        seed: int = 0,
        decode_burst: int | None = None,
        fused_decode: bool | None = None,
        prefix_cache: bool | None = None,
        prefix_cache_slots: int | None = None,
        min_prefix_len: int | None = None,
        kv_layout: str | None = None,
        kv_page_size: int | None = None,
        kv_pages: int | None = None,
        kv_ship: bool | None = None,
        kv_offload_bytes: int | None = None,
        spec_decode: bool | None = None,
        spec_max_draft: int | None = None,
        spec_ngram: int | None = None,
        quantize: str | None = None,
        prefill_chunk_budget: int | None = None,
        role: str | None = None,
        disagg_prefill_slots: int | None = None,
        lora_dir: str | None = None,
        lora_max_adapters: int | None = None,
        lora_rank_cap: int | None = None,
    ):
        self.cfg = cfg
        # Serving role (docs/disaggregation.md): "both" (default) is the
        # classic combined loop; "split" runs a prefill pool and a decode
        # pool as two step loops over one shared PagePool (in-process
        # disaggregation — built at the end of __init__ once slots exist);
        # "prefill"/"decode" keep the combined loop and only change what the
        # server layer advertises and accepts (cross-process roles).
        from llmlb_tpu.disagg import normalize_role

        if role is None:
            role = os.environ.get("LLMLB_ROLE")
        self.role = normalize_role(role)
        self._disagg_prefill_slots_arg = disagg_prefill_slots
        self.split = None  # SplitRuntime in split mode
        # The family's module (it is called through self.programs alone)
        # and its record: what the family IS (models/family.py).
        self.family = family_for(cfg)
        record = self._record = self.family.FAMILY
        # Generation by diffusion over blocks: a family that declares a
        # block length B > 1 decodes by BLOCK PASSES (_decode_bursts) — a
        # row commits 0 or B tokens a pass — and every other family by one
        # token a step, on the programs it always built.
        self.block = int(record.block_length(cfg))
        # A family whose layers keep a recurrent state per SLOT beside the
        # page pool (docs/hybrid-state.md) says so by `state_slot_bytes`:
        # its pool is made for the slots, its prefill calls are told the
        # rows' slots, and what would serve such a state wrong is off unless
        # asked for, and refused when asked for (_check_family_engine).
        self._slot_state = record.state_slot_bytes is not None
        if self._slot_state:
            if prefix_cache is None and "LLMLB_PREFIX_CACHE" not in os.environ:
                prefix_cache = False
            if kv_ship is None and "LLMLB_KV_SHIP" not in os.environ:
                kv_ship = False
        self.num_slots = num_slots
        self.slot_capacity = min(slot_capacity, cfg.max_position_embeddings)
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= self.slot_capacity
        )
        self.eos_id = eos_id

        # There is one KV layout. The keyword stays only until its last
        # caller outside the package drops it (ROADMAP.md, Design debts).
        if kv_layout not in (None, "paged"):
            raise ValueError(f"kv_layout must be 'paged', got {kv_layout!r}")

        # Int8 quantization (llmlb_tpu/quant, docs/quantization.md): two
        # independent knobs — per-output-channel int8 projection weights
        # and int8 KV pages — resolved from `--quantize`/LLMLB_QUANTIZE.
        # OFF by default; with both knobs off every path below is the
        # pre-quantization engine bit for bit (tier-1 guarded).
        self.quant = parse_quant_mode(quantize)

        # Page size: TPU-friendly default of 128 tokens (one flash block),
        # clamped into the slot capacity. docs/kv-cache.md discusses the
        # waste-vs-overhead tradeoff of other sizes.
        self.kv_page_size = max(1, min(kv_page_size or 128,
                                       self.slot_capacity))
        self.pages_per_slot = -(-self.slot_capacity // self.kv_page_size)
        # Pool size resolves after the mesh exists (the per-device default
        # depends on the dp degree); 0 until the cache-init block runs.
        self._kv_pages_arg = kv_pages
        self.kv_num_pages = 0

        # Prefix KV cache: completed requests may donate their full pages to
        # a radix tree keyed on prompt token ids; later requests sharing a
        # prefix reference those pages and prefill only the suffix. Disabled
        # (None) the scheduler behaves exactly as before — every new branch
        # below is gated on `self.prefix_cache is not None`.
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "LLMLB_PREFIX_CACHE", "1"
            ).lower() not in ("0", "false", "off", "no")
        # Matched lengths are aligned DOWN to the smallest prefill bucket so
        # the uncached suffix always starts on a bucket boundary (chunked
        # prefill then runs at its existing compiled sizes), and to whole
        # pages: only FULL pages can be shared zero-copy (a partially-shared
        # page would mix two requests' rows), so the quantum is
        # lcm(bucket, page_size).
        self.prefix_align = self.prefill_buckets[0] if self.prefill_buckets else 0
        if self.prefix_align:
            self.prefix_align = math.lcm(self.prefix_align, self.kv_page_size)
        self.min_prefix_len = (
            max(1, int(min_prefix_len)) if min_prefix_len is not None
            else self.prefix_align
        )
        if prefix_cache_slots is None:
            prefix_cache_slots = max(1, num_slots // 2)
        # the entry budget: max cached prefixes, capped below the slot count
        budget = max(0, min(int(prefix_cache_slots), num_slots - 1))
        self._prefix_cache_asked = bool(prefix_cache)
        self.prefix_cache: PrefixCache | None = (
            PrefixCache(max_entries=budget, min_len=self.min_prefix_len,
                        align=self.prefix_align)
            if prefix_cache and budget > 0 and self.prefix_align > 0
            else None
        )

        # Speculative decoding (llmlb_tpu/spec): prompt-lookup drafting +
        # batched K+1-token verification. `spec_decode` sets the DEFAULT for
        # requests that do not carry their own `speculative` knob (a request
        # may opt in on an engine defaulting off, and vice versa); the
        # engine-level max_draft_tokens bounds the verify chunk width, so
        # there is exactly one verify compile per window bucket. OFF by
        # default: with no drafter attached anywhere the decode path is
        # bit-identical to the pre-speculation engine.
        if spec_decode is None:
            spec_decode = os.environ.get(
                "LLMLB_SPEC_DECODE", "0"
            ).lower() in ("1", "true", "on", "yes")
        if spec_max_draft is None:
            spec_max_draft = int(os.environ.get("LLMLB_SPEC_MAX_DRAFT", "4"))
        if spec_ngram is None:
            spec_ngram = int(os.environ.get("LLMLB_SPEC_NGRAM", "3"))
        self.spec = SpecConfig(
            enabled=bool(spec_decode),
            max_draft_tokens=max(1, min(int(spec_max_draft), 16)),
            max_ngram=max(1, int(spec_ngram)),
            min_ngram=1,
        )
        self._spec_available = record.verifies_drafts and self.block == 1

        # Decode burst: number of decode+sample steps fused into ONE device
        # dispatch (lax.scan with on-device token feedback) per host readback.
        # The per-step host sync is pure latency — tokens/sec scales with k
        # while the host↔device round trip dominates the step. Auto: 8 on
        # TPU (not re-derived for a local chip: ROADMAP, Speed, "Time to
        # first token", cure (b)), 1 elsewhere (CPU tests keep single-step
        # token-for-token goldens). Emission becomes k-token bursts;
        # EOS/max_tokens mid-burst are trimmed host-side.
        if decode_burst is None:
            env = os.environ.get("LLMLB_DECODE_BURST")
            if env:
                try:
                    decode_burst = max(1, int(env))
                except ValueError:
                    log.warning(
                        "LLMLB_DECODE_BURST=%r is not an integer; using the "
                        "auto default", env,
                    )
            if decode_burst is None:
                decode_burst = 8 if jax.default_backend() == "tpu" else 1
        self.decode_burst = max(1, int(decode_burst))

        # a model sharded across processes runs in lockstep (below)
        multihost = jax.process_count() > 1
        # KV page shipping (engine/kv_transfer.py, docs/kv-cache.md): move
        # serialized pages instead of chunk-prefill replay on handoff and
        # resume. ON by default but inert until a peer actually offers or
        # requests a payload; requires a single-host combined loop — split
        # mode moves pages in-process by block-table exchange already, and
        # a multihost restore would desync followers whose plan wire
        # carries no page bytes. LLMLB_KV_SHIP=0 restores today's replay-only
        # behavior bit for bit (tier-1 pinned).
        if kv_ship is None:
            kv_ship = os.environ.get(
                "LLMLB_KV_SHIP", "1"
            ).lower() not in ("0", "false", "off", "no")
        self.kv_ship = (bool(kv_ship) and not multihost
                        and self.role != "split")
        # Tiered host-RAM offload (engine/kv_offload.py): cold prefix-cache
        # evictions and parked-slot pages spill D2H into a bounded LRU tier
        # and restore H2D on re-hit/resume. Default 0 = off — no spill, no
        # restore, no behavior change (tier-1 pinned).
        if kv_offload_bytes is None:
            try:
                kv_offload_bytes = int(os.environ.get(
                    "LLMLB_KV_OFFLOAD_BYTES", "0") or 0)
            except ValueError:
                log.warning("LLMLB_KV_OFFLOAD_BYTES is not an integer; "
                            "offload disabled")
                kv_offload_bytes = 0
        self.kv_offload: KVOffloadTier | None = (
            KVOffloadTier(kv_offload_bytes)
            if (kv_offload_bytes and kv_offload_bytes > 0
                and not multihost and self.role != "split")
            else None
        )
        if self.kv_offload is not None:
            log.info("KV offload tier: %.1f MiB host-RAM budget",
                     self.kv_offload.budget_bytes / 2**20)
        if lora_dir is None:
            lora_dir = os.environ.get("LLMLB_LORA_DIR") or None
        self._check_family_engine(multihost, lora=bool(lora_dir))

        devices = jax.devices()
        if mesh_config is None:
            # Size the latency-critical axes (ep, tp) within ONE slice/host —
            # in a multi-process cluster their per-layer collectives must
            # ride ICI, never DCN; dp (independent requests) spans hosts.
            n_local = (jax.local_device_count()
                       if jax.process_count() > 1 else len(devices))
            ep = 1
            if getattr(cfg, "num_experts", 0) > 1:
                # MoE default: give experts as much of the mesh as divides both
                # the device count and the expert count, tp/dp with the rest.
                ep = math.gcd(n_local, cfg.num_experts)
            tp = default_tp(n_local // ep, cfg.num_heads, cfg.num_kv_heads)
            mesh_config = MeshConfig(
                dp=n_local // (ep * tp), ep=ep, tp=tp
            )
        if jax.process_count() > 1:
            from llmlb_tpu.parallel.distributed import build_hybrid_mesh

            # dp multiplies across slices over DCN; sp/ep/tp stay inside
            self.mesh = build_hybrid_mesh(
                mesh_config, dcn_dp=jax.process_count(), devices=devices
            )
        else:
            self.mesh = build_mesh(mesh_config, devices=devices)
        # Every device program this engine runs, and every call of the
        # family's entry points (engine/programs.py).
        self.programs = StepPrograms(
            self.family, cfg, self.mesh, decode_burst=self.decode_burst,
            max_draft_tokens=self.spec.max_draft_tokens,
            num_slots=num_slots, slot_capacity=self.slot_capacity,
            eos_id=eos_id)

        if params is None:
            params = self.family.init_params(cfg, jax.random.PRNGKey(seed))
        if self.quant.weights:
            # Idempotent: checkpoints quantized at load time (streaming,
            # engine/weights.py) pass through; random-init / caller-supplied
            # bf16 pytrees quantize here so every construction path serves
            # the same int8 layout.
            params = quantize_params(params)

        # Multi-LoRA serving (llmlb_tpu/lora, docs/lora.md): a device-resident
        # adapter pool rides the param pytree as `<name>_lora_a/_lora_b`
        # companions (zeros at boot; hot-loaded rows overwrite in place), and
        # every dispatch carries per-row adapter indices. OFF by default —
        # with no pool in the pytree every forward compiles the original
        # program bit for bit (the quantize-off contract, tier-1 pinned).
        # Adapter deltas stay bf16 on top of (possibly int8) base weights:
        # the delta adds to the projection OUTPUT, so the dequant-on-read
        # path above is untouched.
        self.lora = None
        # one-time CP→chunked prefill fallback warning (satellite of the
        # fused-decode PR; the counter keeps counting after the first)
        self._lora_cp_warned = False
        if lora_dir:
            from llmlb_tpu.lora import LoraManager

            if jax.process_count() > 1:
                raise ValueError(
                    "--lora-dir is single-host only for now: followers have "
                    "no deterministic mirror of the leader's adapter pool "
                    "slot assignment"
                )
            if lora_max_adapters is None:
                lora_max_adapters = int(os.environ.get(
                    "LLMLB_LORA_MAX_ADAPTERS", "8"))
            if lora_rank_cap is None:
                lora_rank_cap = int(os.environ.get(
                    "LLMLB_LORA_RANK_CAP", "16"))
            # MoE families serve attention-target adapters only (no pools
            # over the routed expert FFNs).
            targets = (("wq", "wk", "wv", "wo")
                       if getattr(cfg, "num_experts", 0) > 1
                       else ("wq", "wk", "wv", "wo", "wg", "wu", "wd"))
            self.lora = LoraManager(
                cfg, lora_dir=lora_dir, max_adapters=lora_max_adapters,
                rank_cap=lora_rank_cap, targets=targets,
            )
            pool_leaves = self.lora.init_pool_leaves(np.dtype(cfg.dtype))
            params = {**params, **pool_leaves}
            log.info(
                "lora: pool of %d adapter slots at rank cap %d over %s "
                "(%d adapter(s) discovered in %s)",
                self.lora.max_adapters, self.lora.rank_cap,
                "/".join(targets), len(self.lora.available), lora_dir,
            )
        shardings = self.family.param_shardings(cfg, self.mesh)
        self.params = {
            k: jax.device_put(v, shardings[k]) for k, v in params.items()
        }
        if self.lora is not None:
            self.lora.attach(self)
        if self.quant.weights:
            log.info(
                "weights: int8 per-output-channel (%d quantized leaves), "
                "%.2f GiB on device",
                sum(1 for k in self.params if k.endswith("_scale")),
                sum(v.size * v.dtype.itemsize
                    for v in self.params.values()) / 2**30,
            )

        # Host side of the page pool: per-slot page lists and the block
        # tables (host numpy mirror + device array refreshed before the next
        # dispatch whenever a table row changes); the allocator follows.
        self._slot_pages: list[list[int]] = [[] for _ in range(num_slots)]
        self._block_tables = np.zeros((num_slots, self.pages_per_slot),
                                      np.int32)
        self._d_block_tables = jnp.asarray(self._block_tables)
        self._tables_dirty = False
        # A request popped from pending that the pool cannot yet cover waits
        # here (retried first), preserving arrival order without re-queueing.
        self._held_request: Request | None = None
        # Page REFERENCES held by prefix-cache entries (int: GIL-atomic so
        # scrape threads can read it while the step loop mutates the cache).
        # Entries sharing head pages each count their reference; the pool's
        # used() figure is the distinct-page truth.
        self._prefix_pinned_pages = 0

        # Default pool: every slot's full capacity PER DEVICE plus the
        # reserved trash page. The pool replicates over dp (pages are shared
        # by every slot, so they must be co-resident) — sizing from the full
        # slot count on a dp>1 mesh would multiply per-device KV HBM by dp.
        dp = self.mesh.shape.get("dp", 1)
        default_pages = (
            -(-num_slots // dp) * self.pages_per_slot + 1
        )
        self.kv_num_pages = max(int(self._kv_pages_arg or default_pages),
                                self.pages_per_slot + 1)
        if dp > 1:
            log.info(
                "paged KV pool replicates over dp=%d; defaulting to the "
                "per-device slot budget (%d pages) — raise --kv-pages "
                "to trade HBM for aggregate capacity", dp,
                self.kv_num_pages,
            )
        self.page_pool = PagePool(self.kv_num_pages)
        self.cache_k, self.cache_v = self.programs.fresh_kv_pool(
            self.kv_num_pages, self.kv_page_size, self.quant.kv)
        log.info(
            "KV cache: paged%s, %d pages x %d tokens (%d slots, %d "
            "pages/slot) = %.2f GiB in HBM",
            " int8" if self.quant.kv else "",
            self.kv_num_pages, self.kv_page_size, num_slots,
            self.pages_per_slot,
            kv_pool_bytes(cfg, self.kv_num_pages, self.kv_page_size,
                          quantized=self.quant.kv) / 2**30,
        )

        # Context-parallel prefill (ring attention over the mesh sp axis):
        # fills a long prompt's KV in ONE distributed pass instead of many
        # sequential chunks.
        self._use_cp_prefill = (self.mesh.shape.get("sp", 1) > 1
                                and record.context_parallel_prefill)
        self._prefill_rr = 0  # fair rotation among concurrently-prefilling slots

        # Multi-host lockstep (engine/multihost.py): with the model sharded
        # across processes every step is a collective, so the leader
        # broadcasts each tick's plan and all hosts run identical scheduler
        # logic on mirrored state. Device scalars/tokens are replicated
        # before host fetches (a cross-host shard is not addressable).
        self.coordinator = None
        self._replicate = None
        self._stop_requested = False
        # Graceful drain (docs/deployment.md): while draining the step loop
        # admits nothing new — in-flight decodes run to completion under the
        # server's grace window; `request_drain_park` then asks the NEXT
        # loop iteration (slot state is loop-thread-owned) to park every
        # decoding slot through the PR 10 park path so the gateway's
        # mid-stream resume can move those streams to another engine.
        self.draining = False
        self._drain_park_requested = False
        self._drain_flush_requested = False
        # Park-on-demand (gateway rebalancer, docs/resilience.md): gateway
        # request ids whose slots should park + export at the next loop
        # iteration — the migration analogue of request_drain_park, scoped
        # to single streams instead of the whole engine.
        self._park_rids: set[str] = set()
        # What kept the NEXT decode burst from leaving before its
        # predecessor was emitted (_ahead_blocker), for that burst's record.
        self._ahead_blocked_by = "first"
        # The decode burst the device holds and the host has not
        # fetched, at most ONE: set at its dispatch, cleared at its fetch.
        self._in_flight: _Burst | None = None
        # the way in (_first_token): the seq of the step whose fetch is being
        # delivered, and the entries of the requests whose FIRST token it
        # brought, which its record takes as `first_tokens` (_observe_step)
        self._fetch_seq = 0
        self._first_tokens: list[dict] = []
        # Cancellations take effect ONLY via the plan in multihost mode: the
        # live .cancelled flag flips at arbitrary times on the leader (HTTP
        # thread), and acting on it directly would make hosts dispatch
        # different collectives and deadlock the cluster. Single-host reads
        # the live flag; the discard on the emit paths still touches the set.
        self._cancelled_effective: set[str] = set()
        if jax.process_count() > 1:
            from llmlb_tpu.engine.multihost import StepCoordinator

            self.coordinator = StepCoordinator()
            self._replicate = jax.jit(
                lambda x: x,
                out_shardings=NamedSharding(self.mesh, PartitionSpec()),
            )
            # leader-only intake; mirrored into self.pending via the plan
            self._intake: queue.SimpleQueue[Request] = queue.SimpleQueue()
            self._plan_backlog: list[Request] = []  # budget-spilled, FIFO
            log.info(
                "multihost lockstep: %s of %d hosts",
                "leader" if self.coordinator.is_leader else "follower",
                self.coordinator.num_hosts,
            )

        # Host-side slot bookkeeping (lengths mirror device state for stop
        # checks without D2H); sampling params + tokens live ON DEVICE and are
        # only touched at insert time — the decode hot loop does zero H2D.
        self.slots = [_Slot() for _ in range(num_slots)]
        self._seq_lens = np.zeros((num_slots,), np.int32)
        self._init_slot_state()
        self._key = self._on_mesh(jax.random.PRNGKey(seed))

        # Grammar-constraint mask: one float32 [slots, V] additive bias
        # (0 allowed / -1e30 blocked), host-mutated as slot FSMs advance and
        # re-shipped before the next masked dispatch. Lazily allocated — an
        # engine that never sees a constrained request never pays the HBM or
        # the H2D, and sample_tokens gets mask_bias=None (the original
        # compiled path, bit for bit). Compiler is installed by the service
        # layer (it owns the tokenizer); direct-core users may leave it None
        # and pre-compile Request.compiled_constraint themselves.
        self.constraint_compiler = None
        self._mask_bias: np.ndarray | None = None
        self._d_mask: jnp.ndarray | None = None
        # Rows changed since the last device sync: one FSM advance dirties
        # ONE row, and shipping only those keeps the per-token H2D at
        # rows×V·4B instead of slots×V·4B (32 MiB/token at 64×128k).
        self._mask_dirty_rows: set[int] = set()
        self._constrained_count = 0

        # Per-position verify mask: a persistent [slots, K+1, V] device
        # buffer (lazily allocated — spec-free and constraint-free engines
        # never pay the HBM), refreshed per step ONLY for rows that are
        # masked now or were last step (the lookahead states change every
        # step, but unconstrained rows stay zero and never ship) — the
        # verify-path analogue of the decode mask's dirty-row H2D contract.
        self._d_spec_mask: jnp.ndarray | None = None
        self._spec_masked_prev: set[int] = set()

        # Fused decode (docs/fused-decode.md): serve every decode step as
        # ONE device program — the burst scan (even at k=1) with sampling
        # inside, grammar masking via the device-resident transition table
        # (ops/grammar.py), and verify steps with in-program mask columns,
        # last-token splice, and accept counting. On by default;
        # LLMLB_FUSED_DECODE=0 keeps every legacy path bit for bit (tier-1
        # pinned).
        if fused_decode is None:
            env = os.environ.get("LLMLB_FUSED_DECODE", "").strip().lower()
            if env in ("1", "true", "on", "yes"):
                fused_decode = True
            elif env in ("0", "false", "off", "no"):
                fused_decode = False
            elif env:
                log.warning(
                    "LLMLB_FUSED_DECODE=%r is not a boolean; using the "
                    "default (on)", env,
                )
        if fused_decode is None:
            fused_decode = True
        self.fused_decode = bool(fused_decode)
        # Device grammar tables: one concatenated [rows, V] int32 next-state
        # array shared by every resident schema (row 0 = the free row).
        # Allocated only when fused decode is on — legacy engines keep the
        # host [slots, V] mask mirror below and never pay the table bytes.
        self._grammar_tables: GrammarTables | None = (
            GrammarTables(cfg.vocab_size) if self.fused_decode else None
        )
        self._grammar_warned = False
        # Flips (one-way) when a schema cannot go device-resident: host
        # mask rows are then maintained for every constrained slot so the
        # legacy fallback path masks mixed batches correctly.
        self._grammar_fallback = False

        # Context-window buckets (pow2, up to capacity): every decode reads
        # only the smallest bucket covering all active sequences, so
        # attention HBM traffic scales with the context in use instead of
        # the slot capacity (a 2048-cap cache at 300-token contexts was
        # spending ~85% of its cache bandwidth on empty cells).
        buckets = []
        w = 256
        while w < self.slot_capacity:
            buckets.append(w)
            w *= 2
        buckets.append(self.slot_capacity)
        self._window_buckets = tuple(buckets)

        # queue.Queue (not SimpleQueue): the multihost plan collector
        # snapshots .queue to find cancelled-but-still-queued requests;
        # in that mode the loop thread is both producer and consumer.
        self.pending: queue.Queue[Request] = queue.Queue()
        # Priority admission (docs/scheduling.md): the step loop drains
        # `pending` (the thread-safe intake) into per-class deques and
        # always serves the most important non-empty class, FIFO within a
        # class. Preempted requests re-enter at the FRONT of their class —
        # they already held a slot once. Step-loop-private state, so every
        # multihost host mirrors it deterministically from the plan order.
        self._class_queues: dict[int, collections.deque] = {
            p: collections.deque() for p in PRIORITY_CLASSES
        }
        # Chunked-prefill decode budget: max prompt tokens prefilled per
        # step-loop iteration WHILE other slots are decoding (0 = no cap).
        # Bounds the decoders' ITL regardless of arriving prompt size: a
        # long prompt runs as budget-sized chunks with decode steps between.
        if prefill_chunk_budget is None:
            try:
                prefill_chunk_budget = int(os.environ.get(
                    "LLMLB_PREFILL_CHUNK_BUDGET", "0") or 0)
            except ValueError:
                log.warning("LLMLB_PREFILL_CHUNK_BUDGET is not an integer; "
                            "budget disabled")
                prefill_chunk_budget = 0
        self.prefill_chunk_budget = max(0, int(prefill_chunk_budget))
        if (self.prefill_chunk_budget and self.prefill_buckets
                and self.prefill_chunk_budget < self.prefill_buckets[0]):
            # chunks must be compiled bucket sizes, so a budget below the
            # smallest bucket cannot be honored exactly
            log.warning(
                "prefill chunk budget %d is below the smallest prefill "
                "bucket; effective per-chunk floor is %d tokens",
                self.prefill_chunk_budget, self.prefill_buckets[0],
            )
        # Prompt tokens already dispatched to prefill in the CURRENT step-loop
        # iteration (_try_insert's one-shot batches). _advance_prefill only
        # spends what remains, so an iteration that both inserted a batch and
        # feeds a chunk stays bounded by the budget (+ at most one
        # minimum-bucket rounding) instead of paying each path a full budget.
        self._prefill_spent_iter = 0
        self.metrics = EngineMetrics()
        self.metrics.sampling = selection_plan(cfg.vocab_size)
        if self.lora is not None:
            self.lora.metrics = self.metrics
        # Step introspection (engine/stepstats.py): per-step span records,
        # slow-step anomalies, and the sliding decode window live MFU math
        # reads. Always on — the recorder is one clock read per span
        # boundary (< 1% of step time, guarded by test_step_introspection).
        # Each loop thread keeps its own LoopClock (self._clock()).
        self.step_stats = StepRecorder()
        # Per-request flight recorder (engine/flightrec.py): one event per
        # lifecycle edge, keyed by the gateway's X-Request-Id, served at
        # /api/requests/{id}/timeline and joined cross-process by the
        # gateway's /api/traces/{id}?view=timeline. LLMLB_FLIGHTREC=0
        # disables it (emit() returns before its first clock read).
        self.flightrec = FlightRecorder()
        # Serialized exports captured at drain-park time, keyed by gateway
        # request id, served via POST /v1/kv/export so the gateway can move
        # a mid-stream request's KV to the adopting engine instead of
        # replaying. Bounded by num_slots per drain; entries are consumed on
        # fetch and dropped wholesale on shutdown.
        self._kv_exports: dict[str, dict] = {}
        # static per-token cost base for perf_info(): parameter count of the
        # served model (device arrays are cheap to .size). Scale leaves are
        # bookkeeping, not parameters — excluded from the FLOP count, as are
        # the LoRA pool leaves (mostly-empty adapter slots; the rank-R delta
        # FLOPs are noise next to the base matmuls); the measured byte
        # footprint (param_bytes) includes both so the HBM accounting stays
        # honest under int8 weights and resident adapters.
        self.n_params = sum(
            int(v.size) for k, v in self.params.items()
            if not (k.endswith("_scale") or "_lora_" in k)
        )
        self.param_bytes = sum(
            int(v.size) * jnp.dtype(v.dtype).itemsize
            for v in self.params.values()
        )
        # what the pool holds per slot beside its pages (a recurrent state)
        self.state_bytes = (num_slots * int(record.state_slot_bytes(cfg))
                            if self._slot_state else 0)
        self._running = False
        self._thread: threading.Thread | None = None
        self._started_at = time.monotonic()
        self.total_requests = 0
        self.total_tokens = 0
        self._lock = threading.Lock()

        # Which step loop this thread belongs to ("main" for the combined
        # loop; split mode tags its two threads "prefill"/"decode" and the
        # adoption path "handoff") — drives the per-loop prefill-dispatch
        # ledger below, the tier-1 proof that in split mode ZERO prefill
        # dispatches ever execute on the decode pool's loop.
        self._tls = threading.local()
        self.prefill_dispatch_by_loop: dict[str, int] = {
            "main": 0, "prefill": 0, "decode": 0, "handoff": 0,
        }
        # Decode-side companion ledger: device dispatches issued by
        # decode/verify steps, per loop. The fused-decode invariant —
        # exactly ONE dispatch per decode-loop step — is asserted over this
        # dict plus the per-step `dispatches` field on stepstats records
        # (scripts/check_fused_dispatch.py).
        self.decode_dispatch_by_loop: dict[str, int] = {
            "main": 0, "prefill": 0, "decode": 0, "handoff": 0,
        }
        if self.role == "split":
            from llmlb_tpu.disagg.split import SplitRuntime

            if self.coordinator is not None:
                raise ValueError(
                    "--role split is single-host only (multihost lockstep "
                    "broadcasts one plan per combined step loop)"
                )
            self.split = SplitRuntime(self, self._disagg_prefill_slots_arg)
        # the prompt width of this engine's mixed step; 0: no arrival rides
        self.mixed_width = self._mixed_prompt_width()
        # the mixed programs, by window: those the loop has dispatched a
        # burst in, in the order it first did (the prewarm thread builds
        # these, the latest first: _prewarm_mixed); those the thread has
        # built (an arrival rides a burst of such a window alone: _rides);
        # and those of them the loop has not CALLED yet — it calls each
        # once, at an empty house (_build_mixed_program)
        self._mixed_wanted: list[int] = []
        self._mixed_ready: set[int] = set()
        self._mixed_uncalled: list[int] = []

    def _mixed_prompt_width(self) -> int:
        """The ONE static prompt width T of this engine's mixed step
        (StepPrograms.admit_many: a decode burst whose first step carries an
        arrival's prompt), or 0 where no arrival rides a burst.

        Who may ride is read off what the engine is: the family's record
        offers the entry point (`Family.mixed_step`); the decode step is a
        burst program (a legacy single step is three dispatches already);
        the loop is alone (a coordinator's tick and split mode keep today's
        order anyway); and the engine carries nothing the mixed program
        does not serve — an ADAPTER POOL is that (its rows' deltas are
        gathered a row, and the prompt's T tokens would need the arrival's
        row each), and so are step counters a family computes on the device
        (the mixed program carries none out). An int8 pool and int8 weights ride as they are: the
        shared body writes and reads the pool through the same helpers as
        prefill and decode (tests/engine/test_mixed_step.py holds both).

        T is the largest one-shot bucket b with num_slots + b tokens under
        the RIDGE: the tokens a pass at which the weights' products stop
        being paid in bytes, peak FLOP/s x bytes a weight / (2 FLOPs a
        weight and token x peak bytes/s) — 197e12 x 2 / (2 x 0.82e12) = 240
        on a v5e at bf16 (engine/telemetry.CHIP_SPECS: the chip's published
        peaks), so 128 at 32 and at 64 slots. Under it the pass costs what a decode step costs and a
        shorter prompt padded into T costs what T does; one width keeps it
        at one program a window. A chip the table does not know (a CPU) is
        sized as the v5e."""
        from llmlb_tpu.engine.telemetry import chip_spec_for

        if (not self._record.mixed_step or self.block > 1
                or self.programs.counter_shapes
                or self.lora is not None or self.coordinator is not None
                or self.split is not None
                or not (self.decode_burst > 1 or self.fused_decode)):
            return 0
        devices = jax.local_devices()
        spec = (chip_spec_for(getattr(devices[0], "device_kind", ""))
                or chip_spec_for("v5e"))
        flops, weight_bytes = ((spec.int8_flops, 1) if self.quant.weights else
                               (spec.peak_flops,
                                jnp.dtype(self.cfg.dtype).itemsize))
        ridge = flops * weight_bytes / (2 * spec.peak_hbm_bw)
        fits = [b for b in self.prefill_buckets
                if self.num_slots + b <= ridge]
        return max(fits, default=0)

    def _check_family_engine(self, multihost: bool, lora: bool) -> None:
        """What an engine of this family refuses to start with rather than
        serve wrong or half done, read off its record: what the family does
        not serve; a block family's sizes and what it is not served with
        yet (docs/block-diffusion.md); what would read or move pages whose
        state per slot is not with them (docs/hybrid-state.md)."""
        record, name = self._record, self.family.__name__
        record.refuse(int8_weights=self.quant.weights, lora=lora,
                      int8_kv=self.quant.kv)
        if self.block > 1:
            b = self.block
            sizes = {"slot_capacity": self.slot_capacity,
                     "kv_page_size": self.kv_page_size,
                     **{f"prefill bucket {n}": n
                        for n in self.prefill_buckets}}
            bad = [size for size, n in sizes.items() if n % b]
            if bad:
                raise ValueError(
                    f"a block length of {b} must divide {bad}: prefill "
                    "chunks, prefix-cache entries and pages end on block "
                    "boundaries")
            for on, what in (
                    (self.quant.kv, "an int8 KV pool (--quantize kv)"),
                    (self.spec.enabled, "speculative decoding"),
                    (self.role == "split", "--role split"),
                    (multihost, "multihost lockstep")):
                if on:
                    raise NotImplementedError(
                        f"{name} generates by diffusion over blocks, which "
                        f"is not served with {what} yet")
            # KV travels as bytes only between block boundaries, which a
            # park keeps to, but the adopter's activation would sample a
            # first token: a block family resumes by chunk-prefill replay
            # alone
            self.kv_ship = False
            self.kv_offload = None
        if self._slot_state:
            for on, what, why in (
                (self._prefix_cache_asked, "the prefix cache",
                 "a hit would extend behind pages that carry no state"),
                (self.spec.enabled, "speculative decoding",
                 "a rejected draft would leave the state advanced"),
                (self.kv_ship, "kv_ship",
                 "the state has no wire form: a handoff or resume replays"),
                (self.kv_offload is not None, "the KV offload tier",
                 "parked pages would come back without their state"),
                (self.role == "split", "--role split",
                 "the handoff moves pages by block table, not the state"),
            ):
                if on:
                    raise NotImplementedError(
                        f"{name} keeps a state per slot beside the page "
                        f"pool (a {record.pool}), which is not served with "
                        f"{what} yet ({why})")

    def _check_block_request(self, request: Request) -> None:
        """Raise ValueError for what a request asks of generation by
        diffusion over blocks that this engine cannot give it."""
        s = request.sampling
        asked = {k: getattr(s, k) for k in (
            "block_length", "denoising_steps", "remasking_strategy",
            "confidence_threshold") if getattr(s, k) is not None}
        if self.block == 1:
            if asked:
                raise ValueError(
                    f"{sorted(asked)} are parameters of generation by "
                    "diffusion over blocks; this model decodes one token a "
                    "step")
            return
        if s.constraint is not None:
            raise ValueError(
                "structured outputs (a grammar constraint) are not served "
                "by a model that generates by diffusion over blocks: the "
                "grammar's cursor advances token by token")
        if (s.speculative or {}).get("enabled"):
            raise ValueError(
                "speculative decoding is not served by a model that "
                "generates by diffusion over blocks")
        if asked.get("block_length", self.block) != self.block:
            raise ValueError(
                f"'block_length' must be the model's, {self.block}; got "
                f"{asked['block_length']}")
        cfg = self.cfg
        self._record.check_generation(
            self.block,
            asked.get("denoising_steps", cfg.denoising_steps),
            asked.get("remasking_strategy", cfg.remasking_strategy),
            asked.get("confidence_threshold", cfg.confidence_threshold))

    # ------------------------------------------------------------------ public

    def _loop_tag(self) -> str:
        return getattr(self._tls, "tag", "main")

    def _clock(self) -> LoopClock:
        """This thread's loop clock (engine/stepstats.py), made on first
        use: the step loops, and a test that drives the steps by hand."""
        clock = getattr(self._tls, "clock", None)
        if clock is None:
            clock = self._tls.clock = LoopClock(self.step_stats,
                                                self._loop_tag())
            self.metrics.loop_clocks[clock.tag] = clock
        return clock

    def _note_prefill_dispatch(self, ahead: bool = False) -> None:
        """Ledger every prefill dispatch by the loop that ran it. Split
        mode's acceptance invariant — the decode loop NEVER runs prefill —
        is asserted over this dict in tier-1. `ahead`: it leaves before the
        burst fetched in front of it is emitted (_admit_ahead)."""
        self.prefill_dispatch_by_loop[self._loop_tag()] += 1
        self.metrics.record_prefill_dispatch(ahead)

    def start(self) -> None:
        self._running = True
        if self.split is not None:
            self.split.start()
        else:
            self._thread = threading.Thread(
                target=self._loop, name="engine-step-loop", daemon=True
            )
            self._thread.start()
        if len(self._window_buckets) > 1 or self.mixed_width:
            # Pre-compile every window-bucket variant off-thread: the first
            # sequence to cross a bucket boundary must not stall every
            # in-flight stream behind a multi-second XLA compile — nor the
            # first arrival that rides a burst (the mixed program, which no
            # warm-up traffic reaches: _prewarm_mixed, _build_mixed_program).
            threading.Thread(
                target=self._prewarm, name="engine-prewarm",
                daemon=True,
            ).start()

    def _decode_operands(self, key) -> tuple:
        """What a dispatch hands this engine's decode program before the
        live rows: the dense burst's, or a block family's (the per-row state
        its program takes and returns; behind the tables what it only reads:
        each row's sampling params and its unmasking procedure)."""
        if self.block > 1:
            return (self.params, self._d_blk, self._d_masked,
                    self._d_seq_lens, self._d_left, self._d_skip,
                    self.cache_k, self.cache_v, self._d_block_tables,
                    self._d_temps, self._d_top_ps, self._d_top_ks,
                    self._d_seeds, self._d_per_pass, self._d_dynamic,
                    self._d_threshold, key)
        return (self.params, self._d_last_tokens, self._d_seq_lens,
                self.cache_k, self.cache_v, self._d_block_tables,
                self._d_temps, self._d_top_ps, self._d_top_ks,
                self._d_seeds, key)

    def _prewarm(self) -> None:
        """The prewarm thread: the mixed programs where this engine has
        them — no traffic's warm-up reaches those, and an arrival rides only
        a burst whose program stands (_rides) — else the pure decode
        bursts' windows. Not both: the thread shares the interpreter with
        the loop's own first builds, a traffic's warm-up reaches every
        window it will use through the loop anyway, and the windows'
        lowering lands under another key than a dispatch finds
        (StepPrograms.prewarm_mixed says why; ROADMAP Speed, "Set-up")."""
        compilelog.set_thread_class("prewarm")
        if self.mixed_width:
            self._prewarm_mixed()
        elif len(self._window_buckets) > 1:
            self._prewarm_windows()

    def _prewarm_mixed(self) -> None:
        """Lower the mixed program of every window the loop has dispatched a
        burst in (`_mixed_wanted`), off the loop's thread
        (StepPrograms.prewarm_mixed), the window it reached LAST first: a
        full house decodes in the window of its longest row, so the newest
        window is the one the next arrival will want to ride. A window no
        burst has run in has no row for an arrival to ride with, and is
        built the day one does; until its program stands an arrival there is
        prefilled ahead (_rides). Nothing is wanted before the loop's first
        burst, and by then a program has RETURNED the page pool: as
        fresh_kv_pool placed it, it stands under another cache key than
        every later dispatch finds it (the same bytes on the same devices),
        and what is built for that key is built for nothing."""
        asked: set[int] = set()
        while self._running:
            wanted = [w for w in self._mixed_wanted if w not in asked]
            if not wanted:
                time.sleep(0.05)
                continue
            window = wanted[-1]
            asked.add(window)
            if self.programs.prewarm_mixed(
                    window, self._decode_operands(self._key),
                    self.mixed_width):
                self._mixed_ready.add(window)
                self._mixed_uncalled.append(window)

    def _build_mixed_program(self) -> None:
        """CALL the mixed program (StepPrograms.admit_many) of the next
        window the prewarm thread has built and the loop has not called yet,
        at an EMPTY house: no row live, a prompt of one token into slot 0,
        whose table row names the trash page alone. The thread's lowering
        lands under a dispatch's cache key on one device; on a mesh of
        several it may not (another thread's), and a call is what a dispatch
        is: whatever is left to build is built here, with nobody decoding,
        and not at the first arrival that rides. It costs a burst; what it
        leaves in the per-slot state is what a never-used row holds anyway
        (its length counts on, slot 0's sampling row is rewritten at its
        next admission). The engine's key is read and not advanced: the
        sequence of keys the requests see is the one without the call."""
        window = self._mixed_uncalled.pop(0)
        self._clock().switch("other")
        # the rows freed since the last burst still name their old pages on
        # the device, and a prefix donor's are pinned: zero them first, as
        # host_sync does before every burst
        self._sync_block_tables()
        _, key = jax.random.split(self._key)
        riding = _Riding(
            0, None, 1, np.zeros((1, self.mixed_width), np.int32),
            np.asarray([0, 1, 0, -1], np.int32), np.ones((2,), np.float32))
        with compilelog.thread_class("prewarm"):  # what it builds is that
            jax.block_until_ready(
                self._dispatch_burst(window, key, [], False, {}, riding))

    def _house_is_empty(self) -> bool:
        """No slot holds a request and none waits: what the device's
        per-slot state holds is nobody's."""
        return (all(s.request is None for s in self.slots)
                and self._held_request is None and self.pending.empty()
                and not any(self._class_queues.values()))

    def _prewarm_windows(self) -> None:
        compilelog.set_thread_class("prewarm")
        # split keys keep the engine key's shape and dtype
        self.programs.prewarm(
            self._window_buckets, self._decode_operands(self._key),
            fused_decode=self.fused_decode, running=lambda: self._running)

    def stop(self) -> None:
        if self.coordinator is not None and self.coordinator.is_leader:
            # broadcast the shutdown through the tick plan so followers
            # leave their loops too (flipping _running here would strand
            # them blocked in the next exchange)
            self._stop_requested = True
        else:
            self._running = False
        if self.split is not None:
            self.split.join(timeout=30)
        if self._thread:
            self._thread.join(timeout=30)
        self._running = False
        # terminal events for everything still in flight so waiters unblock
        self._fail_all("engine shutting down")

    def submit(self, request: Request) -> Request:
        n = len(request.prompt_ids)
        if n == 0:
            self._release_lora(request)  # service may have pre-pinned
            raise ValueError("prompt must contain at least one token")
        if not self.prefill_buckets:
            self._release_lora(request)
            raise ValueError(
                "engine has no prefill buckets (slot capacity smaller than "
                "every configured bucket)"
            )
        # Prompts beyond the largest one-shot bucket run through chunked
        # prefill (programs.extend); the only hard cap is slot capacity.
        # a slot keeps `block` cells free past a sequence: the one that rows
        # which are not decoding write their garbage to, and for a block
        # family the rest of a block before it (block is 1 otherwise)
        if n + self.block >= self.slot_capacity:
            # a refused submit must not leak a pin the service layer's
            # prepare_lora already took for this request
            self._release_lora(request)
            raise ValueError(
                f"prompt of {n} tokens does not fit the slot capacity "
                f"({self.slot_capacity}) with room to generate"
            )
        try:
            self._check_block_request(request)
        except ValueError:
            self._release_lora(request)
            raise
        # LoRA: pin (and hot-load) the adapter BEFORE the request can reach
        # a slot — the step loop must never block on disk I/O, and eviction
        # must see queued/parked requests as active. Idempotent: the service
        # layer may have prepared off-loop already. Raises ValueError for
        # unknown/invalid adapters (the server maps it to a 400 naming the
        # 'lora' field).
        self.prepare_lora(request)
        with self._lock:
            self.total_requests += 1
        # the way in: `accept` ends and `inbox` begins at this read, and
        # time to first token is counted from it
        request.submitted_at = stepstats._now()
        self._fr_emit(request, "admitted", prompt_tokens=n,
                      queue_depth=self.pending.qsize())
        if self.coordinator is not None:
            # multihost: requests enter via the tick plan so every host
            # mirrors the same queue in the same order
            self._intake.put(request)
        else:
            self.pending.put(request)
        return request

    def prepare_lora(self, request: Request) -> None:
        """Resolve + pin a request's adapter (hot-loading it if cold).
        Callable off-loop (service layer) or from submit; idempotent per
        request. Raises ValueError when the request names an adapter this
        engine cannot serve."""
        name = request.sampling.lora
        if not name:
            return
        if self.lora is None:
            raise ValueError(
                "'lora' adapters are not enabled on this engine "
                "(start it with --lora-dir)"
            )
        t0 = time.perf_counter()
        self.lora.acquire(name, request.request_id)
        # fires once per acquire call; the submit-time re-acquire of a
        # service-prepared adapter shows as a second event with ~0 wait
        self._fr_emit(request, "lora_acquire", adapter=name,
                      wait_s=round(time.perf_counter() - t0, 6))

    def _release_lora(self, request: Request) -> None:
        """Unpin a request's adapter at its terminal event (idempotent —
        some paths fire twice for one request). Every site that records
        record_request_done pairs with one of these."""
        if self.lora is not None and request.sampling.lora:
            self.lora.release(request.request_id)

    def _fr_emit(self, request: Request, event: str, **attrs) -> None:
        """One flight-recorder event for a request. Every terminal path
        (finish / error / shed / park) must call this next to its event-queue
        put — statically enforced by scripts/check_lifecycle_events.py."""
        if self.flightrec.enabled:
            self.flightrec.emit(request.request_id, event, **attrs)

    def _lora_rows(self, requests) -> "np.ndarray":
        """Adapter pool rows for an ordered request list — the per-row
        index vector a prefill dispatch carries (activation then scatters
        the same rows into the per-slot device mirror)."""
        return np.asarray(
            [self.lora.slot_of(r.sampling.lora) for r in requests],
            np.int32,
        )

    def stats(self) -> EngineStats:
        active = sum(1 for s in self.slots if s.request is not None)
        queued = self.pending.qsize()
        queued += sum(len(q) for q in self._class_queues.values())
        if self._held_request is not None:
            queued += 1  # parked on page-pool pressure, still queued work
        if self.coordinator is not None:
            # Multihost: requests sitting in the leader's intake queue or
            # spilled to the next tick's plan backlog are queued work the
            # gateway's telemetry-aware placement must see (reading only
            # self.pending undercounted them).
            queued += self._intake.qsize() + len(self._plan_backlog)
        return EngineStats(
            num_slots=self.num_slots,
            active_slots=active,
            queued=queued,
            total_requests=self.total_requests,
            total_tokens=self.total_tokens,
            uptime_s=time.monotonic() - self._started_at,
        )

    # ------------------------------------------------------------------- loop

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"no prefill bucket for prompt of {n} tokens")

    # ------------------------------------------------------- multihost plans

    def _is_cancelled(self, request: Request) -> bool:
        """Deterministic cancellation check. Single-host reads the live flag;
        multihost reads the plan-mirrored set so every host sees the
        cancellation on the same tick."""
        if self.coordinator is None:
            return request.cancelled
        return request.request_id in self._cancelled_effective

    def _collect_plan(self) -> dict:
        """Leader: drain intake + gather cancellations into this tick's plan.
        Requests cancelled before ever entering a plan are finished here
        directly — no host (including this one) runs device ops for them.
        The plan payload is bounded here, at collection: a too-large batch
        spills to the next tick and an impossibly large single request is
        failed with a terminal event — never by raising mid-broadcast, which
        would desync the lockstep cluster."""
        from llmlb_tpu.engine.multihost import _MAX_PLAN_BYTES

        budget = _MAX_PLAN_BYTES // 8  # ~int32 tokens, pickled with overhead
        candidates = self._plan_backlog
        self._plan_backlog = []
        while True:
            try:
                candidates.append(self._intake.get_nowait())
            except queue.Empty:
                break
        new = []
        tokens = 0
        for idx, req in enumerate(candidates):
            if req.cancelled:
                req.events.put(("done", "cancelled"))
                self.metrics.record_request_done("cancelled")
                self._fr_emit(req, "finished", reason="cancelled")
                self._release_lora(req)
                continue
            if req.deadline_expired():
                # deadline shedding must be deterministic across hosts, so
                # multihost sheds HERE (leader-only, before the plan) — a
                # shed request never reaches any host's queue
                req.events.put(("error", "deadline exceeded before prefill"))
                self.metrics.record_request_done("error")
                self.metrics.record_deadline_shed()
                self._fr_emit(req, "shed", reason="deadline")
                self._release_lora(req)
                continue
            n = len(req.prompt_ids)
            if n > budget:
                req.events.put(("error", "prompt too large for a tick plan"))
                self.metrics.record_request_done("error")
                self._fr_emit(req, "errored",
                              message="prompt too large for a tick plan")
                self._release_lora(req)
                continue
            if tokens + n > budget:
                # spill THIS and everything behind it to the next tick's
                # backlog — arrival order is preserved, no starvation
                self._plan_backlog = candidates[idx:]
                break
            tokens += n
            new.append(req)
        cancelled = []
        in_flight = [s.request for s in self.slots if s.request is not None]
        if self._held_request is not None:
            in_flight.append(self._held_request)  # parked on the page pool
        # snapshot under the queue's own mutex — iterating .queue while a
        # concurrent put() mutates the deque is undefined; the lock makes the
        # snapshot atomic regardless of which thread produces into pending
        with self.pending.mutex:
            in_flight += list(self.pending.queue)
        in_flight += self._queued_requests()  # drained into class deques
        for req in in_flight:
            if req.cancelled and req.request_id not in self._cancelled_effective:
                cancelled.append(req.request_id)
        return {
            "new": new,  # leader keeps real objects; followers get payloads
            "cancelled": cancelled,
            "stop": self._stop_requested,
        }

    def _plan_wire(self, plan: dict) -> dict:
        """Wire form of a plan (shadow payloads instead of Request objects)."""
        return {
            "new": [
                {
                    "request_id": r.request_id,
                    "prompt_ids": list(r.prompt_ids),
                    "sampling": dataclasses.asdict(r.sampling),
                }
                for r in plan["new"]
            ],
            "cancelled": plan["cancelled"],
            "stop": plan["stop"],
        }

    def _apply_plan(self, plan: dict, local: dict | None) -> None:
        """Every host: enqueue this tick's requests in plan order (the leader
        re-queues its real Request objects, followers build shadows whose
        event queues simply go unread) and mirror cancellations."""
        if local is not None:  # leader
            for req in local["new"]:
                self.pending.put(req)
        else:
            for payload in plan["new"]:
                self.pending.put(Request(
                    prompt_ids=payload["prompt_ids"],
                    sampling=SamplingParams(**payload["sampling"]),
                    request_id=payload["request_id"],
                ))
        self._cancelled_effective |= set(plan["cancelled"])
        if plan["stop"]:
            self._running = False

    def _lockstep_tick(self) -> None:
        local = None
        if self.coordinator.is_leader:
            local = self._collect_plan()
            wire = self._plan_wire(local)
        else:
            wire = None
        plan = self.coordinator.exchange(wire)
        self._apply_plan(plan, local)

    def _fetch_tokens(self, tokens_dev) -> np.ndarray:
        """D2H that works when the array spans non-addressable devices."""
        if self._replicate is not None:
            tokens_dev = self._replicate(tokens_dev)
        return np.asarray(tokens_dev)

    def begin_drain(self) -> None:
        """Stop admitting new work; in-flight slots keep decoding. One-way —
        the draining process exits or is restarted by its supervisor."""
        self.draining = True

    def request_drain_park(self) -> None:
        """Ask the step loop to park every decoding slot at its next
        iteration (the drain grace expired). Thread-safe: a plain bool write
        consumed by the loop thread, like Request.cancelled."""
        self._drain_park_requested = True

    def request_park(self, gateway_id: str) -> None:
        """Ask the step loop to park ONE stream (by gateway request id) at
        its next iteration and spill its KV for export — a proactive
        migration is pulling the stream to another engine while this one
        keeps serving everyone else. Thread-safe the same way as
        request_drain_park: the set is only consumed by the loop thread."""
        self._park_rids.add(gateway_id)

    def request_drain_flush(self) -> None:
        """Ask the step loop to terminal-error everything still queued
        (parked-for-drain work included). Called AFTER the drain aborted
        the in-flight connections: the committed tokens live on in the
        gateway's replay ledger, but the HTTP handlers blocked on these
        requests' event queues must unblock or they would pin executor
        threads (and the server's shutdown) forever."""
        self._drain_flush_requested = True

    def _drain_flush_all(self) -> None:
        """Loop thread only (queues are loop-thread-owned)."""
        self._drain_pending()
        flushed: list[Request] = []
        for p in PRIORITY_CLASSES:
            q = self._class_queues[p]
            while q:
                flushed.append(q.popleft())
        if self._held_request is not None:
            flushed.append(self._held_request)
            self._held_request = None
        for request in flushed:
            request.events.put(("error", "engine draining"))
            self.metrics.record_request_done("error")
            self._fr_emit(request, "errored", message="engine draining")
            self._release_lora(request)
        if flushed:
            log.info("drain flushed %d queued request(s)", len(flushed))

    def _drain_park_all(self) -> None:
        """Park every parkable decoding slot (loop thread only). Prefilling
        and first_pending slots cannot park (incomplete KV / device-only
        last token) — their connections are aborted by the server instead,
        and the gateway resumes them from its own replay ledger."""
        for i, slot in enumerate(self.slots):
            if (slot.request is not None and not slot.prefilling
                    and not slot.first_pending and not slot.handoff_ready):
                self._park_slot(i, reason="drain")
                self.metrics.record_drain_park()

    def _park_requested(self, rids: set[str]) -> None:
        """Park the slots serving these gateway request ids (loop thread
        only) — the per-stream migration park. Unparkable states (prefill
        in flight, first token device-only) and ids not decoding here are
        dropped: the gateway's export fetch times out and the migration
        aborts with the origin stream untouched."""
        for i, slot in enumerate(self.slots):
            if (slot.request is not None and not slot.prefilling
                    and not slot.first_pending and not slot.handoff_ready
                    and gateway_rid(slot.request.request_id) in rids):
                self._park_slot(i, reason="migrate")

    def _loop(self) -> None:
        compilelog.set_thread_class("loop")
        # Every stretch of this thread's time goes to one bucket of its
        # clock (engine/stepstats.py): the steps open and close themselves,
        # the rest is switched here — three clock reads on an idle iteration.
        clock = self._clock()
        while self._running:
            did_work = False
            try:
                did_work = self._loop_once(clock)
            except Exception:  # pragma: no cover - defensive: fail loud, keep serving
                log.exception("engine step failed; resetting engine state")
                clock.abandon()
                self._fail_all("engine step error")
                # prefill/decode donate the caches: after a failed dispatch the
                # buffers may already be consumed — rebuild before serving again.
                self._reset_caches()
            if not did_work:
                if self._mixed_uncalled and self._house_is_empty():
                    self._build_mixed_program()
                    continue
                clock.switch("idle")
                time.sleep(0.001)

    def _loop_once(self, clock: LoopClock) -> bool:
        """One iteration of the step loop: the control requests, admission,
        one prefill chunk, the decode step (which may be several bursts,
        _decode_bursts). True: it did work, or the plan said stop."""
        if self.coordinator is not None:
            clock.switch("control")
            self._lockstep_tick()
            if not self._running:
                return True
        if self._drain_park_requested:
            clock.switch("control")
            self._drain_park_requested = False
            self._drain_park_all()
        if self._park_rids:
            clock.switch("control")
            rids = self._park_rids
            self._park_rids = set()
            self._park_requested(rids)
        if self._drain_flush_requested:
            clock.switch("control")
            self._drain_flush_requested = False
            self._drain_flush_all()
        clock.switch("admit")
        did_work = self._try_insert()
        clock.switch("other")
        # At most ONE prefill chunk per iteration: decode steps run
        # between chunks, so active slots keep emitting tokens during
        # a long prompt's prefill (prefill/decode interleaving).
        did_work |= self._advance_prefill()
        did_work |= self._decode_active()
        return did_work

    def _on_mesh(self, x):
        """Place the loop's small device state (the per-slot arrays, the
        PRNG key) where every program that returns it leaves it: replicated
        over the mesh. An array that changed placement after its first
        program would build each program that takes it a second time."""
        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _init_slot_state(self) -> None:
        """Sampling params, lengths and last tokens live ON DEVICE, one row
        per slot, written at activation only — the decode hot loop does
        zero H2D."""
        n = self.num_slots
        self._d_seq_lens = self._on_mesh(np.zeros((n,), np.int32))
        self._d_temps = self._on_mesh(np.ones((n,), np.float32))
        self._d_top_ps = self._on_mesh(np.ones((n,), np.float32))
        self._d_top_ks = self._on_mesh(np.zeros((n,), np.int32))
        self._d_last_tokens = self._on_mesh(np.zeros((n,), np.int32))
        # Per-slot sampling seeds (-1 = shared batch key); always passed to
        # sample_tokens — unseeded rows are bit-identical to the pre-seed
        # path, so goldens hold.
        self._d_seeds = self._on_mesh(np.full((n,), -1, np.int32))
        # Per-slot LoRA adapter pool rows (0 = identity/no adapter),
        # scattered at activation like the sampling params so the decode
        # hot loop does zero per-step H2D. Only consulted when self.lora
        # is set — LoRA-free engines pass lora_idx=None to every dispatch
        # (the original compiled programs, bit for bit).
        self._d_lora_idx = self._on_mesh(np.zeros((n,), np.int32))
        if self.block > 1:
            # A block family's row (_decode_operands): the open
            # block's ids and which of them are still masked, the positions
            # it may yet commit, the given tokens at the open block's head;
            # and the request's procedure. `_d_seq_lens` is the COMMITTED
            # length; `_d_last_tokens` is unused.
            b = self.block
            self._d_blk = self._on_mesh(
                np.full((n, b), self.cfg.mask_token_id, np.int32))
            self._d_masked = self._on_mesh(np.ones((n, b), np.bool_))
            self._d_left = self._on_mesh(np.zeros((n,), np.int32))
            self._d_skip = self._on_mesh(np.zeros((n,), np.int32))
            self._d_per_pass = self._on_mesh(np.ones((n,), np.int32))
            self._d_dynamic = self._on_mesh(np.zeros((n,), np.bool_))
            self._d_threshold = self._on_mesh(np.ones((n,), np.float32))

    def _reset_caches(self) -> None:
        # every page mapping is void with the rebuilt pool
        self.page_pool.reset()
        self._slot_pages = [[] for _ in range(self.num_slots)]
        self._block_tables[:] = 0
        self._d_block_tables = jnp.asarray(self._block_tables)
        self._tables_dirty = False
        self._in_flight = None
        self.cache_k, self.cache_v = self.programs.fresh_kv_pool(
            self.kv_num_pages, self.kv_page_size, self.quant.kv)
        self._seq_lens[:] = 0
        # activation donates the per-slot arrays like the caches
        self._init_slot_state()
        if self.prefix_cache is not None:
            # the rebuilt cache holds zeros; every pinned prefix is gone
            self.prefix_cache.clear()
        self._prefix_pinned_pages = 0

    def _record_step(self, kind: str, step: StepSpan, **counts) -> None:
        """Close one step (its last stamp) and finalize its record
        (_observe_step, whose arguments `counts` are). What that costs after
        the step's last stamp is the next record's since_prev.record_s."""
        clock = self._clock()
        clock.close(step, kind)
        self._observe_step(kind, step, **counts)
        clock.resume(step)

    def _observe_step(self, kind: str, step: StepSpan, *,
                      active_slots: int = 0, tokens: int = 0,
                      slots: "list[int] | None" = None,
                      dispatches: int = 0, fused: bool = False,
                      kv_pages: "dict[str, int] | None" = None,
                      counters: "dict | None" = None,
                      block: "dict[str, int] | None" = None,
                      burst: "_Burst | None" = None,
                      ahead: "bool | None" = None,
                      chunk: "dict[str, int] | None" = None) -> None:
        """Finalize the record of a CLOSED step: the admission time since
        the previous record becomes its plan phase, the record feeds the
        ring buffer + anomaly detector, and the phase durations are mirrored
        into the Prometheus histograms. `slots` names the slot ids this
        dispatch touched: their requests' gateway ids land on the StepRecord
        (so /api/steps?slow=1 names the victims) and a flagged step writes a
        slow_step event into each victim's flight record, with the span or
        bucket that held the time. `dispatches` is the honest device-program
        count this step issued (decode/verify kinds feed the per-loop
        dispatch ledger and the fused-decode "exactly one" invariant);
        `fused` marks steps served by the single-program path. `kv_pages`
        (_kv_pages, decode records) lands on the record and in the engine's
        two running totals: the share of a (slots x window) sweep that live
        pages are. `counters` are the family's step counters of this
        dispatch (a mixture's expert load): the scalars land on the record,
        everything in the engine's running totals. `block` are a block
        family's counts of the burst (_emit_blocks): on the record and in
        the running totals likewise. `burst` is a decode burst's:
        whether it left ahead of its predecessor's emit, or ahead of its
        fetch even, and why not, on the record (`dispatched_ahead`,
        `queued_behind`, `ahead_blocked_by`: one of the three says which
        order the cycle took; `admitted` where an arrival's prompt rode its
        first step) and in the running totals. `ahead` is a
        one-shot prefill group's: whether it left before the burst in front
        of it was emitted (`dispatched_ahead` on its record). `chunk` is a
        chunked prompt's prefill step's: which chunk of its prompt it is
        (`index`, from 0), where the chunk starts (`pos`) and the prompt's
        tokens (`of`), on the record. A step that LoopClock.handover closed
        is observed inside its successor, under `emit_inflight`."""
        phases = step.phases()
        request_ids: dict[str, str] | None = None
        if slots:
            request_ids = {}
            for i in slots:
                r = self.slots[i].request
                if r is not None:
                    request_ids[str(i)] = gateway_rid(r.request_id)
        if kind in ("decode", "verify") and dispatches > 0:
            self.decode_dispatch_by_loop[self._loop_tag()] += dispatches
            self.metrics.record_decode_dispatches(dispatches, fused=fused)
        extra = {**(kv_pages or {}), **(block or {})}
        extra.update((name, v) for name, v in (counters or {}).items()
                     if isinstance(v, int))  # the scalars; not the histogram
        if burst is not None:
            extra["dispatched_ahead"] = (burst.blocked_by is None
                                         and not burst.queued)
            extra["queued_behind"] = burst.queued
            extra["ahead_blocked_by"] = burst.blocked_by
            if burst.admitted is not None:
                extra["admitted"] = {"slot": burst.admitted.slot,
                                     "prompt_tokens": burst.admitted.tokens}
            self.metrics.record_decode_burst(burst.blocked_by,
                                             queued=burst.queued)
        if ahead is not None:
            extra["dispatched_ahead"] = ahead
        if chunk is not None:
            extra["chunk"] = chunk
        if self._first_tokens:
            # the requests whose first token this step's fetch brought
            # (_first_token); absent on every other record
            extra["first_tokens"] = self._first_tokens
            self._first_tokens = []
        slow = self.step_stats.observe(kind, phases,
                                       active_slots=active_slots,
                                       tokens=tokens,
                                       request_ids=request_ids,
                                       dispatches=dispatches, span=step,
                                       extra=extra or None)
        if kv_pages:
            self.metrics.record_decode_kv_pages(**kv_pages)
        if counters:
            self.metrics.record_step_counters(counters)
        if block:
            self.metrics.record_block_passes(block)
        self.metrics.record_step_phases(phases, slow=slow)
        if slow and request_ids and self.flightrec.enabled:
            total = round(sum(phases.values()), 6)
            for rid in request_ids.values():
                self.flightrec.emit(rid, "slow_step", kind=kind,
                                    total_s=total, step_seq=step.seq,
                                    slow_in=step.slow_in)

    # Same-bucket pending prompts prefill TOGETHER in one dispatch (padded to
    # a power-of-two group so the jit cache stays at log2 sizes). Bounded so
    # a deep backlog cannot starve decode for longer than one group's
    # prefill; the loop comes back around for the rest.
    MAX_PREFILL_GROUP = 8

    def _free_slots(self) -> list[int]:
        """Slots available for new requests: every unoccupied one (prefix
        donors pin pages, not slots). Split mode admits only into the
        prefill pool (the decode pool fills exclusively through handoff
        adoption)."""
        if self.split is not None:
            return self.split.free_prefill_slots()
        return [i for i, s in enumerate(self.slots) if s.request is None]

    # ------------------------------------------ priority classes / preemption

    @staticmethod
    def _priority_of(request: Request) -> int:
        try:
            p = int(request.sampling.priority)
        except (TypeError, ValueError):
            p = PRIORITY_NORMAL
        return min(PRIORITY_LOW, max(PRIORITY_HIGH, p))

    def _effective_prompt(self, request: Request) -> list[int]:
        """The token sequence an insert must land in KV: the prompt, plus —
        for a preempted request resuming — every token it already emitted.
        Chunk-prefilling the committed sequence puts each token's KV at the
        exact position the uninterrupted run had it, and the activation
        sample (step = len-1) draws the exact PRNG fold the next decode
        token would have used, so the resumed stream is token-identical."""
        if request.parked is not None:
            return list(request.prompt_ids) + request.parked.tokens
        return request.prompt_ids

    def _drain_pending(self) -> None:
        while True:
            try:
                r = self.pending.get_nowait()
            except queue.Empty:
                return
            r.taken_at = stepstats._now()  # the way in: `inbox` ends
            cls = self._priority_of(r)
            self._class_queues[cls].append(r)
            self._fr_emit(r, "queued", cls=PRIORITY_NAMES[cls],
                          position=len(self._class_queues[cls]) - 1)

    def _queued_requests(self) -> list[Request]:
        out: list[Request] = []
        for p in PRIORITY_CLASSES:
            out.extend(self._class_queues[p])
        return out

    def _pop_request(self) -> Request | None:
        """Next request to admit: strictly by class. The held (page-starved)
        request keeps its place at the FRONT of its own class — but a
        MORE-important class still pops first, else a low-priority request
        wedged on the page pool would block the very arrival whose
        page-pressure preemption could unwedge it (priority inversion)."""
        held = self._held_request
        held_prio = self._priority_of(held) if held is not None else None
        for p in PRIORITY_CLASSES:
            if held_prio is not None and p >= held_prio:
                break
            q = self._class_queues[p]
            if q:
                return q.popleft()
        if held is not None:
            self._held_request = None
            return held
        for p in PRIORITY_CLASSES:
            q = self._class_queues[p]
            if q:
                return q.popleft()
        return None

    def _head_priority(self) -> int | None:
        """Priority of the next request _pop_request would return."""
        best: int | None = None
        if self._held_request is not None:
            best = self._priority_of(self._held_request)
        for p in PRIORITY_CLASSES:
            if self._class_queues[p]:
                return p if best is None else min(best, p)
        return best

    def _hold_on_pool(self, request: Request) -> None:
        """Queue a page-starved request for the next tick's retry. Only one
        hold slot exists; a request popped PAST a still-held one (a
        more-important class, see _pop_request) must not overwrite it —
        the overwritten request's event queue would never answer."""
        if self._held_request is None:
            self._held_request = request
        else:
            self._class_queues[self._priority_of(request)].appendleft(request)

    def _preempt_candidates(self, prio: int) -> list[int]:
        """Decoding slots a class-`prio` request may park, least important
        first, then least committed tokens (cheapest re-prefill), then slot
        id — a deterministic order every multihost mirror computes
        identically. Prefilling slots are never parked (their KV is
        incomplete), and first_pending slots' last token is device-only, so
        parking one would lose it."""
        out = [
            i for i, s in enumerate(self.slots)
            if (s.request is not None and not s.prefilling
                and not s.first_pending
                and self._priority_of(s.request) > prio)
        ]
        out.sort(key=lambda i: (-self._priority_of(self.slots[i].request),
                                int(self._seq_lens[i]), i))
        return out

    def _finish_slot(self, slot_id: int, reason: str) -> None:
        """Terminal teardown of an occupied slot outside the decode-emit
        path (prefill-time cancellation, split-mode staged drops): terminal
        event + accounting, cache entry / KV pages / constraint released,
        and EVERY slot field reset. One copy of the invariant — a new _Slot
        field (the handoff_* trio being the cautionary tale) has exactly
        one place to be cleared."""
        slot = self.slots[slot_id]
        request = slot.request
        assert request is not None
        request.finished_at = stepstats._now()
        request.events.put(("done", reason))
        self.metrics.record_request_done(reason)
        self._fr_emit(request, "finished", reason=reason,
                      generated=slot.generated)
        self._release_lora(request)
        self._cancelled_effective.discard(request.request_id)
        self._release_cache_entry(slot)
        self._free_slot_kv(slot_id)
        self._clear_constraint(slot_id)
        slot.request = None
        slot.generated = 0
        slot.prefilling = False
        slot.prefill_pos = 0
        slot.handoff_ready = False
        slot.handoff_logits = None
        slot.handoff_ready_at = 0.0
        slot.last_emit_at = 0.0
        slot.first_pending = False
        slot.drafter = None
        slot.spec_k = 0
        slot.out_tokens = []

    def _park_slot(self, slot_id: int, reason: str = "preempt") -> None:
        """Preempt one decoding slot: release its KV (pages back to the pool
        — parking is cheap BECAUSE KV is paged), capture resume
        state on the request, and requeue it at the front of its class. The
        grammar cursor and drafter park WITH the request; a resume must
        never re-walk the FSM from its start state. `reason` tags the flight
        record: preempt (priority arrival) | drain | pages (pool
        exhaustion)."""
        slot = self.slots[slot_id]
        request = slot.request
        assert request is not None and not slot.prefilling
        # a park reads the row's mirrors (out_tokens, _seq_lens) and may
        # spill its pages: with a burst in flight the mirrors lag and the
        # pages are still written (docs/kv-cache.md)
        assert self._in_flight is None, "park with a burst in flight"
        request.parked = ParkedState(
            generated=slot.generated,
            tokens=list(slot.out_tokens),
            constraint=slot.constraint,
            drafter=slot.drafter,
            spec_k=slot.spec_k,
        )
        # KV leaves the device BEFORE the pool reclaims it: a draining
        # engine records the wire payload for /v1/kv/export, the offload
        # tier keeps it for a local restore (docs/kv-cache.md)
        self._spill_parked_slot(slot_id, request, reason)
        self._release_cache_entry(slot)
        self._free_slot_kv(slot_id)
        if slot.constraint is not None:
            # cursor parked above — tear down only the live mask row
            self._constrained_count -= 1
            if self._mask_bias is not None:
                self._mask_bias[slot_id] = 0.0
                self._mask_dirty_rows.add(slot_id)
            slot.constraint = None
        slot.request = None
        slot.generated = 0
        slot.last_emit_at = 0.0
        slot.first_pending = False
        slot.prefilling = False
        slot.prefill_pos = 0
        slot.out_tokens = []
        slot.drafter = None
        slot.spec_k = 0
        self.metrics.record_preemption()
        self._fr_emit(request, "parked", reason=reason,
                      generated=len(request.parked.tokens))
        log.info("preempted request %s at %d committed tokens (priority %s)",
                 request.request_id, len(request.parked.tokens),
                 PRIORITY_NAMES[self._priority_of(request)])
        self._class_queues[self._priority_of(request)].appendleft(request)

    def _preempt_for_pages(self, prio: int) -> bool:
        """Page pressure: park one less-important slot that actually holds
        pages, so the reservation retry can succeed. False when no eligible
        victim exists (the caller then holds the request as before)."""
        for i in self._preempt_candidates(prio):
            if self._slot_pages[i]:
                self._park_slot(i, reason="pages")
                return True
        return False

    def _shed_expired(self, request: Request) -> bool:
        """Deadline shedding at admission (single-host only: clocks differ
        across hosts, so multihost sheds at the leader's plan collection
        instead). Never sheds a resumed request — the client already holds
        part of its stream."""
        if (self.coordinator is not None or request.parked is not None
                or not request.deadline_expired()):
            return False
        request.events.put(("error", "deadline exceeded before prefill"))
        self.metrics.record_request_done("error")
        self.metrics.record_deadline_shed()
        self._fr_emit(request, "shed", reason="deadline")
        self._release_lora(request)
        return True

    def _prefill_budget_now(self) -> int:
        """Prompt tokens this step-loop iteration may spend on prefill
        (0 = uncapped). The cap applies only while some slot is decoding —
        an idle engine prefills at full width."""
        b = self.prefill_chunk_budget
        if b <= 0:
            return 0
        if not any(s.request is not None and not s.prefilling
                   for s in self.slots):
            return 0
        return b

    def _budget_chunk_len(self, budget: int) -> int:
        """Largest prefill bucket within the budget (floor: the smallest
        bucket — chunks must be a compiled size)."""
        best = self.prefill_buckets[0]
        for bkt in self.prefill_buckets:
            if bkt <= budget:
                best = bkt
        return best

    def queue_class_depths(self) -> dict[str, int]:
        """Queued requests per priority class (held request included) for
        /metrics and the sched info block."""
        depths = {PRIORITY_NAMES[p]: len(self._class_queues[p])
                  for p in PRIORITY_CLASSES}
        held = self._held_request
        if held is not None:
            depths[PRIORITY_NAMES[self._priority_of(held)]] += 1
        return depths

    def sched_info(self) -> dict:
        """Scheduling block for /api/system, /api/health, and /metrics:
        priority-queue depths plus the overload-protection counters."""
        m = self.metrics
        info = {
            "prefill_chunk_budget": self.prefill_chunk_budget,
            "queued_by_class": self.queue_class_depths(),
            "preemptions_total": m.preemptions_total,
            "preempt_resumes_total": m.preempt_resumes_total,
            "deadline_shed_total": m.deadline_shed_total,
        }
        if self.split is not None:
            # role-labeled queue depths (docs/disaggregation.md): work still
            # waiting for a prefill slot vs prefilled work waiting for a
            # decode slot to adopt it (the handoff backlog)
            info["queued_by_role"] = {
                "prefill": sum(info["queued_by_class"].values()),
                "decode": self.split.backlog(),
            }
        return info

    def disagg_info(self) -> dict:
        """Disaggregation block for /api/system and /api/health: the served
        role, split-pool sizes, and the handoff counters every consumer of
        the docs/disaggregation.md surfaces reads."""
        m = self.metrics
        info = {
            "role": self.role,
            "split": self.split is not None,
            "handoff_total": dict(m.handoff_total),
            "handoff_backlog": m.handoff_backlog,
        }
        if self.split is not None:
            info["prefill_slots"] = len(self.split.prefill_pool)
            info["decode_slots"] = len(self.split.decode_pool)
        return info

    # -------------------------------------------------------------- page pool

    def _pages_for_tokens(self, n: int) -> int:
        return -(-n // self.kv_page_size)

    def _try_reserve_pages(self, count: int) -> list[int] | None:
        """Alloc `count` fresh pages, evicting prefix-cache pages LRU under
        pool pressure. None (no side effects beyond the evictions) when the
        pool still cannot cover the request."""
        if count <= 0:
            return []
        while True:
            pages = self.page_pool.alloc(count)
            if pages is not None:
                return pages
            if self.prefix_cache is None or not self._evict_one_prefix():
                return None

    def _assign_slot_pages(self, slot_id: int, shared, fresh) -> None:
        """Install a slot's block-table row: `shared` donor pages first
        (zero-copy prefix reuse — the slot takes a reference on each, no KV
        bytes move), then `fresh` pages (refcount 1 from alloc, owned)."""
        for p in shared:
            self.page_pool.ref(p)
        row = list(shared) + list(fresh)
        self._slot_pages[slot_id] = row
        self._block_tables[slot_id, :] = 0
        self._block_tables[slot_id, :len(row)] = row
        self._tables_dirty = True

    def _extend_slot_pages(self, slot_id: int, fresh: list[int]) -> None:
        row = self._slot_pages[slot_id]
        start = len(row)
        row.extend(fresh)
        self._block_tables[slot_id, start:start + len(fresh)] = fresh
        self._tables_dirty = True

    def _pages_short(self, slot_id: int, tokens: int) -> int:
        """Pages the slot lacks to hold `tokens` positions (capped at its
        capacity); zero or less: it has them."""
        target = min(tokens, self.slot_capacity)
        return self._pages_for_tokens(target) - len(self._slot_pages[slot_id])

    def _free_slot_kv(self, slot_id: int) -> None:
        """Return a slot's pages to the pool (shared prefix pages just drop
        this slot's reference; the donor entry keeps them alive) and point
        its table row at the trash page so the batched decode step's ongoing
        garbage writes for the freed row can never land in a page a new
        owner holds."""
        pages = self._slot_pages[slot_id]
        if pages:
            for p in pages:
                self.page_pool.unref(p)
            self._slot_pages[slot_id] = []
            self._block_tables[slot_id, :] = 0
            self._tables_dirty = True

    def _sync_block_tables(self) -> None:
        """Refresh the device block tables before a dispatch that reads them
        (one small H2D, only when a row changed since the last sync). A
        COPY goes: a burst in flight keeps the tables it left with while the
        host frees and grows rows under it (docs/kv-cache.md), and the CPU
        backend would alias an aligned host array, not copy it."""
        if self._tables_dirty:
            self._d_block_tables = jnp.asarray(self._block_tables.copy())
            self._tables_dirty = False

    def _ensure_decode_pages(self, active: list[int], k: int,
                             per_row: dict[int, int] | None = None
                             ) -> list[int]:
        """Alloc-on-extend before a decode dispatch: grow each active row's
        page list to cover the k tokens the dispatch writes (`per_row`
        overrides k per slot — the verify dispatch writes a different chunk
        per row, and padded positions beyond a row's allocation land on the
        trash page, so over-allocating for them would just churn pages).
        Under pool exhaustion prefix-cache pages are evicted first; if the
        pool STILL cannot cover a row, that request finishes with 'length' —
        the step loop must never crash or deadlock on a full pool. Returns
        the rows that remain active."""
        kept = []
        for i in active:
            slot = self.slots[i]
            if slot.request is None:
                # parked by a page-pressure preemption earlier in this walk
                continue
            kk = per_row.get(i, k) if per_row is not None else k
            need = self._pages_short(i, int(self._seq_lens[i]) + kk + 1)
            if need > 0:
                fresh = self._try_reserve_pages(need)
                # a more important row may park less important decoders
                # before giving up (their pages come back to the pool)
                while fresh is None and self._preempt_for_pages(
                        self._priority_of(slot.request)):
                    fresh = self._try_reserve_pages(need)
                if fresh is None:
                    request = slot.request
                    if not slot.first_pending and len(active) > 1:
                        # Park rather than force-finish: the pre-preemption
                        # engine cut the request off at 'length' here; now
                        # it resumes token-identical once pages free up.
                        log.warning(
                            "page pool exhausted mid-decode; parking request "
                            "%s at %d tokens", request.request_id,
                            int(self._seq_lens[i]),
                        )
                        self._park_slot(i, reason="pages")
                        continue
                    log.warning(
                        "page pool exhausted mid-decode; finishing request "
                        "%s at %d tokens", request.request_id,
                        int(self._seq_lens[i]),
                    )
                    request.finished_at = stepstats._now()
                    request.events.put(("done", "length"))
                    self.metrics.record_request_done("length")
                    self._fr_emit(request, "finished", reason="length",
                                  generated=slot.generated, cause="pages")
                    self._release_lora(request)
                    self._cancelled_effective.discard(request.request_id)
                    self._free_slot_kv(i)
                    if slot.constraint is not None:
                        # only an UNaccepted grammar cut short is a violation
                        # (same rule as the length path in _emit)
                        if not slot.constraint.is_accepting:
                            self.metrics.record_constraint_violation()
                        self._clear_constraint(i)
                    slot.request = None
                    slot.generated = 0
                    slot.last_emit_at = 0.0
                    slot.first_pending = False
                    slot.drafter = None
                    slot.spec_k = 0
                    slot.out_tokens = []
                    continue
                self._extend_slot_pages(i, fresh)
            kept.append(i)
        return kept

    # ------------------------------------------------------------ kv transfer

    def _kv_dtype_name(self) -> str:
        return "int8" if self.quant.kv else str(jnp.dtype(self.cfg.dtype))

    def _kv_wire_cell(self) -> tuple[int, int] | None:
        """(kv heads, head dim) of the pages a KVSH payload carries, asked
        of the family; None where its pool has no wire form (a latent
        pool's two arrays are not K and V of one shape): such an engine
        ships nothing and adopts nothing, and every move replays."""
        return self._record.kv_wire_cell(self.cfg)

    def _kv_header(self, tokens: int, num_pages: int) -> KVWireHeader:
        num_kv_heads, head_dim = self._kv_wire_cell() or (0, 0)
        return KVWireHeader(
            version=KV_WIRE_VERSION,
            layers=self.cfg.num_layers,
            page_size=self.kv_page_size,
            num_kv_heads=num_kv_heads,
            head_dim=head_dim,
            kv_dtype=self._kv_dtype_name(),
            tokens=tokens,
            num_pages=num_pages,
        )

    def kv_restore_reason(self, header: KVWireHeader) -> str | None:
        """None when an inbound payload can land in THIS pool verbatim,
        else the fallback-counter reason (dtype | page_size | geometry)."""
        cell = self._kv_wire_cell()
        if cell is None:
            return "geometry"
        return kv_compat_reason(
            header,
            layers=self.cfg.num_layers,
            page_size=self.kv_page_size,
            num_kv_heads=cell[0],
            head_dim=cell[1],
            kv_dtype=self._kv_dtype_name(),
        )

    def _gather_kv_sections(self, pages: list[int]) -> dict[str, np.ndarray]:
        """D2H gather of the named pool pages into wire-section arrays
        [L, P', PS, K, D] (int8 pools gather {codes, scales} per cache).
        A plain read — the pool is untouched, so gathering before a free
        is always safe."""
        idx = jnp.asarray(pages, jnp.int32)
        sections: dict[str, np.ndarray] = {}
        if self.quant.kv:
            sections["k_q"] = np.asarray(self.cache_k["q"][:, idx])
            sections["k_s"] = np.asarray(self.cache_k["s"][:, idx])
            sections["v_q"] = np.asarray(self.cache_v["q"][:, idx])
            sections["v_s"] = np.asarray(self.cache_v["s"][:, idx])
        else:
            sections["k"] = np.asarray(self.cache_k[:, idx])
            sections["v"] = np.asarray(self.cache_v[:, idx])
        return sections

    def _capture_kv(self, pages: list[int], tokens: int) -> KVPages:
        return KVPages(header=self._kv_header(tokens, len(pages)),
                       sections=self._gather_kv_sections(pages))

    def _kv_export_payload(self, slot_id: int,
                           request: Request) -> dict | None:
        """Serialize the pages covering this slot's valid KV rows into a
        wire payload (the /v1/handoff pages attachment). None when there is
        nothing shippable."""
        tokens = int(self._seq_lens[slot_id])
        if (tokens <= 0 or not self._slot_pages[slot_id]
                or self._kv_wire_cell() is None):
            return None
        # a row that ends by its budget is not a row of the burst that left
        # ahead (_prepare_burst counts it out): nothing in flight writes
        # below `tokens`, and the gather below waits for what is in flight
        assert (self._in_flight is None
                or slot_id not in self._in_flight.slots)
        pages = self._slot_pages[slot_id][: self._pages_for_tokens(tokens)]
        t0 = time.monotonic()
        kvp = self._capture_kv(pages, tokens)
        payload = serialize_kv_pages(kvp.header, kvp.sections)
        dt = time.monotonic() - t0
        self.metrics.record_kv_ship(kvp.nbytes, dt)
        self._fr_emit(request, "kv_shipped", tokens=tokens,
                      pages=len(pages), bytes=kvp.nbytes,
                      seconds=round(dt, 6))
        return payload

    def take_kv_export(self, gateway_id: str) -> dict | None:
        """Consume a drain-park export (POST /v1/kv/export): the gateway
        fetches the parked stream's serialized pages from the draining
        origin and attaches them to /v1/resume on the adopter. One-shot —
        the payload is handed over exactly once."""
        with self._lock:
            return self._kv_exports.pop(gateway_id, None)

    def _land_kv_pages(self, kvp: KVPages, fresh: list[int]) -> None:
        """H2D: land the first len(fresh) shipped pages into pool pages
        `fresh` via the donated scatter. The page-index vector (and the
        sections) pad to the next power of two by repeating the last page —
        a duplicate scatter of identical bytes — so the jit cache stays at
        log2(pool) variants."""
        n = len(fresh)
        pad = 1
        while pad < n:
            pad *= 2

        def padded(name: str) -> jnp.ndarray:
            a = kvp.sections[name][:, :n]
            if pad > n:
                a = np.concatenate(
                    [a, np.repeat(a[:, -1:], pad - n, axis=1)], axis=1
                )
            return jnp.asarray(a)

        def side(prefix: str):
            if self.quant.kv:
                return {"q": padded(prefix + "_q"),
                        "s": padded(prefix + "_s")}
            return padded(prefix)

        idx = np.asarray(fresh + [fresh[-1]] * (pad - n), np.int32)
        self.cache_k, self.cache_v = _write_kv_pages(
            self.cache_k, self.cache_v, side("k"), side("v"),
            jnp.asarray(idx),
        )

    def _insert_restored(self, slot_id: int, request: Request,
                         prompt: list[int], n: int) -> bool:
        """Page-transfer activation (docs/kv-cache.md): land a shipped KV
        payload into freshly reserved pool pages and enter decode directly
        — ZERO prefill dispatches. The device row restores to
        seq_len = n-1 with committed[-1] pending: the next ordinary decode
        dispatch rewrites position n-1's KV (identical bytes — that row
        shipped too) and samples with the pre-increment fold n-1, exactly
        the dispatch the uninterrupted stream ran at this position, so
        greedy and seeded continuations stay token-identical on bf16 and
        int8 pools alike. Any refusal drops the payload, counts a
        reason-labeled fallback, and returns False — the caller continues
        into the chunk-prefill replay path; a bad payload never fails the
        request."""
        kvp = request.kv_restore
        request.kv_restore = None  # one-shot either way
        st = request.parked
        need_tokens = n - 1
        if (kvp is None or st is None or not st.tokens or need_tokens < 1
                or kvp.header.tokens < need_tokens):
            self.metrics.record_kv_ship_fallback("capacity")
            return False
        need_pages = self._pages_for_tokens(need_tokens)
        if need_pages > kvp.header.num_pages:
            self.metrics.record_kv_ship_fallback("capacity")
            return False
        fresh = self._try_reserve_pages(need_pages)
        while fresh is None and self._preempt_for_pages(
                self._priority_of(request)):
            fresh = self._try_reserve_pages(need_pages)
        if fresh is None:
            self.metrics.record_kv_ship_fallback("capacity")
            return False
        t0 = time.monotonic()
        self._land_kv_pages(kvp, fresh)
        self._assign_slot_pages(slot_id, (), fresh)

        slot = self.slots[slot_id]
        slot.request = request
        # parked cursors first: _attach_constraint/_attach_spec read
        # request.parked for the FSM cursor (already advanced over the
        # committed tokens) and the drafter index
        self._attach_constraint(slot_id, request)
        s = request.sampling
        seed = -1 if s.seed is None else (s.seed & 0x7FFFFFFF)
        self._d_temps = self._d_temps.at[slot_id].set(float(s.temperature))
        self._d_top_ps = self._d_top_ps.at[slot_id].set(float(s.top_p))
        self._d_top_ks = self._d_top_ks.at[slot_id].set(int(s.top_k))
        self._d_seeds = self._d_seeds.at[slot_id].set(seed)
        if self.lora is not None:
            self._d_lora_idx = self._d_lora_idx.at[slot_id].set(
                int(self._lora_rows([request])[0])
            )
        self._d_seq_lens = self._d_seq_lens.at[slot_id].set(need_tokens)
        self._d_last_tokens = self._d_last_tokens.at[slot_id].set(
            int(prompt[-1])
        )
        self._seq_lens[slot_id] = need_tokens
        slot.generated = st.generated
        slot.out_tokens = list(st.tokens)
        slot.prefilling = False
        slot.prefill_pos = 0
        slot.last_emit_at = 0.0
        # NOT first_pending: the next decode fetch's step row IS this
        # stream's next token (the deferred-first row is for activation
        # samples, which never happened here)
        slot.first_pending = False
        request.parked = None
        # no prefill: `place` and `prefill` stay absent from its way in
        self._stamp_activated((request,), stepstats._now())
        self.metrics.record_resume()
        self.metrics.record_kv_restore(kvp.nbytes)
        self._fr_emit(request, "kv_restored", source=kvp.source,
                      kind="stream", tokens=need_tokens, pages=need_pages,
                      bytes=kvp.nbytes,
                      seconds=round(time.monotonic() - t0, 6))
        self._fr_emit(request, "resumed", generated=st.generated,
                      via="kv_restore")
        log.info(
            "kv restore: request %s re-entered decode at %d tokens from %s "
            "(%d pages, %.1f KiB, zero prefill dispatches)",
            request.request_id, need_tokens, kvp.source, need_pages,
            kvp.nbytes / 1024,
        )
        return True

    def _spill_parked_slot(self, slot_id: int, request: Request,
                           reason: str) -> None:
        """Park-time D2H capture with two consumers: a DRAINING engine
        records a wire payload for the gateway's /v1/kv/export fetch (the
        mid-stream resume then moves bytes instead of replaying), and the
        offload tier keeps the pages host-side so a local re-activation
        restores instead of re-prefilling. Skips first_pending parks: with
        zero committed tokens the faithful resume is the replay path."""
        if not self._slot_pages[slot_id]:
            return
        slot = self.slots[slot_id]
        if slot.first_pending or not slot.out_tokens:
            return
        tokens = int(self._seq_lens[slot_id])
        if tokens <= 0:
            return
        pages = self._slot_pages[slot_id][: self._pages_for_tokens(tokens)]
        nbytes = len(pages) * kv_page_bytes(self.cfg, self.kv_page_size,
                                            quantized=self.quant.kv)
        # exports serve two callers: a draining engine spills EVERY park for
        # the gateway's resume fetch; a healthy engine spills only parks the
        # rebalancer explicitly requested (reason="migrate")
        want_export = (self.kv_ship and self._kv_wire_cell() is not None
                       and (self.draining or reason == "migrate"))
        tier = self.kv_offload
        want_tier = tier is not None and tier.would_admit(nbytes)
        if not (want_export or want_tier):
            return
        t0 = time.monotonic()
        kvp = self._capture_kv(pages, tokens)
        kvp.source = "offload"
        self.metrics.record_kv_ship(kvp.nbytes, time.monotonic() - t0)
        dest = []
        if want_export:
            payload = serialize_kv_pages(kvp.header, kvp.sections)
            with self._lock:
                self._kv_exports[gateway_rid(request.request_id)] = payload
            dest.append("export")
        if want_tier and tier.put_parked(request.request_id, kvp):
            dest.append("offload")
        if dest:
            self._fr_emit(request, "kv_spilled", reason=reason,
                          tokens=tokens, bytes=kvp.nbytes,
                          dest=",".join(dest))

    def _spill_prefix_entry(self, entry: PrefixEntry) -> None:
        """Prefix-cache eviction under page pressure: gather the entry's
        pages D2H into the offload tier before their references drop —
        the cold prefix stays warm in host RAM instead of vanishing.
        Request-anonymous, so this counts in metrics but not the flight
        record."""
        tier = self.kv_offload
        if tier is None or not entry.pages:
            return
        nbytes = len(entry.pages) * kv_page_bytes(
            self.cfg, self.kv_page_size, quantized=self.quant.kv
        )
        if not tier.would_admit(nbytes):
            return
        t0 = time.monotonic()
        kvp = self._capture_kv(list(entry.pages), len(entry.tokens))
        kvp.source = "offload"
        self.metrics.record_kv_ship(kvp.nbytes, time.monotonic() - t0)
        tier.put_prefix(entry.ns, entry.tokens, kvp)

    def _maybe_restore_prefix(self, request: Request, n: int) -> None:
        """Admission-time H2D promotion: if the offload tier holds a longer
        usable prefix of this prompt than the live radix cache, land it
        into fresh pages and re-insert it as a live entry — the ordinary
        zero-copy match below then serves it and only the suffix prefills.
        Failure is never fatal: pages unref'd, the cold path proceeds."""
        tier = self.kv_offload
        cache = self.prefix_cache
        if tier is None or cache is None:
            return
        ns = request.sampling.lora
        hit = tier.match_prefix(ns, request.prompt_ids, n - 1)
        if hit is None:
            return
        stored, kvp = hit
        # Usable head: capped at n-1 (one suffix token must prefill),
        # aligned down to the cache grain so the re-inserted entry obeys
        # the same alignment every live donation does. Pages are
        # position-independent, so slicing a long stored entry is free.
        usable = min(len(stored), n - 1)
        usable = (usable // self.prefix_align) * self.prefix_align
        if usable < cache.min_len:
            return
        tokens = tuple(stored[:usable])
        if cache.covers(tokens, ns) or self.kv_restore_reason(
                kvp.header) is not None:
            # live cache already serves it, or the payload was spilled by
            # an incompatible earlier config — drop silently (the tier
            # popped it; bytes free up either way)
            return
        pages_needed = usable // self.kv_page_size
        if pages_needed <= 0 or pages_needed > kvp.header.num_pages:
            return
        fresh = self._try_reserve_pages(pages_needed)
        if fresh is None:
            return  # pool pressure: re-prefill is the honest fallback
        t0 = time.monotonic()
        self._land_kv_pages(kvp, fresh)
        for stale in cache.evict_subsumed_entries(tokens, ns):
            self._release_entry_pages(stale)
        if len(cache) >= cache.max_entries and not self._evict_one_prefix():
            for p in fresh:
                self.page_pool.unref(p)
            return
        if cache.insert(tokens, tuple(fresh), ns=ns) is None:
            for p in fresh:
                self.page_pool.unref(p)
            return
        # unlike _maybe_cache_prefix's co-ownership, the cache is the SOLE
        # owner of these freshly alloc'd pages (refcount 1 from alloc) —
        # no extra ref, balancing _release_entry_pages' single unref
        self._prefix_pinned_pages += pages_needed
        self.metrics.record_kv_restore(kvp.nbytes)
        self.metrics.record_prefix_insert(len(tokens))
        self._fr_emit(request, "kv_restored", source="offload",
                      kind="prefix", tokens=len(tokens),
                      pages=pages_needed, bytes=kvp.nbytes,
                      seconds=round(time.monotonic() - t0, 6))

    def kv_transfer_info(self) -> dict:
        """KV movement block for /api/health and /api/system: the shipping
        knob, transfer/fallback counters, and the host-RAM offload tier's
        occupancy (docs/kv-cache.md)."""
        m = self.metrics
        return {
            "ship_enabled": self.kv_ship,
            "ship_total": m.kv_ship_total,
            "ship_bytes_total": m.kv_ship_bytes_total,
            "restored_total": m.kv_restored_total,
            "restored_bytes_total": m.kv_restored_bytes_total,
            "ship_fallback_total": dict(m.kv_ship_fallback_total),
            "pending_exports": len(self._kv_exports),
            "offload": (self.kv_offload.info()
                        if self.kv_offload is not None
                        else {"enabled": False}),
        }

    def _try_insert(self) -> bool:
        if self.draining:
            # graceful drain: nothing new is admitted or re-activated —
            # parked work stays queued for the gateway's resume to collect
            return False
        self._prefill_spent_iter = 0  # first call of every loop iteration
        self._drain_pending()
        queued = (sum(len(q) for q in self._class_queues.values())
                  + (1 if self._held_request is not None else 0))
        free = self._free_slots()
        if not free and queued > 0 and self.split is None:
            # Slot-pressure preemption: a queued request of a MORE important
            # class than some decoding slot parks the least important victim
            # (docs/scheduling.md). Same-class work always waits its turn.
            # Split mode skips this: parking a decode-pool victim cannot free
            # a PREFILL slot — its preemption point is handoff adoption
            # (disagg/split.py acquire_decode_slot) instead.
            head = self._head_priority()
            if head is not None:
                cands = self._preempt_candidates(head)
                if cands:
                    self._park_slot(cands[0])
                    free = self._free_slots()
        if not free:
            return False
        max_oneshot = self.prefill_buckets[-1] if self.prefill_buckets else 0
        # Chunked-prefill decode budget: while decoders are active, at most
        # `budget` prompt tokens prefill this iteration — larger prompts run
        # through the chunked path and one-shot batches stop accumulating at
        # the budget, so decode steps interleave (bounded ITL).
        budget = self._prefill_budget_now()
        long_cutoff = max_oneshot
        if budget:
            long_cutoff = min(max_oneshot, self._budget_chunk_len(budget))
        handled = False
        inserted = 0  # long inserts count toward the group cap too
        batch: list[tuple[int, Request, int]] = []  # (slot_id, request, n)
        batch_tokens = 0
        while free and len(batch) + inserted < self.MAX_PREFILL_GROUP:
            request = self._pop_request()
            if request is None:
                break
            if self._is_cancelled(request):
                if self.kv_offload is not None:
                    # a cancelled request's parked spill is dead bytes
                    self.kv_offload.drop_parked(request.request_id)
                request.events.put(("done", "cancelled"))
                self.metrics.record_request_done("cancelled")
                self._fr_emit(request, "finished", reason="cancelled")
                self._release_lora(request)
                self._cancelled_effective.discard(request.request_id)
                handled = True
                continue
            if self._shed_expired(request):
                handled = True
                continue
            # Resumed (preempted) requests prefill their COMMITTED sequence
            # (prompt + emitted tokens) — see _effective_prompt.
            prompt = self._effective_prompt(request)
            n = len(prompt)
            # Cap generation so the slot cache can hold prompt + output.
            room = self.slot_capacity - n - self.block  # as in submit
            # a block family prefills the prompt's WHOLE blocks; the
            # remainder opens its first block (_activate_block_group)
            n -= n % self.block
            if room <= 0:
                if request.parked is not None:
                    # a request parked at the capacity edge has no room left
                    # to decode: finish it cleanly rather than erroring a
                    # stream the client is already consuming
                    request.finished_at = stepstats._now()
                    request.events.put(("done", "length"))
                    self.metrics.record_request_done("length")
                    self._fr_emit(request, "finished", reason="length",
                                  cause="capacity_edge_resume")
                    self._release_lora(request)
                    handled = True
                    continue
                request.events.put(
                    ("error", "prompt does not fit slot capacity")
                )
                self.metrics.record_request_done("error")
                self._fr_emit(request, "errored",
                              message="prompt does not fit slot capacity")
                self._release_lora(request)
                handled = True
                continue
            try:
                self._prepare_constraint(request)
            except Exception as e:
                request.events.put(("error", f"constraint rejected: {e}"))
                self.metrics.record_request_done("error")
                self._fr_emit(request, "errored",
                              message=f"constraint rejected: {e}")
                self._release_lora(request)
                handled = True
                continue
            # Page-transfer re-activation (docs/kv-cache.md): a parked
            # request whose KV travelled as bytes — a /v1/resume wire
            # payload, or a spill into the host-RAM offload tier — lands
            # its pages and re-enters decode directly. No prefill dispatch
            # runs and no decode-budget tokens are charged: nothing
            # prefills. Any refusal falls through to the ordinary
            # chunk-prefill replay below.
            if request.parked is not None:
                if request.kv_restore is None and self.kv_offload is not None:
                    request.kv_restore = self.kv_offload.pop_parked(
                        request.request_id
                    )
                if request.kv_restore is not None:
                    slot_id = free.pop(0)
                    if self._insert_restored(slot_id, request, prompt, n):
                        handled = True
                        inserted += 1
                        continue
                    free.insert(0, slot_id)
            if (budget and batch_tokens + min(n, long_cutoff) > budget
                    and (batch or inserted)):
                # the decode budget for this iteration is spent: the request
                # keeps its place at the front of its class for the next one
                self._class_queues[self._priority_of(request)].appendleft(
                    request
                )
                break
            # Prompts that cannot possibly match (too short for min_prefix_len
            # after reserving one suffix token) bypass the cache silently —
            # counting them as misses would page the hit-rate-collapse alert
            # on workloads with nothing cacheable in them. Resumed requests
            # bypass it too: their committed tokens are not a shareable
            # prompt, and their own prompt head may already be donated.
            if (self.prefix_cache is not None and request.parked is None
                    and n - 1 >= self.min_prefix_len):
                # Host-RAM tier promotion first: a spilled cold prefix lands
                # back into fresh pages and re-enters the live cache, so the
                # ordinary zero-copy match below serves it (docs/kv-cache.md)
                self._maybe_restore_prefix(request, n)
                # Longest cached prefix, capped at n-1 (at least one suffix
                # token must prefill to produce the first sampled logits).
                # Namespaced by adapter id (docs/lora.md): under LoRA the
                # prompt KV depends on the adapter's wq/wk/wv deltas, so an
                # adapter-blind hit would be silent corruption.
                hit = self.prefix_cache.match(request.prompt_ids,
                                              max_len=n - 1,
                                              ns=request.sampling.lora)
                if hit is not None and not self._prefer_cp_over(hit[1], n):
                    entry, use_len = hit
                    # zero-copy hit: the shared head rides the donor's
                    # pages; only the suffix needs fresh ones. The donor
                    # must be pinned ACROSS the reservation — its LRU
                    # eviction inside _try_reserve_pages would free the
                    # very pages we are about to share (and could hand
                    # them back as the "fresh" suffix pages).
                    self.prefix_cache.acquire(entry)
                    shared = use_len // self.kv_page_size
                    fresh = self._try_reserve_pages(
                        self._pages_for_tokens(n) - shared
                    )
                    self.prefix_cache.release(entry)
                    if fresh is None:
                        self._hold_on_pool(request)
                        break
                    # no eviction point between the release above and
                    # _insert_cached's re-acquire (same thread, no pool
                    # ops in between), so the donor cannot vanish here
                    self._insert_cached(free.pop(0), request, entry, use_len,
                                        fresh)
                    handled = True
                    inserted += 1
                    continue
                self.metrics.record_prefix_miss()
            need = self._pages_for_tokens(n)
            pages = self._try_reserve_pages(need)
            # Page-pressure preemption: a more important request may
            # park less important decoders (their pages free) until the
            # reservation covers — a refcount walk, no KV bytes move.
            while pages is None and self._preempt_for_pages(
                    self._priority_of(request)):
                pages = self._try_reserve_pages(need)
            if pages is None:
                self._hold_on_pool(request)
                break
            slot_id = free.pop(0)
            self._assign_slot_pages(slot_id, (), pages)
            if n > long_cutoff:
                heavy = self._insert_long(slot_id, request, n)
                handled = True
                inserted += 1
                if heavy:
                    # a context-parallel prefill is a full synchronous pass;
                    # get back to decode before taking another
                    break
                continue
            # Claim the slot BEFORE any dispatch: a failed prefill then
            # reaches these requests through _fail_all instead of leaving
            # their event queues silent forever.
            self.slots[slot_id].request = request
            self.slots[slot_id].generated = 0
            self._attach_constraint(slot_id, request)
            batch.append((slot_id, request, n))
            batch_tokens += n

        if not batch:
            # admission work with no prefill dispatch of its own (cached
            # inserts, long-prompt claims): the loop's clock holds it under
            # `admit`, and the next step record takes it as its plan phase
            return handled

        self._prefill_spent_iter = batch_tokens
        # one prefill dispatch per length bucket present in the batch
        by_bucket: dict[int, list[tuple[int, Request, int]]] = {}
        for entry in batch:
            by_bucket.setdefault(self._bucket_for(entry[2]), []).append(entry)
        for bucket, group in by_bucket.items():
            self._prefill_group(bucket, group)
        return True

    def _insert_long(self, slot_id: int, request: Request, n: int) -> bool:
        """Claim a slot for a prompt beyond the largest one-shot bucket.
        Returns True when it ran a heavy synchronous prefill (CP path)."""
        slot = self.slots[slot_id]
        cp_capable = self._use_cp_prefill
        lora_request = self.lora is not None and request.sampling.lora
        if cp_capable and not lora_request:
            # Ring-attention prefill: one distributed pass over the mesh
            # sp axis fills the whole prompt's KV (per-chip sequence cost
            # ~n/sp), then scatters into the slot row.
            self._cp_prefill_into_slot(slot_id, request, n)
            return True
        if cp_capable and lora_request:
            # LoRA requests take the chunked path even on an sp>1 mesh: the
            # ring-attention prefill closure carries no adapter indices (a
            # sharded bgmv inside shard_map is future work — docs/lora.md).
            # Counted + warned once: this prompt pays single-chip prefill
            # latency, which an operator sizing the mesh should see.
            self.metrics.record_lora_cp_fallback()
            if not self._lora_cp_warned:
                self._lora_cp_warned = True
                log.warning(
                    "LoRA request %s (adapter %r, %d prompt tokens) fell "
                    "back from context-parallel to chunked prefill: the CP "
                    "pass carries no adapter indices. Further fallbacks "
                    "count on llmlb_engine_lora_cp_fallback_total.",
                    request.request_id, request.sampling.lora, n,
                )
        # Single-chip long prompt: chunked prefill. Claim the slot, park
        # its device seq_len at capacity-1 (batched decode's garbage
        # writes for this row land in the unused last cell), and let
        # _advance_prefill feed chunks between decode steps.
        slot.request = request
        slot.generated = 0
        self._attach_constraint(slot_id, request)
        slot.prefilling = True
        slot.prefill_pos = 0
        self._seq_lens[slot_id] = 0
        self._d_seq_lens = self._d_seq_lens.at[slot_id].set(
            self.slot_capacity - 1
        )
        return False

    # ----------------------------------------------------------- prefix cache

    def _prefer_cp_over(self, use_len: int, n: int) -> bool:
        """On a context-parallel mesh (sp > 1), a long prompt prefills in ONE
        distributed ring-attention pass (~n/sp per chip), while a cache hit
        routes the suffix through sequential single-chip chunks. A small hit
        on a huge prompt would make the request slower than a clean miss —
        only take the hit when the cache covers at least half the prompt."""
        return (
            self._use_cp_prefill
            and n > (self.prefill_buckets[-1] if self.prefill_buckets else 0)
            and use_len < n // 2
        )

    def _insert_cached(self, slot_id: int, request: Request,
                       entry: PrefixEntry, use_len: int,
                       fresh_pages: list[int]) -> None:
        """Prefix-cache hit insert, then _advance_prefill chunk-prefills only
        the uncached suffix (prefill_pos starts at use_len).

        The hit is ZERO-COPY: the donor's page ids for the matched head go
        straight into this slot's block table with a refcount bump
        (`fresh_pages`, reserved by the caller, cover the suffix) — no device
        dispatch at all.

        The entry stays acquired until activation/cancellation so the donor
        cannot be evicted mid-flight (the hit holds its own page references
        too; the acquire keeps the entry's LRU and eviction accounting
        honest)."""
        # Claim the slot BEFORE any dispatch (same invariant as the batch
        # path): a failed dispatch then reaches this request through
        # _fail_all — which also releases cache_entry — instead of leaving
        # its event queue silent forever.
        slot = self.slots[slot_id]
        slot.request = request
        slot.generated = 0
        self._attach_constraint(slot_id, request)
        slot.prefilling = True
        slot.prefill_pos = use_len
        slot.cache_entry = entry
        self.prefix_cache.acquire(entry)
        self._seq_lens[slot_id] = 0
        # park device seq_len like any prefilling slot: batched decode's
        # garbage writes land in the unused last cell
        self._d_seq_lens = self._d_seq_lens.at[slot_id].set(
            self.slot_capacity - 1
        )
        shared = entry.pages[: use_len // self.kv_page_size]
        self._assign_slot_pages(slot_id, shared, fresh_pages)
        self.metrics.record_prefix_hit(use_len)
        request.cached_tokens = use_len
        # the uncached suffix prefills via _advance_prefill (its own
        # prefill_chunk events); this event records the reused head
        self._fr_emit(request, "prefill_chunk", tokens=0,
                      cached_tokens=use_len)

    # ------------------------------------------------------------ constraints

    def _prepare_constraint(self, request: Request) -> None:
        """Ensure a constrained request carries its compiled token-DFA before
        a slot is claimed. The service layer pre-compiles off the step loop;
        this is the fallback for multihost followers (which only receive the
        JSON spec over the plan wire) and direct core submitters. Raises for
        uncompilable specs — the caller turns that into a terminal event.

        Known cost: on a follower a COLD schema compiles here, on the step
        loop, stalling decode for the compile (large vocabularies: seconds).
        The leader stalls identically at its own service-level compile and
        the LRU makes it once-per-schema, so lockstep stays aligned — but a
        multihost fleet serving many distinct cold schemas pays it per
        schema (docs/structured-outputs.md)."""
        if (request.compiled_constraint is None
                and request.sampling.constraint is not None):
            if self.constraint_compiler is None:
                raise ValueError(
                    "request carries a constraint but the engine has no "
                    "constraint compiler"
                )
            request.compiled_constraint = self.constraint_compiler.compile_spec(
                request.sampling.constraint
            )

    def _attach_constraint(self, slot_id: int, request: Request) -> None:
        """Install the per-request FSM cursor and its initial mask stripe at
        slot-claim time (every insert path funnels through here) — plus the
        speculative drafter, which needs exactly the same claim-time hook."""
        self._attach_spec(slot_id, request)
        if request.compiled_constraint is None:
            return
        parked = request.parked
        if parked is not None and parked.constraint is not None:
            # Preemption resume: the FSM cursor parked WITH the request —
            # re-walking a fresh ConstraintState from the start state would
            # mask the continuation as if at the beginning of the string.
            state = parked.constraint
        else:
            state = ConstraintState(request.compiled_constraint)
            self.metrics.record_structured_request()
        slot = self.slots[slot_id]
        slot.constraint = state
        slot.gram_offset = -1
        self._constrained_count += 1
        if self._grammar_tables is not None:
            # Fused decode: make the schema device-resident so this slot's
            # masks and cursor advances run in-program. Registration is
            # per-schema (idempotent); failure = table budget exceeded —
            # the slot then keeps the legacy host-mask path, and every
            # already-registered slot regrows a host row so a mixed batch's
            # legacy fallback masks ALL constrained rows.
            off = self._grammar_tables.register(request.compiled_constraint)
            if off is not None:
                slot.gram_offset = off
            elif not self._grammar_fallback:
                self._grammar_fallback = True
                if not self._grammar_warned:
                    self._grammar_warned = True
                    log.warning(
                        "grammar table budget exceeded "
                        "(LLMLB_GRAMMAR_TABLE_MB=%d MiB); constrained "
                        "decoding falls back to the host-mask path",
                        self._grammar_tables.budget_bytes >> 20,
                    )
                for j, s in enumerate(self.slots):
                    if s.constraint is not None and j != slot_id:
                        self._set_mask_row(j, s.constraint, force=True)
        self._set_mask_row(slot_id, state)

    def _set_mask_row(self, slot_id: int, state: ConstraintState, *,
                      force: bool = False) -> None:
        if (not force and self.fused_decode and not self._grammar_fallback
                and self.slots[slot_id].gram_offset >= 0):
            # device-resident schema: the fused program derives this row
            # from the grammar table in-program — no host mirror to keep
            return
        if self._mask_bias is None:
            self._mask_bias = np.zeros(
                (self.num_slots, self.cfg.vocab_size), np.float32
            )
        self._mask_bias[slot_id] = state.bias_row()
        self._mask_dirty_rows.add(slot_id)

    def _clear_constraint(self, slot_id: int) -> None:
        slot = self.slots[slot_id]
        slot.gram_offset = -1
        if slot.constraint is None:
            return
        slot.constraint = None
        self._constrained_count -= 1
        if self._mask_bias is not None:
            self._mask_bias[slot_id] = 0.0
            self._mask_dirty_rows.add(slot_id)

    def _sync_mask(self) -> jnp.ndarray:
        """Device mirror of the mask, refreshed per DIRTY ROW (same
        small-H2D contract as the paged block tables — an FSM advance
        touches one row, so only that row ships)."""
        if self._d_mask is None:
            self._d_mask = jnp.asarray(self._mask_bias)
            self._mask_dirty_rows.clear()
        elif self._mask_dirty_rows:
            rows = sorted(self._mask_dirty_rows)
            self._d_mask = self._d_mask.at[jnp.asarray(rows, jnp.int32)].set(
                jnp.asarray(self._mask_bias[rows])
            )
            self._mask_dirty_rows.clear()
        return self._d_mask

    # ---------------------------------------------------- speculative decode

    def _attach_spec(self, slot_id: int, request: Request) -> None:
        """Install the per-request prompt-lookup drafter at slot-claim time.
        Per-request `speculative` knobs override the engine default; the
        draft budget clamps into the engine verify width so the chunk shape
        (and therefore the jit cache) never varies per request."""
        slot = self.slots[slot_id]
        slot.drafter = None
        slot.spec_k = 0
        if not self._spec_available:
            return
        parked = request.parked
        if parked is not None and parked.drafter is not None:
            # resume the parked index: it already holds prompt + emitted
            # tokens, exactly what a rebuild over the committed sequence
            # would produce
            slot.drafter = parked.drafter
            slot.spec_k = parked.spec_k
            return
        if not self._speculates(request):
            return
        knobs = request.sampling.speculative
        knobs = knobs if isinstance(knobs, dict) else {}
        try:
            k = int(knobs.get("max_draft_tokens")
                    or self.spec.max_draft_tokens)
        except (TypeError, ValueError):
            k = self.spec.max_draft_tokens
        slot.spec_k = max(1, min(k, self.spec.max_draft_tokens))
        slot.drafter = PromptLookupDrafter(
            request.prompt_ids,
            max_ngram=self.spec.max_ngram, min_ngram=self.spec.min_ngram,
        )

    def _speculates(self, request: Request) -> bool:
        """Whether a fresh request gets a drafter at its slot claim: the
        family verifies drafts, and the request's `speculative` knobs, else
        the engine's default, say so."""
        if not self._spec_available:
            return False
        knobs = request.sampling.speculative
        knobs = knobs if isinstance(knobs, dict) else {}
        return bool(knobs.get("enabled", self.spec.enabled))

    def _fused_step_ok(self, active: list[int]) -> bool:
        """True when this step can run as ONE fused device program: fused
        mode on and every active constrained slot's schema device-resident
        (a slot whose schema missed the grammar-table budget drags the
        whole step back to the legacy multi-dispatch path — correctness
        over dispatch count)."""
        if not self.fused_decode:
            return False
        return all(
            self.slots[i].constraint is None
            or self.slots[i].gram_offset >= 0
            for i in active
        )

    def _collect_drafts(
        self, active: list[int], fused: bool = False
    ) -> tuple[dict[int, list[int]], dict[int, list[int]]]:
        """Per-slot draft proposals for this step (empty for slots that are
        not speculating, have no n-gram match, or no room to speculate), plus
        each constrained slot's FSM-state path along its kept drafts — the
        lookahead that builds the per-position verify masks. With `fused`
        the host pre-walk is skipped entirely: the fused verify program
        derives every mask column from the device grammar table, and a
        disallowed draft simply fails its (masked) acceptance comparison at
        the same position the truncation would have cut."""
        drafts: dict[int, list[int]] = {}
        lookahead: dict[int, list[int]] = {}
        for i in active:
            slot = self.slots[i]
            d: list[int] = []
            # first_pending slots' last token is still device-only, so the
            # drafter has not seen it — their proposal would continue the
            # wrong suffix; they join the verify batch with a plain 1-token
            # chunk and speculate from the next step.
            if slot.drafter is not None and not slot.first_pending:
                request = slot.request
                room = self.slot_capacity - 2 - int(self._seq_lens[i])
                budget = request.sampling.max_tokens - slot.generated - 1
                k = min(slot.spec_k, room, budget)
                if k > 0:
                    d = slot.drafter.propose(k)
                if d and slot.constraint is not None and not fused:
                    d, states = self._constrained_draft_prefix(
                        slot.constraint, d
                    )
                    lookahead[i] = states
            drafts[i] = d
        return drafts, lookahead

    @staticmethod
    def _constrained_draft_prefix(
        state: ConstraintState, drafts: list[int]
    ) -> tuple[list[int], list[int]]:
        """Truncate a draft proposal at the first token the grammar FSM
        disallows, walking a lookahead copy of the cursor (the live cursor
        only advances on EMITTED tokens, in _emit). Returns (kept drafts,
        FSM states after each kept draft, starting with the current state).
        EOS never drafts: acceptance-to-stop is the model's call."""
        tc = state.tc
        s = state.state
        kept: list[int] = []
        states = [s]
        if state.violated:
            return kept, states
        for t in drafts:
            if (t == tc.eos_id or not 0 <= t < tc.allowed.shape[1]
                    or not tc.allowed[s, t]):
                break
            nxt = tc.advance(s, t)
            if nxt is None:
                break
            kept.append(t)
            s = nxt
            states.append(s)
        return kept, states

    def _trim_slot_pages(self, slot_id: int, keep_tokens: int) -> None:
        """Rejected-draft rollback: release the trailing pages a verify
        dispatch allocated beyond what the accepted length needs (kept:
        enough to cover keep_tokens). Trailing pages are always this slot's
        own fresh allocations — shared prefix pages sit at the front of the
        row and committed length never rolls back below the prompt — so one
        unref per page is exactly right and the pool's double-free guard
        stays armed."""
        keep = self._pages_for_tokens(keep_tokens)
        row = self._slot_pages[slot_id]
        if len(row) <= keep:
            return
        for p in row[keep:]:
            self.page_pool.unref(p)
        del row[keep:]
        self._block_tables[slot_id, keep:] = 0
        self._tables_dirty = True

    def _verify_active(self, active: list[int], drafts: dict[int, list[int]],
                       lookahead: dict[int, list[int]],
                       step: StepSpan, fused: bool = False) -> bool:
        """One speculative verify step: dispatch every active slot's last
        token + drafts as a K+1-token chunk through the extend path, sample
        every position, accept the longest prefix of drafts matching the
        model's own samples, emit accepted + 1 tokens per slot, roll back
        rejected-suffix state (committed length + over-allocated pages).
        With `fused` the whole step is ONE device program (mask columns,
        last-token splice, accept counts, and the lens/last advance all
        in-program); the host emit loop is unchanged either way. `step` is
        the open step _decode_active drafted in."""
        k1 = self.spec.max_draft_tokens + 1
        t_sync = step.mark("host_sync")
        per_row = {i: len(drafts.get(i, ())) + 1 for i in active}
        active = self._ensure_decode_pages(active, 1, per_row)
        if not active:
            self._clock().abandon()
            self.metrics.set_batch_occupancy(0)
            return True
        self._sync_block_tables()

        # Chunk arrays: active rows carry [last, d1..dm]; every other row
        # (prefilling/parked/free) degenerates to a 1-token chunk writing
        # garbage at its clamped last cell / trash page — exactly decode's
        # garbage contract for non-active rows.
        b = self.num_slots
        ids = np.zeros((b, k1), np.int32)
        chunk_lens = np.ones((b,), np.int32)
        start_pos = np.full((b,), self.slot_capacity - 1, np.int32)
        for i in active:
            d = drafts.get(i, ())
            ids[i, 1:1 + len(d)] = d
            chunk_lens[i] = 1 + len(d)
            start_pos[i] = self._seq_lens[i]

        masked = [i for i in active if self.slots[i].constraint is not None]
        mask = None
        dispatches = 0
        if fused:
            # Fused step: no host mask stripes, no persistent spec-mask
            # buffer — the program derives every mask column from the
            # device grammar table. Host ships only the per-row grammar
            # cursors (offset + FSM state) and the active-row mask.
            grammar = bool(masked)
            gs = np.zeros((b,), np.int32)
            act = np.zeros((b,), bool)
            for i in active:
                act[i] = True
                state = self.slots[i].constraint
                if state is not None:
                    gs[i] = self.slots[i].gram_offset + state.state
            if grammar:
                self.metrics.record_masked_decode_step()
        else:
            # Per-position grammar masks: column 0 is the live cursor's
            # row, later columns the FSM lookahead along the
            # (pre-validated) drafts. Only rows masked this step or last
            # (stale rows zero out) are built host-side and scattered into
            # the persistent device buffer.
            if masked or self._spec_masked_prev:
                rows_upd = sorted(set(masked) | self._spec_masked_prev)
                v = self.cfg.vocab_size
                if self._d_spec_mask is None:
                    self._d_spec_mask = jnp.zeros((b, k1, v), jnp.float32)
                stripes = np.zeros((len(rows_upd), k1, v), np.float32)
                for n, i in enumerate(rows_upd):
                    state = self.slots[i].constraint
                    if state is None:
                        continue  # left the masked set: zero stripe clears
                    stripes[n, 0] = state.bias_row()
                    states = lookahead.get(i, [state.state])
                    for j, s in enumerate(states[1:], start=1):
                        # tc.bias_row handles dead-end states with the same
                        # EOS-only fallback as the live cursor
                        stripes[n, j] = state.tc.bias_row(s)
                    for j in range(max(1, len(states)), k1):
                        stripes[n, j] = stripes[n, len(states) - 1]
                self._d_spec_mask = self._d_spec_mask.at[
                    jnp.asarray(rows_upd, jnp.int32)
                ].set(jnp.asarray(stripes))
                self._spec_masked_prev = set(masked)
                dispatches += 1  # the stripe scatter
            if masked:
                mask = self._d_spec_mask.reshape(b * k1, -1)
                self.metrics.record_masked_decode_step()

        self._key, sk = jax.random.split(self._key)
        window = self._window_for(active, k1)
        step.mark("dispatch")
        lora_idx = self._d_lora_idx if self.lora is not None else None
        if fused:
            fn = self.programs.verify(window, fused=True, grammar=grammar)
            gram_args = ({"gram_table": self._grammar_tables.device(),
                          "gram_state": jnp.asarray(gs)} if grammar else {})
            # jnp.asarray is an H2D transfer, not a device program; the
            # column-0 last-token splice happens in-program
            (toks_dev, new_last, new_lens,
             self.cache_k, self.cache_v) = fn(
                self.params, jnp.asarray(ids), jnp.asarray(chunk_lens),
                jnp.asarray(start_pos), self._d_block_tables,
                self.cache_k, self.cache_v,
                self._d_temps, self._d_top_ps, self._d_top_ks,
                self._d_seeds, sk, self._d_last_tokens,
                jnp.asarray(act), self._d_seq_lens,
                lora_idx=lora_idx, **gram_args,
            )
            self._d_last_tokens = new_last
            self._d_seq_lens = new_lens
            dispatches = 1
        else:
            # column 0 is the on-device last token per row — newly
            # activated slots' first tokens never round-tripped through
            # the host
            ids_dev = jnp.asarray(ids).at[:, 0].set(self._d_last_tokens)
            fn = self.programs.verify(window, fused=False)
            toks_dev, self.cache_k, self.cache_v = fn(
                self.params, ids_dev, jnp.asarray(chunk_lens),
                jnp.asarray(start_pos), self._d_block_tables,
                self.cache_k, self.cache_v,
                self._d_temps, self._d_top_ps, self._d_top_ks,
                self._d_seeds, mask, sk, lora_idx=lora_idx,
            )
            dispatches += 2  # the ids splice + the verify program
        step.mark("compute")
        jax.block_until_ready(toks_dev)
        step.mark("fetch")
        tokens = self._fetch_tokens(toks_dev)  # [B, K+2]: input col + samples
        step_s = step.mark("emit") - t_sync

        drafted = sum(len(drafts.get(i, ())) for i in active)
        accepted_total = 0
        emitted_total = 0  # every token delivered (all slots; MFU/throughput)
        spec_emitted = 0  # tokens from SPECULATING slots (accepted + 1 each)
        rows: list[int] = []
        new_lens: list[int] = []
        new_lasts: list[int] = []
        # (request_id, drafted, accepted) per speculating slot — the slot's
        # request may finish inside the emit loop, so capture the id up front
        spec_accepts: list[tuple[str, int, int]] = []
        self._begin_delivery(step)
        for i in active:
            slot = self.slots[i]
            request = slot.request
            held: list[int] = []  # this row's tokens of this fetch: ONE event
            if slot.first_pending and request is not None:
                slot.first_pending = False
                self._emit(i, int(tokens[i, 0]), first=True, held=held)
            if slot.request is None or slot.prefilling:
                self._put_held(request, held)
                continue
            rid_i = slot.request.request_id
            d = drafts.get(i, [])
            # expected emission span (matches until first mismatch, +1 for
            # the correction/bonus sample) — the amortized per-token pacing
            # for this slot's ITL before finish conditions can trim it
            span = 1
            for j, dj in enumerate(d):
                if int(tokens[i, 1 + j]) == dj and dj != self.eos_id:
                    span += 1
                else:
                    break
            itl = step_s / span
            j = 0
            emitted_i = 0
            while True:
                tok = int(tokens[i, 1 + j])
                self._seq_lens[i] += 1
                emitted_i += 1
                matched = j < len(d) and tok == d[j]
                self._emit(i, tok, itl=itl, held=held)
                if matched:
                    j += 1
                if slot.request is None or not matched:
                    break
            self._put_held(request, held)
            accepted_total += j
            emitted_total += emitted_i
            if d:
                spec_emitted += emitted_i
                spec_accepts.append((rid_i, len(d), j))
            if slot.request is not None and not slot.prefilling:
                rows.append(i)
                new_lens.append(int(self._seq_lens[i]))
                # the last emitted sample is the next dispatch's input token
                new_lasts.append(int(tokens[i, emitted_i]))
                # rejected-suffix rollback: keep pages covering the
                # committed length + the next token's write, release the rest
                self._trim_slot_pages(i, int(self._seq_lens[i]) + 1)
        if rows and not fused:
            # fused: the program already advanced lens/last in-program for
            # active rows (bit-equal to these host-computed values for
            # every surviving slot; freed slots' device rows are garbage
            # under the same free-slot contract as the legacy skip)
            idx = jnp.asarray(rows, jnp.int32)
            self._d_seq_lens = self._d_seq_lens.at[idx].set(
                jnp.asarray(new_lens, jnp.int32)
            )
            self._d_last_tokens = self._d_last_tokens.at[idx].set(
                jnp.asarray(new_lasts, jnp.int32)
            )
            dispatches += 2  # the two post-emit scatters

        mean_span = emitted_total / max(1, len(active))
        self.metrics.record_decode_step(step_s / max(1.0, mean_span),
                                        len(active))
        self.metrics.record_spec_step(drafted, accepted_total, spec_emitted)
        if self.flightrec.enabled:
            for rid_i, n_drafted, n_accepted in spec_accepts:
                self.flightrec.emit(rid_i, "spec_accept",
                                    drafted=n_drafted, accepted=n_accepted)
        self._record_step(
            "verify", step,
            active_slots=len(active), tokens=emitted_total,
            slots=active, dispatches=dispatches, fused=fused,
        )
        return True

    def spec_info(self) -> dict:
        """Speculative-decoding block for /api/system, /api/health, and
        /metrics consumers: config + live acceptance figures."""
        m = self.metrics
        drafted = m.spec_draft_tokens_total
        return {
            "enabled": self.spec.enabled,
            "available": self._spec_available,
            "max_draft_tokens": self.spec.max_draft_tokens,
            "ngram": [self.spec.min_ngram, self.spec.max_ngram],
            "verify_steps_total": m.spec_verify_steps_total,
            "draft_tokens_total": drafted,
            "accepted_tokens_total": m.spec_accepted_tokens_total,
            "emitted_tokens_total": m.spec_emitted_tokens_total,
            "acceptance_rate": (
                round(m.spec_accepted_tokens_total / drafted, 4)
                if drafted else None
            ),
        }

    def lora_info(self) -> dict:
        """Multi-LoRA block for /api/system, /api/health, and /metrics
        consumers: pool config + live residency/eviction figures
        (docs/lora.md)."""
        if self.lora is None:
            return {"enabled": False}
        info = self.lora.info()
        # CP-mesh prefill fallbacks (docs/lora.md): LoRA prompts that paid
        # single-chip chunked prefill because the ring-attention pass
        # carries no adapter indices — surfaced here so /api/system shows
        # the same figure the counter exports.
        info["cp_fallback_total"] = self.metrics.lora_cp_fallback_total
        return info

    def _release_cache_entry(self, slot: _Slot) -> None:
        if slot.cache_entry is not None:
            if self.prefix_cache is not None:
                self.prefix_cache.release(slot.cache_entry)
            slot.cache_entry = None

    def _release_entry_pages(self, entry: PrefixEntry) -> None:
        """Drop the prefix cache's page references of a removed entry."""
        for p in entry.pages:
            self.page_pool.unref(p)
        self._prefix_pinned_pages -= len(entry.pages)

    def _evict_one_prefix(self) -> bool:
        entry = self.prefix_cache.evict_lru_entry()
        if entry is None:
            return False  # every donor has an in-flight reader
        # page-pressure demotion, not destruction: the cold prefix moves to
        # the host-RAM tier (when enabled) before its pages free
        self._spill_prefix_entry(entry)
        self._release_entry_pages(entry)
        self.metrics.record_prefix_eviction()
        return True

    def _maybe_cache_prefix(self, slot_id: int, request: Request) -> None:
        """On request completion: donate this request's prompt KV when the
        aligned head is long enough and not already covered. Only the PAGES
        covering the head are pinned — the slot itself frees immediately."""
        cache = self.prefix_cache
        n = len(request.prompt_ids)
        length = (n // cache.align) * cache.align
        if length < cache.min_len:
            return
        tokens = tuple(request.prompt_ids[:length])
        # Donations are namespaced by adapter id like matches: two adapters
        # sharing a prompt text donate to DISJOINT trees (docs/lora.md).
        ns = request.sampling.lora
        if cache.covers(tokens, ns):
            cache.touch(tokens, ns)  # a re-served prefix is a use: refresh LRU
            return
        # A longer prefix subsumes its ancestors (any match they could serve
        # routes through this entry's subtree) — reclaim their entries
        # first, or each turn of a growing conversation spends a fresh one.
        # NOT counted as evictions: coverage is preserved, and on healthy
        # multi-turn traffic this fires once per turn — charging it to
        # evictions_total would make the donor-churn signal operators alert
        # on track plain insertion rate.
        for stale in cache.evict_subsumed_entries(tokens, ns):
            self._release_entry_pages(stale)
        if len(cache) >= cache.max_entries and not self._evict_one_prefix():
            return
        pages = tuple(
            self._slot_pages[slot_id][: length // self.kv_page_size]
        )
        if not pages:
            return
        if cache.insert(tokens, pages, ns=ns) is not None:
            for p in pages:  # the cache is now a co-owner of the head
                self.page_pool.ref(p)
            self._prefix_pinned_pages += len(pages)
            self.metrics.record_prefix_insert(length)

    def prefix_cache_info(self) -> dict:
        """One JSON-safe block for /api/health, /api/system, and /metrics."""
        if self.prefix_cache is None:
            return {"enabled": False}
        return {
            "enabled": True,
            "entries": len(self.prefix_cache),
            "budget_slots": self.prefix_cache.max_entries,
            "cached_tokens": self.prefix_cache.cached_tokens(),
            "min_prefix_len": self.min_prefix_len,
            "align": self.prefix_align,
            # zero-copy donors pin pages, never slots; HBM held is per page
            "pinned_pages": self._prefix_pinned_pages,
            "pinned_hbm_bytes": (
                self._prefix_pinned_pages
                * kv_page_bytes(self.cfg, self.kv_page_size,
                                quantized=self.quant.kv)
            ),
        }

    def structured_info(self) -> dict:
        """Structured-output block for /api/system, /api/health, /metrics:
        the constraint compiler's mask-cache figures plus live load."""
        if self.constraint_compiler is None:
            return {"enabled": False}
        info = self.constraint_compiler.info()
        info["active_constrained_slots"] = self._constrained_count
        return info

    def kv_cache_info(self) -> dict:
        """KV memory block for /api/system, /api/health, and /metrics: live
        page-pool utilization. Gauge reads are approximate under concurrent
        step-loop mutation (same stance as every other scrape-time
        figure)."""
        pool = self.page_pool
        active = 0
        active_pages = 0
        waste = 0
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            active += 1
            held = len(self._slot_pages[i])
            active_pages += held
            used = s.prefill_pos if s.prefilling else int(self._seq_lens[i])
            waste += max(0, held * self.kv_page_size - used)
        return {
            "layout": "paged",
            # derived from the ACTUAL pool dtype — implied-bf16 accounting
            # would be 2x wrong under int8 (the gauges below feed capacity
            # planning and the Grafana KV panels)
            "kv_dtype": ("int8" if self.quant.kv
                         else str(jnp.dtype(self.cfg.dtype))),
            "effective_kv_dtype": ("int8" if self.quant.kv
                                   else str(jnp.dtype(self.cfg.dtype))),
            "page_size": self.kv_page_size,
            "num_slots": self.num_slots,
            "slot_capacity": self.slot_capacity,
            "pages_total": pool.total,
            "pages_free": pool.available(),
            "pages_active": active_pages,
            "pages_pinned": self._prefix_pinned_pages,
            "utilization": round(pool.used() / max(1, pool.total), 4),
            # allocated-but-unfilled cells of occupied rows: the internal
            # fragmentation the --kv-page-size knob trades against
            "fragmentation": round(
                waste / max(1, active_pages * self.kv_page_size), 4
            ),
            "waste_tokens_mean": (round(waste / active, 1) if active else 0.0),
            "bytes_per_page": kv_page_bytes(self.cfg, self.kv_page_size,
                                            quantized=self.quant.kv),
            # all layers of one token: the family's own cell
            "bytes_per_token": kv_page_bytes(self.cfg, 1, self.quant.kv),
            "hbm_bytes": kv_pool_bytes(self.cfg, self.kv_num_pages,
                                       self.kv_page_size,
                                       quantized=self.quant.kv),
        }

    def quant_info(self) -> dict:
        """Quantization block for /api/system, /api/health, and /metrics:
        the resolved knobs plus the honest byte footprints they produce."""
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        return {
            "mode": self.quant.mode,
            "weights_int8": self.quant.weights,
            "kv_int8": self.quant.kv,
            # the dtype the KV pool actually stores: the same source of
            # truth as the pool allocation
            "effective_kv_dtype": ("int8" if self.quant.kv
                                   else str(jnp.dtype(self.cfg.dtype))),
            "param_bytes": self.param_bytes,
            "state_bytes": self.state_bytes,
            "param_bytes_bf16": self.n_params * itemsize,
            "kv_cell_bytes": kv_cell_bytes(self.cfg.head_dim_,
                                           self.quant.kv, itemsize),
        }

    def perf_info(self) -> dict:
        """Live roofline block for /api/system and /metrics: model-derived
        static FLOPs/bytes per token divided by measured busy-time
        throughput against the chip's peak specs (engine/telemetry.py
        CHIP_SPECS, keyed off device_kind). `available` is False on chips
        outside the table (CPU included) or before any decode traffic —
        the gauges are then absent, never wrong."""
        from llmlb_tpu.engine.telemetry import (
            chip_spec_for,
            model_bytes_per_token,
            model_flops_per_token,
        )

        devices = jax.local_devices()
        kind = (getattr(devices[0], "device_kind", "unknown")
                if devices else "none")
        n_chips = max(1, len(devices))
        spec = chip_spec_for(kind)
        busy_s, toks = self.step_stats.window_throughput()
        tok_per_s = toks / busy_s if busy_s > 0 else 0.0
        # mean live context + batch across active decode slots; the window
        # figures already average over recent steps, so a point-in-time
        # read of the live state is the matching granularity
        contexts = [
            int(self._seq_lens[i]) for i, s in enumerate(self.slots)
            if s.request is not None and not s.prefilling
        ]
        mean_ctx = (sum(contexts) / len(contexts)) if contexts else 0.0
        batch = max(1, len(contexts))
        flops_tok = model_flops_per_token(self.cfg, self.n_params)
        # quantization-honest byte accounting: the measured param footprint
        # (int8 values + f32 scales when weights quantize) and the actual
        # KV cell size (D·1 + 4-byte scale under int8 KV) — the implied
        # bf16 math would double-count HBM traffic quantization removed
        bytes_tok = model_bytes_per_token(
            self.cfg, self.n_params, mean_ctx, batch=batch,
            weight_bytes=self.param_bytes,
            kv_token_layer_bytes=self._record.kv_token_layer_bytes(
                self.cfg, self.quant.kv),
        )
        info = {
            "device_kind": str(kind),
            "n_chips": n_chips,
            "n_params": self.n_params,
            "quantize": self.quant.mode,
            "flops_per_token": flops_tok,
            "bytes_per_token": round(bytes_tok, 1),
            "mean_context_tokens": round(mean_ctx, 1),
            "window_tokens": toks,
            "window_busy_s": round(busy_s, 4),
            "tokens_per_sec_busy": round(tok_per_s, 2),
            "available": spec is not None and tok_per_s > 0,
        }
        if spec is not None:
            info["chip"] = {
                "generation": spec.generation,
                "peak_flops": spec.peak_flops,
                "peak_flops_int8": spec.int8_flops,
                "peak_hbm_bw": spec.peak_hbm_bw,
            }
        if info["available"]:
            per_chip = tok_per_s / n_chips
            # int8-weight engines are judged against the chip's int8 OPS
            # column — quantized matmuls move int8 operands through the MXU,
            # and dividing by the bf16 peak would overstate MFU ~2x on
            # chips with an int8 fast path
            peak = spec.int8_flops if self.quant.weights else spec.peak_flops
            info["mfu"] = round(flops_tok * per_chip / peak, 6)
            info["hbm_bw_utilization"] = round(
                bytes_tok * per_chip / spec.peak_hbm_bw, 6
            )
        return info

    def _prefill_counters(self, stats: list,
                          step: StepSpan | None = None) -> dict | None:
        """A prefill dispatch's step counters on the host. The dispatch has
        been waited for, and a prefill fetches nothing else: these are its
        reads, one blocking device-to-host copy an ARRAY of the family's
        counters (a burst's ride its one token fetch instead). `step`: the
        prefill's own open step, nothing of the loop in flight — the reads
        stand under a span of their own, `counters` (stepstats.SPANS);
        without it (a prefill recorded inside its successor) they hide
        behind the burst in flight and have no span."""
        if not stats:
            return None
        if step is not None:
            step.mark("counters")
        return {name: (np.asarray(v).tolist() if np.ndim(v)
                       else int(v)) for name, v in stats[0].items()}

    def _prefill_group(self, bucket: int,
                       group: list[tuple[int, Request, int]],
                       after: StepSpan | None = None
                       ) -> "_AheadPrefill | None":
        """Prefill G same-bucket prompts in one dispatch, padded to the next
        power of two by repeating the last row — duplicate scatters write
        identical data to the same slot, so padding rows are free.

        `after` (_admit_ahead): the group leaves AHEAD, behind the closed,
        unrecorded step of a burst that is fetched and not emitted. Nothing
        is waited for: the activation is dispatched behind the prefill, the
        step is returned open, in `activate_inflight`, for the caller to
        hand over to the burst behind both, and its record is closed with
        that burst in flight (_record_ahead_prefill)."""
        g = len(group)
        padded = 1
        while padded < g:
            padded *= 2
        ids = np.zeros((padded, bucket), np.int32)
        lens = np.zeros((padded,), np.int32)
        slot_ids = np.zeros((padded,), np.int32)
        for row, (slot_id, request, n) in enumerate(group):
            # [:n]: a block family's n is the prompt's whole blocks
            ids[row, :n] = self._effective_prompt(request)[:n]
            lens[row] = n
            slot_ids[row] = slot_id
        ids[g:] = ids[g - 1]
        lens[g:] = lens[g - 1]
        slot_ids[g:] = slot_ids[g - 1]
        # Per-row adapter indices (docs/lora.md): a mixed-adapter group
        # prefills in this ONE dispatch — the bgmv delta gathers each row's
        # factors by index, no per-adapter sub-batching. Padding rows repeat
        # the last real row like everything else.
        lora_idx = None
        if self.lora is not None:
            lidx = np.zeros((padded,), np.int32)
            lidx[:g] = self._lora_rows([r for _, r, _ in group])
            lidx[g:] = lidx[g - 1]
            lora_idx = jnp.asarray(lidx)

        ahead = after is not None
        self._note_prefill_dispatch(ahead)
        step = self._clock().begin("dispatch", after=after)
        self._stamp_prefill(step, [r for _, r, _ in group])
        # padding rows repeat the last real slot's table row, so their
        # duplicate scatters rewrite identical cells (same trick as ids)
        (logits, self.cache_k, self.cache_v,
         *stats) = self.programs.prefill(
            self.params, jnp.asarray(ids), jnp.asarray(lens),
            jnp.asarray(self._block_tables[slot_ids]),
            self.cache_k, self.cache_v,
            slot_ids=slot_ids, lora_idx=lora_idx)
        if ahead:
            self._activate_group(group, slot_ids, lens, logits, inflight=True)
            return _AheadPrefill(step, group, logits, stats)
        step.mark("compute")
        # jitted prefill returns futures (async dispatch); block before timing
        # or the histogram records dispatch overhead, not device execution.
        jax.block_until_ready(logits)
        self.metrics.record_prefill_step(step.mark("emit") - step.t0)
        # before activation: split mode stages the group and vacates the
        # prefill slots, after which the requests are unreachable here
        self._fr_prefilled(group)
        self._activate_group(group, slot_ids, lens, logits)
        self._record_step("prefill", step, ahead=False,
                          **self._prefill_counts(group, stats, step))
        return None

    def _fr_prefilled(self, group: list[tuple[int, Request, int]]) -> None:
        """A one-shot group's `prefill_chunk` events, stamped where the
        host knows its prefill done."""
        if self.flightrec.enabled:
            for _slot_id, request, n in group:
                self.flightrec.emit(request.request_id, "prefill_chunk",
                                    tokens=n, cached_tokens=0)

    def _prefill_counts(self, group: list[tuple[int, Request, int]],
                        stats: list, step: StepSpan | None = None) -> dict:
        """What a one-shot group's record counts (_observe_step); `step`
        as _prefill_counters takes it."""
        return {"active_slots": len(group),
                "tokens": sum(n for _, _, n in group),
                "slots": [s for s, _, _ in group],
                "counters": self._prefill_counters(stats, step)}

    def _record_ahead_prefill(self, prefill: _AheadPrefill) -> None:
        """With the burst behind it in flight and the burst in front of it
        emitted: what _prefill_group does between its wait and its return,
        for a group that left ahead. The wait is the first instant the host
        knows the prefill done, so the prefill histogram still reads
        dispatch -> done (not the dispatch alone), and the flight records'
        `prefill_chunk` is stamped where it is in today's order."""
        jax.block_until_ready(prefill.logits)
        now = stepstats._now()
        self.metrics.record_prefill_step(now - prefill.step.t0)
        # the activation was issued behind the dispatch; `prefill` ends
        # where the host knows the prompt filled, as in today's order
        self._stamp_activated([r for _, r, _ in prefill.group], now)
        self._fr_prefilled(prefill.group)
        self._observe_step(
            "prefill", prefill.step,
            **self._prefill_counts(prefill.group, prefill.stats), ahead=True)

    def _activate_group(self, group: list[tuple[int, Request, int]],
                        padded_slot_ids: np.ndarray, padded_lens: np.ndarray,
                        logits, *, inflight: bool = False) -> None:
        """Batched activation: ONE program (`_activate_rows`) samples every
        row's first token from the padded logits and scatters the group's
        sampling state, lengths, first tokens and adapter rows into the
        per-slot device arrays — one dispatch for the whole group, built
        once per padded group size (and once more where a grammar bias is
        present). The host rows go in as the NumPy arrays filled here, so
        the one call carries every transfer. Padding rows repeat the last
        real row, so their scatters rewrite identical values.

        Split mode: a prefill-loop activation never lands in the prefill
        slot — the finished slot is STAGED (prompt KV pinned in its pages,
        final logits row held) and the handoff pump adopts it into a decode
        slot, re-entering here under the "handoff" tag.

        `inflight`: the group's prefill is still computing (it left ahead,
        _prefill_group): the span is `activate_inflight`."""
        if self.split is not None and self._loop_tag() == "prefill":
            self.split.stage_group(group, logits)
            self.split.pump_handoffs()
            return
        if self.block > 1:
            self._activate_block_group(group, padded_slot_ids, padded_lens,
                                       inflight=inflight)
            return
        # inside a step (the prefill paths) this is its `activate` span;
        # a handoff adoption between steps stays in the loop's bucket
        self._clock().mark("activate_inflight" if inflight else "activate")
        g = len(group)
        padded = len(padded_slot_ids)
        temps = np.ones((padded,), np.float32)
        top_ps = np.ones((padded,), np.float32)
        top_ks = np.zeros((padded,), np.int32)
        seeds = np.full((padded,), -1, np.int32)
        for row, (_slot_id, request, _n) in enumerate(group):
            s = request.sampling
            temps[row] = s.temperature
            top_ps[row] = s.top_p
            top_ks[row] = s.top_k
            if s.seed is not None:
                seeds[row] = s.seed & 0x7FFFFFFF
        temps[g:] = temps[g - 1]
        top_ps[g:] = top_ps[g - 1]
        top_ks[g:] = top_ks[g - 1]
        seeds[g:] = seeds[g - 1]

        # Constrained rows mask their first-token sampling too: the bias is
        # each slot's FSM start-state row (padding repeats the last real row,
        # so its duplicate scatter writes the same value).
        constrained = [
            (row, self.slots[slot_id].constraint)
            for row, (slot_id, _r, _n) in enumerate(group)
            if self.slots[slot_id].constraint is not None
        ]
        bias = None
        if constrained:
            bias = np.zeros((padded, logits.shape[-1]), np.float32)
            for row, state in constrained:
                bias[row] = state.bias_row()
            bias[g:] = bias[g - 1]

        lora_rows = None
        if self.lora is not None:
            # adapter rows ride the same activation scatter as the sampling
            # params: the decode hot loop then needs zero per-step H2D
            lora_rows = np.zeros((padded,), np.int32)
            lora_rows[:g] = self._lora_rows([r for _, r, _ in group])
            lora_rows[g:] = lora_rows[g - 1]

        (self._key, firsts,
         (self._d_temps, self._d_top_ps, self._d_top_ks, self._d_seeds,
          self._d_seq_lens, self._d_last_tokens,
          self._d_lora_idx)) = self.programs.activate_rows(
            logits, self._key, temps, top_ps, top_ks, seeds,
            padded_lens, padded_slot_ids, bias, lora_rows,
            (self._d_temps, self._d_top_ps, self._d_top_ks, self._d_seeds,
             self._d_seq_lens, self._d_last_tokens, self._d_lora_idx),
        )

        if constrained:
            # The NEXT decode dispatch needs each constrained slot's mask
            # advanced past its first token, which only exists on device —
            # one synchronous fetch per constrained activation (the
            # constrained-TTFT cost documented in docs/structured-outputs.md;
            # unconstrained slots keep the zero-sync deferred-first path).
            first_host = self._fetch_tokens(firsts)
            for row, (slot_id, _r, _n) in enumerate(group):
                state = self.slots[slot_id].constraint
                if state is None:
                    continue
                if state.advance(int(first_host[row])):
                    self._set_mask_row(slot_id, state)
                else:
                    self.metrics.record_constraint_violation()

        for slot_id, request, n in group:
            self._seq_lens[slot_id] = n
            slot = self.slots[slot_id]
            slot.request = request
            if request.parked is not None:
                # preemption resume: restore the generation cursor — the
                # activation sample above IS the next token of the
                # interrupted stream (its step folded len(committed)-1,
                # exactly the step an uninterrupted decode would have used)
                st = request.parked
                slot.generated = st.generated
                slot.out_tokens = list(st.tokens)
                request.parked = None
                self.metrics.record_resume()
                self._fr_emit(request, "resumed", generated=st.generated)
            else:
                slot.generated = 0
                slot.out_tokens = []
            # last_emit_at 0 ⇒ the first token records no inter-token gap;
            # it is emitted with the next decode fetch (first_pending).
            slot.last_emit_at = 0.0
            slot.first_pending = True
        if not inflight:  # else _record_ahead_prefill, behind the wait
            self._stamp_activated([r for _, r, _ in group], stepstats._now())

    def _stamp_prefill(self, step: StepSpan, requests: "list[Request]",
                       cut: bool = True) -> None:
        """The way in: `step` is a prefill dispatch of `requests`. For one
        that has no first token yet, `place` ends where its FIRST dispatch
        began (the step's own first stamp: no clock is read here), and the
        cut of its `prefill` stage opens there (stepstats.PrefillCut) —
        but with `cut` false: `step` is a burst the prompt RIDES, a stage
        with no prefill step in it."""
        for r in requests:
            if r.first_token_at is None:
                if r.prefill_at is None:
                    r.prefill_at, r.prefill_seq = step.t0, step.seq
                    if cut:
                        r.prefill_cut = stepstats.PrefillCut(
                            self._clock(), step)
                elif r.prefill_cut is not None:
                    r.prefill_cut.next_step(step)
                r.prefill_chunks += 1

    def _stamp_activated(self, requests: "list[Request]",
                         now: float) -> None:
        """The way in: `prefill` ends at `now`, ONE clock read for the
        group, where the host knows the prompts filled and has issued the
        activation; `first_fetch` runs from it, and the stage's cut is
        closed (LoopClock.close_cut)."""
        for r in requests:
            if r.activated_at is None:
                r.activated_at = now
                if r.prefill_cut is not None:
                    self._clock().close_cut(r.prefill_cut, now)

    # the per-slot device arrays an activation of a block family writes, in
    # the order of _activate_block_group's rows
    _BLOCK_ROW_STATE = ("_d_temps", "_d_top_ps", "_d_top_ks", "_d_seeds",
                        "_d_seq_lens", "_d_blk", "_d_masked", "_d_left",
                        "_d_skip", "_d_per_pass", "_d_dynamic",
                        "_d_threshold")

    def _activate_block_group(self, group: list[tuple[int, Request, int]],
                              padded_slot_ids: np.ndarray,
                              padded_lens: np.ndarray, *,
                              inflight: bool = False) -> None:
        """_activate_group for a block family: nothing is sampled — the
        prefill's logits are of the last prompt block's own tokens. Each
        row's committed length is the `n` whole-block tokens just prefilled;
        the prompt's remainder opens its first block as given tokens, the
        rest of the block masked; `left` is the positions the row may yet
        commit (given ones included), from which the program stops it.
        Nothing is fetched and no key is split, so a group whose prefill
        left ahead (`inflight`, as in _activate_group) is activated as any
        other."""
        self._clock().mark("activate_inflight" if inflight else "activate")
        g, padded, b = len(group), len(padded_slot_ids), self.block
        cfg = self.cfg
        temps = np.ones((padded,), np.float32)
        top_ps = np.ones((padded,), np.float32)
        top_ks = np.zeros((padded,), np.int32)
        seeds = np.full((padded,), -1, np.int32)
        blk = np.full((padded, b), cfg.mask_token_id, np.int32)
        masked = np.ones((padded, b), np.bool_)
        left = np.zeros((padded,), np.int32)
        skip = np.zeros((padded,), np.int32)
        per_pass = np.ones((padded,), np.int32)
        dynamic = np.zeros((padded,), np.bool_)
        threshold = np.ones((padded,), np.float32)
        for row, (slot_id, request, n) in enumerate(group):
            s = request.sampling
            temps[row], top_ps[row], top_ks[row] = (s.temperature, s.top_p,
                                                    s.top_k)
            if s.seed is not None:
                seeds[row] = s.seed & 0x7FFFFFFF
            rest = self._effective_prompt(request)[n:]
            r = len(rest)
            blk[row, :r] = rest
            masked[row, :r] = False
            skip[row] = r
            done = request.parked.generated if request.parked else 0
            limit = min(s.max_tokens,
                        done + self.slot_capacity - b - n - r)
            left[row] = r + limit - done
            steps = (cfg.denoising_steps if s.denoising_steps is None
                     else s.denoising_steps)
            per_pass[row] = b // steps
            dynamic[row] = ((s.remasking_strategy or cfg.remasking_strategy)
                            == "low_confidence_dynamic")
            threshold[row] = (cfg.confidence_threshold
                              if s.confidence_threshold is None
                              else s.confidence_threshold)
            slot = self.slots[slot_id]
            slot.given, slot.token_limit = r, limit
        rows = (temps, top_ps, top_ks, seeds, padded_lens, blk, masked, left,
                skip, per_pass, dynamic, threshold)
        for arr in rows:
            arr[g:] = arr[g - 1]
        state = self.programs.activate_block_rows(
            padded_slot_ids, rows,
            tuple(getattr(self, name) for name in self._BLOCK_ROW_STATE))
        for name, arr in zip(self._BLOCK_ROW_STATE, state):
            setattr(self, name, arr)
        for slot_id, request, n in group:
            self._seq_lens[slot_id] = n
            slot = self.slots[slot_id]
            slot.request = request
            slot.generated, slot.out_tokens = 0, []
            if request.parked is not None:
                # preemption resume: the committed tokens were prefilled as
                # prompt; the open block starts over from masks, and the
                # seeded keys of its passes are the ones it drew before
                st = request.parked
                slot.generated, slot.out_tokens = st.generated, list(st.tokens)
                request.parked = None
                self.metrics.record_resume()
                self._fr_emit(request, "resumed", generated=st.generated)
            slot.last_emit_at = 0.0
            slot.first_pending = False
        if not inflight:  # else _record_ahead_prefill, behind the wait
            self._stamp_activated([r for _, r, _ in group], stepstats._now())

    def _cp_bucket_for(self, n: int) -> int:
        """Padded length for the context-parallel prefill jit cache: next
        power of two (≥ the largest one-shot bucket), capped at capacity."""
        b = max(self.prefill_buckets[-1], 1)
        while b < n:
            b *= 2
        return min(b, self.slot_capacity)

    def _cp_prefill_into_slot(self, slot_id: int, request: Request,
                              n: int) -> None:
        """One-shot ring-attention prefill of a long prompt, scattered into
        the slot's pages (engine wiring for
        make_context_parallel_prefill)."""
        padded = self._cp_bucket_for(n)
        ids = np.zeros((1, padded), np.int32)
        ids[0, :n] = self._effective_prompt(request)
        self._note_prefill_dispatch()
        step = self._clock().begin("dispatch")
        self._stamp_prefill(step, [request])
        logits, k_all, v_all = self.programs.context_parallel_prefill(
            self.params, jnp.asarray(ids), jnp.asarray([n], np.int32)
        )
        step.mark("compute")
        jax.block_until_ready(logits)  # async dispatch; time real execution
        # the legacy phases of this path end here (dispatch and compute);
        # the record stays open so the KV scatter and the activation are
        # spans of it and not a hole between records
        step.freeze_phases()
        self.metrics.record_prefill_step(step.mark("emit") - step.t0)
        self._fr_emit(request, "prefill_chunk", tokens=n, cached_tokens=0)
        # KV beyond n is padding garbage; it lands in cells past the valid
        # length (masked by decode attention and overwritten as the sequence
        # grows into them) — same contract as the chunked path.
        self.cache_k, self.cache_v = _scatter_kv_row_paged(
            self.cache_k, self.cache_v, k_all, v_all,
            jnp.asarray(self._block_tables[slot_id]),
        )
        slot = self.slots[slot_id]
        slot.request = request
        slot.generated = 0
        self._attach_constraint(slot_id, request)
        self._activate_slot(slot_id, request, n, logits)
        self._record_step("prefill", step, active_slots=1, tokens=n)

    def _advance_prefill(self) -> bool:
        """Feed ONE chunk of ONE prefilling slot's prompt into the KV cache.
        Rotates among prefilling slots so a second long prompt shares prefill
        bandwidth instead of waiting head-of-line behind the first."""
        prefilling = [i for i, s in enumerate(self.slots)
                      if s.prefilling and not s.handoff_ready]
        if not prefilling:
            return False
        slot_id = prefilling[self._prefill_rr % len(prefilling)]
        self._prefill_rr += 1
        slot = self.slots[slot_id]
        request = slot.request
        assert request is not None
        if self._is_cancelled(request):
            self._finish_slot(slot_id, "cancelled")
            return True

        prompt = self._effective_prompt(request)
        n = len(prompt) - len(prompt) % self.block
        start = slot.prefill_pos
        chunk_max = self.prefill_buckets[-1]
        prefill_budget = self._prefill_budget_now()
        if prefill_budget:
            # decode-token budget (docs/scheduling.md): while decoders are
            # active, cap each chunk so decode steps interleave — a 128k
            # prompt then costs the decoders one budget-sized prefill per
            # iteration, never a whole drain iteration. The budget is shared
            # with _try_insert's one-shot batch from the same iteration.
            remaining = prefill_budget - self._prefill_spent_iter
            if remaining <= 0:
                return False
            chunk_max = min(chunk_max, self._budget_chunk_len(remaining))
        chunk_len = min(chunk_max, n - start)
        bucket = self._bucket_for(chunk_len)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :chunk_len] = prompt[start:start + chunk_len]
        lora_idx = (jnp.asarray(self._lora_rows([request]))
                    if self.lora is not None else None)

        self._note_prefill_dispatch()
        step = self._clock().begin("dispatch")
        # which chunk of which prompt this record is: with `request_ids`
        # and `seq`, the identity of the span on the device trace's clock
        chunk = {"index": request.prefill_chunks, "pos": start, "of": n}
        self._stamp_prefill(step, [request])
        (logits, self.cache_k, self.cache_v,
         *stats) = self.programs.extend(
            self.params, jnp.asarray(ids),
            jnp.asarray([chunk_len], np.int32),
            jnp.asarray([start], np.int32),
            jnp.asarray(self._block_tables[slot_id:slot_id + 1]),
            self.cache_k, self.cache_v,
            slot_ids=[slot_id], lora_idx=lora_idx)
        step.mark("compute")
        jax.block_until_ready(logits)  # async dispatch; time real execution
        self.metrics.record_prefill_step(step.mark("emit") - step.t0)

        slot.prefill_pos = start + chunk_len
        self._fr_emit(request, "prefill_chunk", tokens=chunk_len,
                      cached_tokens=0, pos=start)
        if slot.prefill_pos >= n:
            slot.prefilling = False
            self._release_cache_entry(slot)  # suffix landed; donor evictable
            self._activate_slot(slot_id, request, n, logits)
        self._record_step(
            "prefill", step,
            active_slots=1, tokens=chunk_len,
            slots=[slot_id], chunk=chunk,
            counters=self._prefill_counters(stats, step),
        )
        return True

    def _activate_slot(self, slot_id: int, request: Request, n: int,
                       logits) -> None:
        """Single-slot activation (chunked/CP prefill completions): the
        sampled first token stays ON DEVICE and is emitted with the next
        decode fetch, so activation costs no host sync. first_token_at is
        stamped when the token actually reaches the host (_emit), keeping
        TTFT client-honest."""
        self._activate_group(
            [(slot_id, request, n)],
            np.asarray([slot_id], np.int32),
            np.asarray([n], np.int32),
            logits,
        )

    def _window_for(self, active: list[int], k: int) -> int:
        """Smallest context-window bucket covering every active sequence
        plus the k tokens this dispatch will add."""
        return self._window_covering(
            max(int(self._seq_lens[i]) for i in active) + k + 1)

    def _window_covering(self, cells: int) -> int:
        """Smallest context-window bucket of at least `cells` cells."""
        for w in self._window_buckets:
            if w >= cells:
                return w
        return self.slot_capacity

    def _live_rows(self, active: list[int]) -> np.ndarray:
        """[slots] bool, true for the rows a decode dispatch emits for. It
        rides the call as a host array (as the rows of _activate_rows do):
        the device's length counters cannot say which rows decode — a freed
        or never-used row keeps counting, a prefilling one is parked at
        capacity - 1 — and the attention kernel walks the pages of live rows
        only (models/llama._decode_paged_impl)."""
        live = np.zeros((self.num_slots,), np.bool_)
        live[active] = True
        return live

    def _kv_pages(self, active: list[int], k: int, window: int) -> dict:
        """A decode record's two page counts: the pages the dispatch's live
        rows hold once its k tokens are written — what the attention kernel
        walks at the burst's last step — and the pages a rectangular
        (slots x window) grid would sweep."""
        ps = self.kv_page_size
        live = sum(-(-(int(self._seq_lens[i]) + k) // ps) for i in active)
        return {"kv_pages_live": live,
                "kv_pages_window": self.num_slots * -(-window // ps)}

    def _decode_active(self) -> bool:
        decode_pool = (self.split.decode_pool if self.split is not None
                       else range(self.num_slots))
        active = [
            i for i in decode_pool
            if self.slots[i].request is not None
            and not self.slots[i].prefilling
        ]
        if not active:
            # The occupancy gauge is otherwise only written on decode steps
            # and would freeze at the last batch size on an idle engine.
            self.metrics.set_batch_occupancy(0)
            self._ahead_blocked_by = "first"  # the next burst follows none
            return False

        # Speculative decoding: when any active slot proposes drafts, ONE
        # verify dispatch replaces this step's decode — it scores all drafts
        # plus a correction/bonus sample and emits 1..K+1 tokens per slot.
        # Constrained slots ride the same dispatch with per-position FSM
        # lookahead masks, so a JSON-mode request advances multi-token
        # instead of forcing the whole batch into single-step decode. With
        # no drafter attached anywhere this block is a no-op and the decode
        # path below is bit-identical to the pre-speculation engine.
        clock = self._clock()
        if self._spec_available and any(
            self.slots[i].drafter is not None for i in active
        ):
            step = clock.begin("draft")
            fused_spec = self._fused_step_ok(active)
            drafts, lookahead = self._collect_drafts(active, fused=fused_spec)
            if any(drafts.values()):
                return self._verify_active(active, drafts, lookahead, step,
                                           fused=fused_spec)
            # no n-gram matched: fall through to plain decode; the draft
            # span stays on this step's record
            t_sync = step.mark("host_sync")
        else:
            step = clock.begin("host_sync")
            t_sync = step.t0
        # alloc-on-extend: every page this dispatch writes must exist
        # before the tables ship to the device (a block family: every cell a
        # commit of this burst may write)
        active = self._ensure_decode_pages(
            active, self._burst_bounds(self.decode_burst).reach - 1)
        if not active:
            clock.abandon()
            self.metrics.set_batch_occupancy(0)
            return True  # pool exhaustion finished requests: work done
        self._sync_block_tables()

        self._key, sk = jax.random.split(self._key)
        k = self.decode_burst
        # Constrained slots advance a host-side FSM per token, so on the
        # LEGACY path their mask cannot be updated mid-burst: any constrained
        # slot in the batch forces single-step decode for this dispatch (the
        # constrained-TPS cost documented in docs/structured-outputs.md).
        # With fused decode the grammar lives on the device (ops/grammar) and
        # constrained slots ride the burst scan — the fallback below only
        # fires when a schema failed to register (budget), and is counted so
        # the "zero single-step fallbacks" invariant is checkable.
        constrained_active = self._constrained_count > 0 and any(
            self.slots[i].constraint is not None for i in active
        )
        # a block family's burst is ONE program at any k (its passes)
        fused_step = self.block > 1 or self._fused_step_ok(active)
        if k > 1 and constrained_active and not fused_step:
            k = 1
            self.metrics.record_constrained_burst_fallback()
        if k > 1 or fused_step:
            return self._decode_bursts(
                step, t_sync, active, k, sk, fused_step,
                grammar=fused_step and constrained_active)

        lora_idx = self._d_lora_idx if self.lora is not None else None
        first_in = self._d_last_tokens  # pre-step tokens: pending firsts
        window = self._window_for(active, 1)
        kv_pages = self._kv_pages(active, 1, window)
        step.mark("dispatch")
        (logits, self.cache_k, self.cache_v,
         *_) = self.programs.decode_step(
            self.params, self._d_last_tokens, self._d_seq_lens,
            self.cache_k, self.cache_v, self._d_block_tables,
            window=window, live=self._live_rows(active), lora_idx=lora_idx)
        mask = None
        if constrained_active:
            step.mark("host_sync")
            mask = self._sync_mask()
            self.metrics.record_masked_decode_step()
            step.mark("dispatch")
        tokens_dev = sample_tokens(
            logits, sk, self._d_temps, self._d_top_ps, self._d_top_ks,
            mask, self._d_seeds, self._d_seq_lens,
        )
        self._d_last_tokens = tokens_dev
        self._d_seq_lens = self._d_seq_lens + 1
        step.mark("compute")
        jax.block_until_ready(tokens_dev)  # device execution, not transfer
        step.mark("fetch")
        # the one D2H sync per step; row 0 carries deferred first emissions.
        # itl = this step's duration: a deferred first and its decode token
        # land in the same fetch, so the wall gap between them is ~0 and
        # would skew the histogram exactly like an unamortized burst.
        tokens = self._fetch_tokens(jnp.stack([first_in, tokens_dev]))
        step_s = step.mark("emit") - t_sync
        self.metrics.record_decode_step(step_s, len(active))
        self._begin_delivery(step)
        self._emit_fetched(tokens, self._burst_rows(active), itl=step_s)
        self._record_step(
            "decode", step,
            active_slots=len(active), tokens=len(active),
            slots=active,
            # legacy eager step: model forward, sample, lens advance are
            # separate dispatches, plus the mask scatter when constrained
            dispatches=3 + (1 if mask is not None else 0), fused=False,
            kv_pages=kv_pages,
        )
        return True

    def _burst_rows(self, active: list[int]) -> list[tuple[int, Request, bool]]:
        """The rows of a decode dispatch as _emit_fetched delivers them:
        (slot, its request, whether the request's first token is pending)
        at the dispatch."""
        return [(i, self.slots[i].request, self.slots[i].first_pending)
                for i in active]

    def _decode_bursts(self, step: StepSpan, t_cycle: float,
                       active: list[int], k: int, sk, fused_step: bool,
                       grammar: bool) -> bool:
        """The decode burst — k steps in one program, or a block family's k
        passes: ONE loop for both, and what a burst is (its bounds, its
        program and the state it carries, the rows that go on, its
        delivery) follows the family's block length in _burst_bounds,
        _dispatch_burst, _prepare_burst and _deliver_burst — and as many
        more as can leave AHEAD: a burst whose rows need nothing from the
        host is dispatched right after its predecessor's fetch, and the
        predecessor's tokens are delivered and its record closed while it
        computes (docs/scheduling.md "The five orders of a decode cycle").
        Where the one thing in the way is an arrival that can be placed
        without the predecessor's emit (_admit_ahead), its prefill and its
        activation leave first and the burst, its rows among the burst's,
        right behind them — and where it is ONE arrival whose prompt fits
        the mixed step's width (_rides), nothing leaves for it at all: it
        is placed (_admit_riding) and the burst is the program whose first
        step carries its prompt. Either way the host prepares the next burst
        (_prepare_burst) between a dispatch and the wait for it — and where
        nothing stands in that burst's way even then and no slot is free
        for an arrival (_queues_behind), it is dispatched BEFORE the wait,
        QUEUED BEHIND the burst in flight: the device starts it the moment
        its predecessor ends, and whatever lies between a fetch and the
        next program's first operation runs under a burst (at most
        QUEUED_RUN bursts in a row: today every second burst of a full
        house; the next leaves ahead, behind its predecessor's fetch — the
        constant says for whom). Which order a
        cycle takes is decided from what the loop can observe
        (_queues_behind before the wait; _ahead_blocker, _arrivals_ahead
        after the fetch); where anything else stands in the way, the cycle
        is the parent's, step for step: emit, record, back through _loop,
        and host_sync again (which finds its pages grown and its tables
        clean).

        What the device holds. At every emit and every _prepare_burst
        exactly ONE burst the host has not fetched (`_in_flight` names it),
        with the prefill and activation dispatched in front of it; a second
        one, queued behind it, only while the host does nothing but wait
        for the first and fetch it. _prepare_burst's precondition (the
        burst of `rows` in flight, every earlier one emitted) is the same
        in every order, and so are the rows that go on and the pages taken;
        programs are dispatched in the order they would be otherwise — an
        admission takes today's order, because a free slot forbids queuing —
        so the sequence of keys is the same and tokens are identical,
        request for request (a riding arrival has no activation to split
        the key for it: greedy and seeded rows draw what they drew, rows on
        the engine's key draw from another sequence behind it). Nothing is
        in flight when this returns."""
        clock = self._clock()
        bounds = self._burst_bounds(k)
        window = self._window_for(active, bounds.reach - 1)
        plan = (self._burst_rows(active), window,
                self._kv_pages(active, bounds.counted, window))
        gram_args = {}
        if grammar:
            # Fresh int32 cursor vector from the host FSMs (source of
            # truth, advanced in _emit): one [SLOTS] H2D per step instead
            # of a [SLOTS, V] float32 mask scatter. Free/parked rows sit
            # at cursor 0 — the all-zero free row.
            gs = np.zeros((self.num_slots,), dtype=np.int32)
            for i in active:
                slot = self.slots[i]
                if slot.constraint is not None and slot.gram_offset >= 0:
                    gs[i] = slot.gram_offset + slot.constraint.state
            gram_args = {
                "gram_table": self._grammar_tables.device(),
                "gram_state": jnp.asarray(gs),
            }
            self.metrics.record_masked_decode_step()

        def leave(step, plan, key, blocked_by, queued=False,
                  riding=None) -> _Burst:
            """Dispatch the burst of `plan`, whose record is `step`; with
            `riding`, the arrival whose prompt is its first step's."""
            rows, window, kv_pages = plan
            burst = _Burst(step, rows, kv_pages, blocked_by, queued,
                           admitted=riding)
            burst.toks_dev = self._dispatch_burst(
                window, key, burst.slots, grammar, gram_args, riding)
            return burst

        # what no wait for a burst changes (a grammar's cursors above are
        # the host FSMs': such a burst is never followed ahead)
        fixed = self._ahead_fixed_blocker(active, grammar)
        blocked_by, self._ahead_blocked_by = self._ahead_blocked_by, "first"
        prev: _Burst | None = None
        # the burst dispatched behind the one in flight, before its fetch,
        # and how many in a row have been (QUEUED_RUN)
        queued: _Burst | None = None
        run = 0
        # the prefill dispatched ahead, in front of the burst about to leave
        placed: _AheadPrefill | None = None
        # the arrival placed to ride the burst about to leave
        riding: _Riding | None = None
        while True:
            if queued is not None:
                # on the device already: its record begins where its
                # predecessor's ended, under `emit_inflight`
                burst, queued = queued, None
                burst.step = step
            else:
                if prev is None:
                    step.mark("dispatch")
                else:
                    # the key is split at the dispatch, never at the
                    # preparation: the sequence of keys is that of bursts
                    # and activations in the order they are dispatched
                    self._key, sk = jax.random.split(self._key)
                burst = leave(step, plan, sk, blocked_by, riding=riding)
                riding = None
                if prev is not None:
                    step.mark("emit_inflight")
            self._in_flight = burst
            if prev is not None:
                self._deliver_burst(prev, k, fused_step, closed=True)
                if placed is not None:
                    self._record_ahead_prefill(placed)
                    placed = None
            plan = None
            if fixed is None:
                step.mark("host_sync_inflight")
                plan = self._prepare_burst(burst.rows, k)
                if run < self.QUEUED_RUN and self._queues_behind(plan):
                    step.mark("dispatch_inflight")
                    self._key, sk = jax.random.split(self._key)
                    queued = leave(None, plan, sk, None, queued=True)
                    run += 1
                else:
                    run = 0
            step.mark("compute")
            # split device execution from the D2H readback: the dispatch
            # returned futures, block_until_ready is the compute wait, the
            # fetch below is pure transfer
            jax.block_until_ready(burst.toks_dev)
            step.mark("fetch" if queued is None else "fetch_inflight")
            # ONE D2H per k tokens
            burst.fetched = self._fetch_tokens(burst.toks_dev)
            if burst.admitted is not None:
                self._rode(burst.admitted)
            if queued is not None:
                # the fetched burst's record ends here and the queued one's
                # begins: no decision is left to take, it has left
                step = clock.handover(step, "decode", "emit_inflight")
                burst.step_s = (step.t0 - t_cycle) / k
                prev, t_cycle = burst, step.t0
                continue
            self._in_flight = None
            blocked_by = fixed or self._ahead_blocker(plan)
            arrivals = (self._arrivals_ahead(plan, k)
                        if blocked_by == "admission" else None)
            # Tokens reach the host back-to-back, so wall-clock gaps between
            # _emit calls are ~0 and would poison the ITL histogram; record
            # the amortized per-token pacing of the burst's cycle instead.
            if arrivals is not None:
                # the burst's record ends here, the prefill's begins behind
                # the placing, and the next burst's where the prefill's ends
                clock.close(step, "decode")
                burst.step_s = (step.t1 - t_cycle) / k
                clock.switch("admit")  # the placing, whichever way it goes in
                if self._rides(arrivals, plan, k):
                    # no prefill and no activation: the next burst's record
                    # begins behind the placing, the prompt in its first step
                    riding, plan = self._admit_riding(arrivals[0], plan, k)
                    step = clock.begin("dispatch", after=step)
                    self._stamp_prefill(step, [riding.request], cut=False)
                    prev, t_cycle, blocked_by = burst, step.t0, None
                    continue
                placed, plan = self._admit_ahead(step, arrivals, plan, k)
                step = clock.handover(placed.step, "prefill",
                                      "dispatch_inflight")
                prev, t_cycle, blocked_by = burst, step.t0, None
                continue
            if blocked_by is None:
                step = clock.handover(step, "decode", "dispatch")
                burst.step_s = (step.t0 - t_cycle) / k
                prev, t_cycle = burst, step.t0
                continue
            self._ahead_blocked_by = blocked_by
            burst.step_s = (step.mark("emit") - t_cycle) / k
            self._deliver_burst(burst, k, fused_step, closed=False)
            return True

    def _queues_behind(self, plan) -> bool:
        """With a burst in flight, its predecessor emitted and `plan`
        prepared for the next (_prepare_burst): whether that next burst
        leaves NOW, before the wait for the one in flight. It does where
        nothing the loop can observe stands in its way (_ahead_blocker, the
        predicate of the fetch, asked a burst earlier) and NO SLOT IS FREE:
        an arrival is placed only in a free slot (_arrivals_ahead), so with
        none there is nothing the loop could do for one at the fetch that it
        cannot do now, and with one the cycle keeps the ahead order, where
        an arrival's prefill goes in front of the next burst."""
        return not self._free_slots() and self._ahead_blocker(plan) is None

    # The most bursts in a row that leave queued behind their predecessors;
    # the next one waits for its predecessor's fetch and leaves ahead, which
    # leaves the device one gap of a fetch and a call (2-4 ms) every
    # QUEUED_RUN + 1 bursts. NOTHING IN THE ENGINE NEEDS THAT GAP. It is
    # there for the measuring harness's trace reader alone, which is not
    # this code's to change: the profiler's trace ends 20-70 ms behind the
    # host-clock window the reader divides the device's busy time by, so a
    # full house that queues every burst reads busier than its window
    # (8.023 s of 7.993) and the run is refused. A run of 5 read 30-37 ms
    # under its window, inside what that tail varies by; 1 (every second
    # burst of a full house queues) leaves 1.3-1.6% of the device's time
    # (docs/scheduling.md "A run of queued bursts is bounded"; PERF.md
    # section 7 says what lifts it).
    QUEUED_RUN = 1

    def _burst_bounds(self, k: int) -> _BurstBounds:
        """What a burst of k may do to a row: a dense one writes k tokens
        (and the host keeps a cell ahead of them); a block family's reaches
        _block_reach() and commits at most a block a pass."""
        if self.block > 1:
            reach = self._block_reach()
            return _BurstBounds(reach, self.block * k, reach)
        return _BurstBounds(k + 1, k, k)

    def _dispatch_burst(self, window: int, key, slots: list[int],
                        grammar: bool, gram_args: dict,
                        riding: "_Riding | None" = None):
        """Call the burst's program for `window` over the rows `slots` —
        with `riding`, the one whose first step carries that arrival's
        prompt and which writes its row of the per-slot state itself: the
        per-slot state and the pool it returns are the loop's from here on
        (device futures: the next dispatch takes them unread). Returns the
        array the burst's ONE fetch reads."""
        live = self._live_rows(slots)
        if self.mixed_width and window not in self._mixed_wanted:
            self._mixed_wanted.append(window)  # for the prewarm thread
        if riding is not None:
            (self._d_last_tokens, self._d_seq_lens, self._d_temps,
             self._d_top_ps, self._d_top_ks, self._d_seeds, self.cache_k,
             self.cache_v, toks_dev) = self.programs.admit_many(window)(
                *self._decode_operands(key), live, riding.prompt_ids,
                riding.arrival, riding.arrival_f)
            return toks_dev
        if self.block > 1:
            (self._d_blk, self._d_masked, self._d_seq_lens, self._d_left,
             self._d_skip, self.cache_k, self.cache_v,
             out_dev) = self.programs.block_many(window)(
                *self._decode_operands(key), live)
            return out_dev
        # the adapter rows as the last activation left them (one ahead
        # of this burst donates the array an earlier burst was handed)
        lora_idx = self._d_lora_idx if self.lora is not None else None
        (self._d_last_tokens, self._d_seq_lens, self.cache_k,
         self.cache_v, toks_dev) = self.programs.decode_many(window, grammar)(
            *self._decode_operands(key), live,
            lora_idx=lora_idx, **gram_args,
        )
        return toks_dev

    def _deliver_burst(self, burst: _Burst, k: int, fused_step: bool, *,
                       closed: bool) -> None:
        """Emit a fetched burst's tokens — a block family's: the blocks its
        rows committed, in order (_emit_blocks) — and finalize its record;
        `closed`: LoopClock.handover has closed its step already."""
        rows, b = burst.rows, self.block
        fetched, counters = self.programs.unpack(
            burst.fetched, k * (b + 2) if b > 1 else k + 1)
        self.metrics.record_decode_step(burst.step_s, len(rows))
        self._begin_delivery(burst.step)
        block = None
        if b > 1:
            block = self._emit_blocks(
                fetched.reshape(k, b + 2, self.num_slots), rows,
                burst.step_s * k)
        else:
            self._emit_fetched(fetched, rows, itl=burst.step_s)
        record = self._observe_step if closed else self._record_step
        record("decode", burst.step, active_slots=len(rows),
               tokens=block["tokens_committed"] if block else k * len(rows),
               slots=burst.slots, dispatches=1, fused=fused_step,
               kv_pages=burst.kv_pages, counters=counters, block=block,
               burst=burst)

    def _ahead_fixed_blocker(self, active: list[int],
                             grammar: bool) -> str | None:
        """What keeps every burst of these rows from leaving ahead, and no
        wait for a burst changes: the loop is not alone (a coordinator's
        tick, split mode's lock), or a row needs the host between two
        bursts — its next mask comes from a host FSM advanced in _emit
        (`grammar`: a constrained row reaches a dense burst on the fused
        grammar alone), or a drafter reads what _emit appends."""
        if self.coordinator is not None or self.split is not None:
            return "control"
        if grammar:
            return "constraint"
        if self._spec_available and any(
                self.slots[i].drafter is not None for i in active):
            return "draft"
        return None

    def _ahead_blocker(self, plan) -> str | None:
        """After a burst's fetch: what today's loop would serve before the
        next burst, as far as the loop can observe it — a stop, drain, park
        or flush request; a slot in prefill; a request in the inbox, the
        class queues or held on the pool — or why _prepare_burst has no
        burst to offer. None: the prepared burst may leave now."""
        if (not self._running or self._stop_requested or self.draining
                or self._drain_park_requested or self._park_rids
                or self._drain_flush_requested):
            return "control"
        if any(s.prefilling for s in self.slots):
            return "prefilling"
        if (self._held_request is not None or not self.pending.empty()
                or any(self._class_queues.values())):
            return "admission"
        return plan if isinstance(plan, str) else None

    def _arrivals_ahead(self, plan, k: int
                        ) -> "list[tuple[Request, int, int, bool]] | None":
        """After a burst's fetch, with `admission` the one thing in the way
        of the prepared burst `plan`: the queued requests in the order
        _try_insert would take them — each with the tokens it prefills (a
        block family: the prompt's whole blocks), the pages it takes (those
        tokens' and what the burst behind the prefill may write) and whether
        the prefix cache was asked about it — if EVERY one of them
        can be placed now and prefilled as ONE one-shot group, without the
        host having emitted the fetched burst; else None, and the cycle
        takes today's order, where _try_insert serves them all behind the
        emit. That is: there is a burst to put them in (`plan` has rows and
        pages); none is held on the pool; a slot is free NOW for each (the
        slots the un-emitted burst will free do not count, so a queue deeper
        than the free slots waits for that emit, as it does today); each is
        fresh (not cancelled, expired, parked or carrying KV as bytes),
        unconstrained (a constrained activation fetches its first token),
        gets no drafter, fits a one-shot bucket and the iteration's prefill
        budget, and misses the prefix cache as it stands (a hit extends
        behind the donor's pages; with a host-RAM tier a miss may still be
        a restore, so such an engine keeps today's order); all share one
        bucket; and the free list alone covers every prompt plus what the
        burst behind the prefill writes, all or none — nothing is evicted,
        parked or preempted for an arrival placed ahead. Moves the inbox
        into the class queues (as _try_insert would a few spans later) and
        changes nothing else."""
        if (not isinstance(plan, tuple) or self.kv_offload is not None
                or self._held_request is not None):
            return None
        free = len(self._free_slots())
        if not free:
            return None  # a full house: nothing is touched, the inbox neither
        self._drain_pending()
        queued = self._queued_requests()
        if not queued or len(queued) > min(free, self.MAX_PREFILL_GROUP):
            return None
        cutoff = self.prefill_buckets[-1] if self.prefill_buckets else 0
        budget = self._prefill_budget_now()
        if budget:
            cutoff = min(cutoff, self._budget_chunk_len(budget))
        if budget and sum(len(r.prompt_ids) for r in queued) > budget:
            return None
        reach = self._burst_bounds(k).reach
        arrivals: list[tuple[Request, int, int, bool]] = []
        bucket = None  # the group's, set by its first request
        for request in queued:
            n = len(request.prompt_ids)
            room = self.slot_capacity - n - self.block  # as in _try_insert
            n -= n % self.block  # what prefills: the prompt's whole blocks
            if (self._is_cancelled(request) or request.deadline_expired()
                    or request.parked is not None
                    or request.kv_restore is not None
                    or request.sampling.constraint is not None
                    or request.compiled_constraint is not None
                    or self._speculates(request)
                    or n > cutoff or room <= 0):
                return None
            bucket = bucket or self._bucket_for(n)
            if self._bucket_for(n) != bucket:
                return None
            cacheable = (self.prefix_cache is not None
                         and n - 1 >= self.min_prefix_len)
            if cacheable and self.prefix_cache.match(
                    request.prompt_ids, max_len=n - 1,
                    ns=request.sampling.lora) is not None:
                return None
            pages = self._pages_for_tokens(min(n + reach, self.slot_capacity))
            arrivals.append((request, n, pages, cacheable))
        if sum(a[2] for a in arrivals) > self.page_pool.available():
            return None
        return arrivals

    def _rides(self, arrivals: "list[tuple[Request, int, int, bool]]",
               plan: tuple, k: int) -> bool:
        """Whether what _arrivals_ahead found RIDES the prepared burst
        `plan`: it is exactly ONE arrival, its prompt fits the mixed step's
        width (`mixed_width`, 0 where this engine has no mixed step), and
        the mixed program of the burst's window STANDS (the prewarm thread
        has lowered it: nothing is built between two bursts of a house that
        decodes). Two or more at once, a longer prompt, a window whose
        program is not there yet take the admission-ahead order; what
        _arrivals_ahead refuses (a chunked or cached prompt, a grammar, a
        drafter, a resume) takes today's."""
        if len(arrivals) != 1 or not 0 < arrivals[0][1] <= self.mixed_width:
            return False
        # the burst's window with the arrival in it, as _plan_with will
        # find it: the row enters with n - 1 cells
        window = max(plan[1], self._window_covering(
            arrivals[0][1] + self._burst_bounds(k).reach - 1))
        return window in self._mixed_ready

    def _admit_riding(self, arrival: "tuple[Request, int, int, bool]",
                      plan: tuple, k: int) -> "tuple[_Riding, tuple]":
        """Admission INSIDE a burst: with the burst in front fetched and not
        emitted, its record closed, place `arrival` as _admit_ahead does —
        the same slot, the same pages from the free list in one piece, the
        placing under the loop's `admit` — and dispatch nothing: its prompt
        is the first step of the prepared burst `plan`, which the caller
        dispatches right behind with the new row in it
        (StepPrograms.admit_many). Returns the arrival with its operands and
        that plan.

        The host's mirror of the row is what the device's is: it enters the
        burst with n - 1 tokens, a decode row whose first step takes the
        whole prompt in place of one token, so every one of the burst's k
        steps brings it a token — the first of them its FIRST token — and
        advances its length by one: nothing of the burst's arithmetic
        (_prepare_burst, _emit_fetched, the record's tokens and pages) knows
        a riding row from another."""
        request, n = arrival[:2]
        slot_id = self._place_ahead(arrival, self._free_slots()[0])
        slot = self.slots[slot_id]
        slot.out_tokens = []
        slot.last_emit_at = 0.0
        slot.first_pending = False  # it comes as the burst's first decode token
        self._seq_lens[slot_id] = n - 1
        self._sync_block_tables()
        self.metrics.record_mixed_admission()
        sampling = request.sampling
        prompt_ids = np.zeros((1, self.mixed_width), np.int32)
        prompt_ids[0, :n] = request.prompt_ids[:n]
        seed = -1 if sampling.seed is None else sampling.seed & 0x7FFFFFFF
        riding = _Riding(
            slot_id, request, n, prompt_ids,
            np.asarray([slot_id, n, sampling.top_k, seed], np.int32),
            np.asarray([sampling.temperature, sampling.top_p], np.float32))
        return riding, self._plan_with(plan, [slot_id], k)

    def _rode(self, riding: _Riding) -> None:
        """The burst that carried `riding`'s prompt is fetched: the first
        instant the host knows the prompt filled. The request's `prefill`
        stage ends here (`activated_at`: what an activation's issue is to a
        prefilled group) and its `prefill_chunk` event is stamped here, as
        _record_ahead_prefill does behind its wait; its first token is in
        the fetch, so `first_fetch` reads the way to the emit."""
        self._stamp_activated([riding.request], stepstats._now())
        self._fr_prefilled([(riding.slot, riding.request, riding.tokens)])

    def _admit_ahead(self, step: StepSpan,
                     arrivals: "list[tuple[Request, int, int, bool]]",
                     plan: tuple, k: int) -> "tuple[_AheadPrefill, tuple]":
        """Admission AHEAD: with the burst of the closed, unrecorded `step`
        fetched and not emitted, place `arrivals` (_arrivals_ahead said they
        can be) as _try_insert's one-shot path does, and dispatch their
        prefill and their activation without waiting for either. Returns the
        prefill, its step open, and the prepared burst `plan` with the new
        rows in it (first token pending; a block family's row has none,
        its first block opens in the burst): the caller dispatches that burst
        right behind, so the device runs prefill, activation and burst back
        to back while the host emits the fetched burst. Each arrival's
        pages, the prompt's and what the burst will write, come from the
        free list in one piece. The placing is the loop's `admit`, as in
        today's order."""
        free = self._free_slots()
        group = [(self._place_ahead(arrival, free.pop(0)), arrival[0],
                  arrival[1]) for arrival in arrivals]
        prefill = self._prefill_group(self._bucket_for(group[0][2]), group,
                                      after=step)
        self._sync_block_tables()
        return prefill, self._plan_with(
            plan, [slot_id for slot_id, _, _ in group], k)

    def _place_ahead(self, arrival: "tuple[Request, int, int, bool]",
                     slot_id: int) -> int:
        """Take `arrival` (one of _arrivals_ahead's) off its queue and give
        it the free slot `slot_id` and its pages, from the free list in one
        piece: the slot is claimed BEFORE any dispatch, as _try_insert
        does. Returns the slot."""
        request, _n, pages, cacheable = arrival
        popped = self._pop_request()
        assert popped is request, "the queue moved under _arrivals_ahead"
        if cacheable:
            self.metrics.record_prefix_miss()
        self._assign_slot_pages(slot_id, (), self.page_pool.alloc(pages))
        self.slots[slot_id].request = request
        self.slots[slot_id].generated = 0
        self._attach_constraint(slot_id, request)
        return slot_id

    def _plan_with(self, plan: tuple, new: list[int], k: int) -> tuple:
        """The prepared burst `plan` with the rows of the slots `new`, just
        placed, in it: as their placing left them (_burst_rows: a dense row
        behind its activation has its first token pending; a riding row and
        a block family's have none), the window and the pages counted with
        theirs."""
        rows, window, kv_pages = plan
        bounds = self._burst_bounds(k)
        window = max(window, self._window_for(new, bounds.reach - 1))
        of_new = self._kv_pages(new, bounds.counted, window)
        kv_pages = {"kv_pages_live": (kv_pages["kv_pages_live"]
                                      + of_new["kv_pages_live"]),
                    "kv_pages_window": of_new["kv_pages_window"]}
        rows = sorted(rows + self._burst_rows(new), key=lambda row: row[0])
        return rows, window, kv_pages

    def _prepare_burst(self, rows: list[tuple[int, Request, bool]], k: int):
        """With the burst of `rows` in flight and every earlier one emitted:
        what host_sync would do for the NEXT burst, from the lengths its
        rows will have. The mirrors lag by the burst in flight: a dense
        row's length is `_seq_lens` + k, which the host can count; a block
        row's is at most `_seq_lens` + block · k (a commit a pass), which it
        can only BOUND, so the next burst's cells are taken for the worst
        case: up to `_seq_lens` + block · k + _block_reach().

        Which rows go on. A dense row the host can count to its end inside
        the burst in flight (max_tokens, the slot's capacity) is not a row
        of the next; one that ends there by EOS, stop or cancel is, and
        _emit_fetched drops its column. Of a block row the host only knows
        whether it CAN end there by count (`generated` + block · k reaches
        its `token_limit`): every row that holds its request goes on — the
        program runs no pass for one that ended (`left` 0; one write to the
        slot's last cell, as for a row that is not decoding) and
        _emit_blocks drops its column — but where NO row is sure to outlive
        the burst in flight there is no next burst to offer: ten passes
        over nothing would stand in front of the next arrival.

        Pages come from the free list alone — this never evicts, parks,
        preempts or finishes anything (_ensure_decode_pages may do all
        four, and a park would read `out_tokens` that lag) — and all of
        them or none. Returns (rows, window, kv_pages) of the next burst,
        or why there is none: "pages", or "first" where no row (is sure
        to) outlive the burst in flight."""
        bounds = self._burst_bounds(k)
        nxt: list[int] = []
        need: dict[int, int] = {}
        outlives = False
        for i, request, first in rows:
            slot = self.slots[i]
            if slot.request is not request:
                continue  # ended in the emit before this one
            length = int(self._seq_lens[i]) + bounds.advance
            limit = (slot.token_limit if self.block > 1
                     else request.sampling.max_tokens)
            may_end = (slot.generated + first + bounds.advance >= limit
                       or length + 1 >= self.slot_capacity)
            if may_end and self.block == 1:
                continue  # a dense row: counted to its end
            outlives = outlives or not may_end
            nxt.append(i)
            short = self._pages_short(i, length + bounds.reach)
            if short > 0:
                need[i] = short
        if not outlives:
            return "first"
        if sum(need.values()) > self.page_pool.available():
            return "pages"
        for i, short in need.items():
            self._extend_slot_pages(i, self.page_pool.alloc(short))
        self._sync_block_tables()
        window = self._window_for(nxt, bounds.advance + bounds.reach - 1)
        return ([(i, self.slots[i].request, False) for i in nxt], window,
                self._kv_pages(nxt, bounds.advance + bounds.counted, window))

    def _block_reach(self) -> int:
        """Positions past its committed length that a row may write in one
        burst: a block may commit in every pass (its commit rides with the
        next block's first unmasking, which at `denoising_steps` 1 completes
        it), and a pass writes two blocks behind the committed length — the
        complete one and the open one of a row that commits, the open one
        and a block of padding of a row that does not."""
        return self.block * (self.decode_burst + 1)

    def _emit_blocks(self, out: np.ndarray,
                     rows: list[tuple[int, Request, bool]],
                     burst_s: float) -> dict:
        """Deliver one fetched burst of block passes, `out` [passes, B + 2,
        SLOTS] as the block program lays it out, to the requests that held
        the columns' slots when it was DISPATCHED (`rows`, _burst_rows; a
        slot whose request has ended since, or that holds another by now,
        has its column dropped, as in _emit_fetched): each block a row
        committed, in order, less the given tokens at the head of its first
        block, as ONE event of several tokens; a request that ends inside a
        block (max_tokens, EOS, cancel) takes the tokens before its end.
        `slot.given` and `_seq_lens` advance here and nowhere else. Returns
        the burst's counts for its step record: what the DEVICE did for the
        burst's rows (a dropped column's passes among them) and what was
        delivered (`blocks_committed`, `tokens_committed`); `blocks_fused`
        are the commits whose pass unmasked positions too, the next
        block's."""
        b = self.block
        active = [i for i, _, _ in rows]
        commits = {i: int((out[:, 0, i] >= 0).sum()) for i in active}
        counts = {"block_passes": int(out.shape[0]),
                  "row_passes": int(out[:, b + 1][:, active].sum()),
                  "positions_unmasked": int(out[:, b][:, active].sum()),
                  "blocks_fused": int(((out[:, 0] >= 0) & (out[:, b] > 0))
                                      [:, active].sum()),
                  "blocks_committed": 0, "tokens_committed": 0}
        committed: dict[int, tuple] = {}  # row -> (blocks, tokens, request)
        for t in range(out.shape[0]):
            for i, request, _ in rows:
                slot = self.slots[i]
                if out[t, 0, i] < 0 or slot.request is not request:
                    continue
                fresh = out[t, slot.given:b, i].tolist()
                slot.given = 0
                start = int(self._seq_lens[i])
                self._seq_lens[i] = start + b
                held: list[int] = []
                # the burst's pacing, amortized over what the row committed
                itl = burst_s / (commits[i] * b)
                for token in fresh:
                    self._emit(i, token, itl=itl, held=held)
                    if slot.request is None:
                        break  # finished (its tokens went out before `done`)
                self._put_held(request, held)
                counts["blocks_committed"] += 1
                counts["tokens_committed"] += b
                n_blocks, n_tokens, _ = committed.get(i, (0, 0, None))
                committed[i] = (n_blocks + 1, n_tokens + len(fresh), request)
        for n_blocks, n_tokens, request in committed.values():
            self._fr_emit(request, "commit", blocks=n_blocks,
                          tokens=n_tokens)
        return counts

    def _emit_fetched(self, tokens, rows: list[tuple[int, Request, bool]],
                      itl: float | None) -> None:
        """Deliver one fetched token matrix [rows, SLOTS], column by column,
        to the requests that held the columns' slots when it was DISPATCHED
        (`rows`, _burst_rows): everything the fetch brought a request goes
        out as ONE content event. A slot whose request has ended since — in
        the emit of the burst before, with this one already in flight — has
        its column dropped, and so has one that holds another request by
        now. Row 0 holds the deferred first emission of a request activated
        since the fetch before the dispatch (no seq_len advance — the first
        token is prefill output, not a decode step); rows 1.. are decode
        steps. A slot that finishes mid-matrix (EOS / max_tokens / capacity
        / cancel) has the rest of its column trimmed. Slots share no state
        here, so the order of the columns is free."""
        for i, request, first in rows:
            slot = self.slots[i]
            if slot.request is not request:
                continue
            held: list[int] = []
            if first:
                slot.first_pending = False
                # first=True: the grammar FSM already advanced on this token
                # at activation (the synchronous fetch there) — advancing
                # again would double-step the grammar.
                self._emit(i, int(tokens[0, i]), first=True, held=held)
            if not slot.prefilling:
                for token in tokens[1:, i].tolist():
                    if slot.request is None:
                        break  # finished: its tokens went out before `done`
                    self._seq_lens[i] += 1
                    self._emit(i, token, itl=itl, held=held)
            self._put_held(request, held)

    @staticmethod
    def _put_held(request: Request | None, held: list[int]) -> None:
        """Put the content event of one row and fetch and leave `held`
        empty; nothing where _emit already put it before the `done`."""
        if held:
            request.events.put(("tokens", held[:]))
            held.clear()

    def _emit(self, slot_id: int, token: int, *, held: list[int],
              itl: float | None = None, first: bool = False) -> None:
        """Deliver one generated token. `itl` overrides the wall-clock
        inter-token gap (burst decode delivers k tokens back-to-back; the
        caller passes the amortized pacing instead). `first` marks the
        deferred first emission, whose grammar advance already happened at
        activation. `held` collects the tokens one fetch brought this row
        (a burst's column, a speculative step's accepted span, a committed
        block) for the ONE ("tokens", [ids]) event the caller puts after
        the last of them; where the request ends here, what was held goes
        out before its `done` and `held` is left empty."""
        slot = self.slots[slot_id]
        request = slot.request
        assert request is not None
        if self._is_cancelled(request):
            self._put_held(request, held)
            request.finished_at = stepstats._now()
            request.events.put(("done", "cancelled"))
            self._fr_emit(request, "finished", reason="cancelled",
                          generated=slot.generated)
            self.metrics.record_request_done("cancelled")
            self._release_lora(request)
            self._cancelled_effective.discard(request.request_id)
            self._free_slot_kv(slot_id)
            self._clear_constraint(slot_id)
            slot.request = None
            slot.generated = 0
            slot.last_emit_at = 0.0
            slot.first_pending = False
            slot.drafter = None
            slot.spec_k = 0
            slot.out_tokens = []
            return
        slot.generated += 1
        if token != self.eos_id:
            # committed-sequence mirror: what a preemption park would need
            # to chunk-prefill on resume (EOS finishes, never parks)
            slot.out_tokens.append(token)
        # Incremental drafter update: every emitted token extends the
        # prompt-lookup index (first_pending emissions included — the first
        # token is part of the sequence the next proposal continues).
        if slot.drafter is not None and token != self.eos_id:
            slot.drafter.append(token)
        now = stepstats._now()
        if request.first_token_at is None:
            self._first_token(request, now)
        if not slot.last_emit_at:
            self.metrics.record_emit(None)  # first token: no inter-token gap
        else:
            self.metrics.record_emit(
                itl if itl is not None else now - slot.last_emit_at
            )
        slot.last_emit_at = now
        with self._lock:
            self.total_tokens += 1

        # Advance the grammar FSM on every sampled token; the updated mask
        # row governs the NEXT dispatch. The mask makes a disallowed sample
        # impossible, so advance() failing means a vocabulary gap forced the
        # EOS fallback — counted, not crashed on.
        state = slot.constraint
        if state is not None and not first:
            if not state.advance(token):
                self.metrics.record_constraint_violation()
            elif token != self.eos_id:
                self._set_mask_row(slot_id, state)

        finish: str | None = None
        if token == self.eos_id:
            finish = "stop"
        elif slot.generated >= request.sampling.max_tokens:
            finish = "length"
        elif self._seq_lens[slot_id] + 1 >= self.slot_capacity:
            finish = "length"
        elif self.block > 1 and slot.generated >= slot.token_limit:
            finish = "length"  # what the slot's capacity left a block row
        if (finish is not None and finish != "stop" and state is not None
                and not state.is_accepting):
            # cut short (max_tokens / capacity) before grammar acceptance
            self.metrics.record_constraint_violation()

        if finish != "stop":  # EOS itself is not emitted as content
            held.append(token)

        if finish is not None:
            self._put_held(request, held)
            request.finished_at = stepstats._now()
            if finish == "length" and request.export_kv and self.kv_ship:
                # Handoff export: serialize this stream's KV pages D2H
                # BEFORE the pool frees them below — the adopter lands
                # them and continues with zero prefill dispatches. Only
                # the budgeted "length" finish exports: stop/cancel means
                # the stream is over, there is nothing to move.
                request.kv_export = self._kv_export_payload(slot_id, request)
            request.events.put(("done", finish))
            self._fr_emit(request, "finished", reason=finish,
                          generated=slot.generated,
                          ttft_s=round(request.first_token_at
                                       - request.submitted_at, 6))
            self.metrics.record_request_done(finish)
            self._release_lora(request)
            if self.prefix_cache is not None:
                # Donor retention: the freed slot's rows [0, prompt_len) hold
                # exactly the prompt's KV — pin the head's pages for prefix
                # reuse instead of discarding; the slot frees immediately
                # below.
                self._maybe_cache_prefix(slot_id, request)
            self._free_slot_kv(slot_id)
            self._clear_constraint(slot_id)
            slot.request = None
            slot.generated = 0
            slot.last_emit_at = 0.0
            slot.first_pending = False
            slot.drafter = None
            slot.spec_k = 0
            slot.out_tokens = []

    def _begin_delivery(self, step: StepSpan) -> None:
        """The tokens `step`'s fetch brought are about to be emitted: the
        first tokens among them are its record's (_first_token)."""
        self._fetch_seq = step.seq
        self._first_tokens = []

    def _first_token(self, request: Request, now: float) -> None:
        """A request's first token reached the host at `now` (_emit's own
        read): its way in is complete. The stages (stepstats.way_in_stages)
        go, from the request's own stamps, to the three places that read
        them: the `first_token` flight-recorder event, the record of the
        step whose fetch brought the token (`first_tokens`, through
        _observe_step) and the cumulative `.metrics.way_in`; the cut of
        the stage `prefill` goes with them (`prefill_cut`). Once a
        request; inside a capture the instant is an annotation too."""
        request.first_token_at = now
        stages = stepstats.way_in_stages(request)
        # the stage `prefill` by what it waited for (stepstats.PREFILL_CUT):
        # absent for a prompt that rode a burst, a restored request, and a
        # stage two loops stamped
        cut = request.prefill_cut and request.prefill_cut.parts
        self.metrics.record_first_token(now - request.submitted_at, stages,
                                        cut, request.prefill_chunks)
        served = {"chunks": request.prefill_chunks,
                  "prefill_seq": request.prefill_seq}
        if cut:
            served["prefill_cut"] = cut
        entry = {"id": gateway_rid(request.request_id), **stages,
                 "cached_tokens": request.cached_tokens, **served}
        self._first_tokens.append(entry)
        self._fr_emit(request, "first_token", **stages, **served,
                      fetch_seq=self._fetch_seq)
        with first_token_annotation(entry["id"], self._fetch_seq):
            pass

    def _fail_all(self, message: str) -> None:
        for slot_id, slot in enumerate(self.slots):
            if slot.request is not None:
                slot.request.events.put(("error", message))
                self._fr_emit(slot.request, "errored", message=message)
                self.metrics.record_request_done("error")
                self._release_lora(slot.request)
                slot.request = None
            self._release_cache_entry(slot)
            self._free_slot_kv(slot_id)
            self._clear_constraint(slot_id)
            slot.prefilling = False
            slot.prefill_pos = 0
            slot.handoff_ready = False
            slot.handoff_logits = None
            slot.handoff_ready_at = 0.0
            slot.generated = 0
            slot.last_emit_at = 0.0
            slot.first_pending = False
            slot.drafter = None
            slot.spec_k = 0
            slot.out_tokens = []
        if self._held_request is not None:
            self._held_request.events.put(("error", message))
            self._fr_emit(self._held_request, "errored", message=message)
            self.metrics.record_request_done("error")
            self._release_lora(self._held_request)
            self._held_request = None
        for p in PRIORITY_CLASSES:
            q = self._class_queues[p]
            while q:
                r = q.popleft()
                r.events.put(("error", message))
                self._fr_emit(r, "errored", message=message)
                self.metrics.record_request_done("error")
                self._release_lora(r)
        while True:
            try:
                r = self.pending.get_nowait()
                r.events.put(("error", message))
                self._fr_emit(r, "errored", message=message)
                self.metrics.record_request_done("error")
                self._release_lora(r)
            except queue.Empty:
                break
