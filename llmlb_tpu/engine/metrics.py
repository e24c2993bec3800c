"""Engine Prometheus metrics: counters, gauges, and latency histograms.

The reference exposes Prometheus text only for cloud-proxy calls
(cloud_metrics.rs:21-39); the tpu:// engine goes further and instruments the
serving loop itself — TTFT and inter-token latency histograms, token/request
counters, queue depth — because those are the numbers a TPU serving operator
tunes against (and what the gateway's telemetry-aware scheduler ultimately
reflects). Dependency-free text exposition; threadsafe for the step loop.
"""

from __future__ import annotations

import threading

from llmlb_tpu.engine import compilelog
from llmlb_tpu.engine.streamstats import StreamStats
from llmlb_tpu.hoststats import cpu_seconds, watch_gc

# Bucket edges in seconds, chosen around serving targets: TTFT p50 goals are
# tens of ms (one-shot prefill) to seconds (chunked 4k prompts); ITL goals
# are single-digit ms on TPU.
TTFT_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
ITL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0)
# Per-dispatch step durations: prefill is tens of ms to seconds (bucketed
# prompt groups), a decode step is single-digit ms on TPU (burst-amortized).
STEP_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5)
# Schema→DFA→mask-table compiles: milliseconds for byte-level vocabularies,
# seconds for 128k-token vocabularies (docs/structured-outputs.md sizing).
COMPILE_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0,
                   30.0)
# Step-phase breakdown (engine/stepstats.py taxonomy): host-side phases
# (plan/sync/dispatch/fetch/emit) are tens of µs to low ms; compute spans
# µs (CPU debug configs) to hundreds of ms (chunked prefill on TPU).
PHASE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)

# Why a KV page transfer fell back to chunk-prefill replay
# (docs/kv-cache.md): shipping knob off / split mode / multihost
# (disabled), no payload arrived with shipping on (absent), wire version
# skew (version), pool dtype or page-size or model-geometry mismatch
# (dtype / page_size / geometry), adopter could not reserve pages
# (capacity), malformed payload (error). Closed set: the fallback counter
# renders one series per reason from the first scrape.
# A block family's counts of one burst of block passes, as its `decode` step
# records carry them and `/api/health .metrics` totals them (`<name>_total`):
# passes of the burst; decoding rows summed over the passes; blocks and
# positions committed; positions unmasked; commits whose pass also ran the
# next block's first unmasking (the others are the last blocks of requests).
BLOCK_COUNTS = ("block_passes", "row_passes", "blocks_committed",
                "tokens_committed", "positions_unmasked", "blocks_fused")

# Why a decode burst (dense, or a block family's) did not leave before its
# predecessor was emitted (scheduler._ahead_blocker; docs/scheduling.md), a
# closed set: a request
# waits for admission; a slot is prefilling; a park / drain / flush / stop
# request, a coordinator or split mode; a row's next mask comes from a host
# FSM; a row has a drafter; the free list could not cover the pages; the
# burst had no predecessor to leave ahead of.
AHEAD_BLOCKERS = ("admission", "prefilling", "control", "constraint",
                  "draft", "pages", "first")

# The engine's threads by class, for CPU seconds by class (hoststats.py):
# the step loops (one, or split mode's two), the service layer's bridge
# threads (one blocked in `events.get` for every stream in flight) and the
# window prewarm. The HTTP event loop has no name: its handlers say so.
THREAD_CLASSES = {
    "step_loop": ("engine-step-loop", "engine-prefill-pool",
                  "engine-decode-pool"),
    "http_loop": (),
    "event_bridge": ("engine-events",),
    "prewarm": ("engine-prewarm",),
}

KV_FALLBACK_REASONS = ("disabled", "absent", "version", "dtype",
                       "page_size", "geometry", "capacity", "error")


class Histogram:
    def __init__(self, buckets: tuple[float, ...]):
        self.edges = tuple(buckets)
        self.counts = [0] * (len(buckets) + 1)  # last = +Inf
        self.total = 0.0
        self.n = 0
        self.max = 0.0

    def observe(self, value: float) -> None:
        for i, edge in enumerate(self.edges):
            if value <= edge:
                self.counts[i] += 1
                break
        else:
            self.counts[-1] += 1
        self.total += value
        self.n += 1
        if value > self.max:
            self.max = value

    def percentile(self, pct: float) -> float | None:
        """Approximate percentile, linearly interpolated within the landing
        bucket (None if empty). The bucket's mass is assumed uniform between
        its lower and upper edge (lower edge 0 for the first bucket), so a
        sample entirely below the first edge no longer reports the full edge.
        Percentiles above the top edge report the max observed value — a
        finite, JSON-safe figure (`inf` would serialize as the non-standard
        `Infinity` token and break strict parsers of /api/health)."""
        if self.n == 0:
            return None
        target = self.n * pct / 100.0
        seen = 0
        lower = 0.0
        for i, edge in enumerate(self.edges):
            count = self.counts[i]
            if count and seen + count >= target:
                frac = (target - seen) / count
                return lower + frac * (edge - lower)
            seen += count
            lower = edge
        return max(self.edges[-1], self.max)



def _render_histogram(lines: list, name: str, hist: "Histogram",
                      label: str = "") -> None:
    """Append one histogram family in Prometheus exposition form (shared by
    every histogram block in render() — cumulative buckets, +Inf, sum,
    count). `label` is a pre-rendered `k="v"` pair for labeled families."""
    brace = f"{{{label},le=" if label else "{le="
    cumulative = 0
    for i, edge in enumerate(hist.edges):
        cumulative += hist.counts[i]
        lines.append(f'{name}_bucket{brace}"{edge}"}} {cumulative}')
    cumulative += hist.counts[-1]
    lines.append(f'{name}_bucket{brace}"+Inf"}} {cumulative}')
    suffix = f"{{{label}}}" if label else ""
    lines.append(f"{name}_sum{suffix} {hist.total}")
    lines.append(f"{name}_count{suffix} {hist.n}")


class EngineMetrics:
    def __init__(self):
        self._lock = threading.Lock()
        self.requests_total = 0
        self.tokens_total = 0
        self.errors_total = 0
        self.cancelled_total = 0
        self.ttft = Histogram(TTFT_BUCKETS)
        self.itl = Histogram(ITL_BUCKETS)
        # Step-loop phase breakdown: duration of each prefill dispatch and
        # each (burst-amortized) decode step, plus the decode batch occupancy
        # at the last step — the figures every scheduling/perf PR tunes.
        self.prefill_step = Histogram(STEP_BUCKETS)
        self.decode_step = Histogram(STEP_BUCKETS)
        self.batch_occupancy = 0
        # Prefix KV cache (engine/prefix_cache.py): hit/miss per insert,
        # prompt tokens served from cached KV instead of prefill compute,
        # donor-slot insertions/evictions. The pinned-state gauges (entries,
        # slots, HBM bytes) are scraped live from the scheduler at render
        # time — they are state, not events.
        self.prefix_hits_total = 0
        self.prefix_misses_total = 0
        self.prefix_cached_tokens_total = 0
        self.prefix_insertions_total = 0
        self.prefix_inserted_tokens_total = 0
        self.prefix_evictions_total = 0
        # Structured outputs (llmlb_tpu/structured): constrained requests
        # served, decode dispatches that applied a grammar mask, requests
        # that ended without grammar acceptance, schema→mask compile cost,
        # and the compiled-mask LRU cache traffic. The cache-size gauges
        # (entries/bytes) are scraped from the compiler at render time.
        self.structured_requests_total = 0
        self.masked_decode_steps_total = 0
        self.constraint_violations_total = 0
        self.mask_cache_hits_total = 0
        self.mask_cache_misses_total = 0
        self.mask_cache_evictions_total = 0
        self.schema_compile = Histogram(COMPILE_BUCKETS)
        # Speculative decoding (llmlb_tpu/spec): verify dispatches run,
        # draft tokens proposed, drafts accepted by the model, and tokens
        # emitted by verify steps (accepted + 1 per speculating slot).
        # acceptance rate = accepted / drafted; speedup proxy =
        # emitted / verify steps per slot.
        self.spec_verify_steps_total = 0
        self.spec_draft_tokens_total = 0
        self.spec_accepted_tokens_total = 0
        self.spec_emitted_tokens_total = 0
        # Fused decode (docs/fused-decode.md): decode/verify steps served by
        # the single-program path, total device dispatches issued by the
        # decode loop (fused: exactly one per step — the invariant
        # scripts/check_fused_dispatch.py pins), and constrained slots that
        # fell back to single-step legacy decode (grammar-table budget or
        # fused mode off).
        self.fused_decode_steps_total = 0
        self.decode_dispatches_total = 0
        # Decode bursts (scheduler._decode_bursts): how many were
        # dispatched, how many of them left BEFORE their predecessor was
        # emitted (right after its fetch), how many before it was even
        # fetched (queued behind it on the device), and for the others
        # what stood in the way (AHEAD_BLOCKERS). The three add up to the
        # first.
        self.decode_bursts_total = 0
        self.decode_bursts_dispatched_ahead_total = 0
        self.decode_bursts_queued_behind_total = 0
        self.decode_bursts_not_ahead_total = dict.fromkeys(AHEAD_BLOCKERS, 0)
        # Prefill dispatches of every kind (one-shot groups, chunks, the
        # context-parallel pass), and the one-shot groups among them that
        # left BEFORE the burst fetched in front of them was emitted, with
        # their activation and the next burst behind them
        # (scheduler._admit_ahead).
        self.prefill_dispatches_total = 0
        self.prefills_dispatched_ahead_total = 0
        # Admissions that dispatched NO prefill: the arrival's prompt rode
        # the first step of a decode burst (scheduler._admit_riding)
        self.mixed_admissions_total = 0
        # Σ over decode steps of the pages their live rows hold, and of the
        # pages of slots x window: live / window is the share of a
        # window-wide sweep that was live context
        self.decode_kv_pages_live_total = 0
        # How the sampler takes its top 64 of this engine's vocabulary
        # (ops/sampling.selection_plan): static per engine, set once where
        # the engine is made, a reading and not a rate
        self.sampling: dict[str, int] = {}
        # The families' step counters (scheduler._record_step) under the
        # names their records export them by: every scalar one some family
        # computes (zero where this engine's computes none), a total or, of
        # a "max" counter, the largest seen. Beside them the dispatches
        # counted and, per expert layer, how many experts took how many
        # assignments in a step (models/deepseek_v3.LOAD_BUCKETS).
        from llmlb_tpu.models import STEP_COUNTERS  # here: the gateway
        # imports this module for Histogram and stays free of jax

        self._step_counters = {name: c for name, c in STEP_COUNTERS.items()
                               if name != "expert_load_hist"}
        self.step_counter_totals = {
            c.export: 0 for c in self._step_counters.values()}
        self.moe_counted_steps_total = 0
        self.moe_expert_load_hist: list[list[int]] = []
        self.decode_kv_pages_window_total = 0
        # Generation by diffusion over blocks (scheduler._emit_blocks):
        # running totals of the bursts' counts, by the step records' names
        self.block_totals = dict.fromkeys(BLOCK_COUNTS, 0)
        self.constrained_burst_fallback_total = 0
        # Overload protection (docs/scheduling.md): slots parked under
        # slot/page pressure, parked requests re-activated, and requests
        # shed at admission because their deadline had already passed.
        self.preemptions_total = 0
        self.preempt_resumes_total = 0
        self.deadline_shed_total = 0
        # Disaggregated prefill/decode (docs/disaggregation.md): handoffs by
        # kind — in_process (split mode's page-id exchange), emitted (this
        # prefill-role engine handed a stream away), adopted (this
        # decode-role engine replayed and continued one) — plus the time a
        # ready request waited between prefill completion and decode
        # adoption, and the live count of requests stuck in that gap.
        self.handoff_total: dict[str, int] = {
            "in_process": 0, "emitted": 0, "adopted": 0,
        }
        self.handoff_latency = Histogram(STEP_BUCKETS)
        self.handoff_backlog = 0
        # Graceful drain (docs/deployment.md): 1 while the engine refuses
        # new admissions and winds down, plus the decoding slots parked when
        # the drain grace expired (their streams resume on another engine
        # via the gateway's replay path).
        self.drain_state = 0
        self.drain_parked_total = 0
        # KV page shipping (docs/kv-cache.md, docs/disaggregation.md):
        # exports serialized for transport (count/bytes/seconds), restores
        # landed H2D with zero prefill dispatches, and the reason-labeled
        # replay fallbacks — without the reason label, replay and transfer
        # are indistinguishable in /metrics. The label set is closed (code
        # picks from KV_FALLBACK_REASONS), so cardinality is bounded and
        # every series renders from scrape one. The offload-tier gauges
        # scrape live from the tier's info() block at render time.
        self.kv_ship_total = 0
        self.kv_ship_bytes_total = 0
        self.kv_ship_seconds_total = 0.0
        self.kv_restored_total = 0
        self.kv_restored_bytes_total = 0
        self.kv_ship_fallback_total: dict[str, int] = {
            r: 0 for r in KV_FALLBACK_REASONS
        }
        # Multi-LoRA serving (llmlb_tpu/lora, docs/lora.md): adapter
        # hot-loads/evictions (their RATE is the thrash signal the
        # EngineLoraThrash alert pages on), disk→device load latency, and a
        # cardinality-capped per-adapter request counter. The residency
        # gauge (llmlb_engine_lora_loaded) scrapes live from the manager at
        # render time — state, not an event.
        self.lora_loads_total = 0
        self.lora_evictions_total = 0
        self.lora_load = Histogram(COMPILE_BUCKETS)
        self.lora_requests_total: dict[str, int] = {}
        self._LORA_LABEL_CAP = 64
        # LoRA requests that disabled the context-parallel prefill mesh and
        # fell back to chunked prefill (the bgmv delta is not mesh-sharded;
        # docs/lora.md). Rate, not a one-off: sustained growth means long
        # LoRA prompts are paying single-chip prefill latency.
        self.lora_cp_fallback_total = 0
        # Step-phase time breakdown (engine/stepstats.py): one histogram per
        # phase of the step loop, fed once per dispatch, plus the slow-step
        # anomaly counter. Lazily keyed so only phases that occur render.
        from llmlb_tpu.engine.stepstats import PHASES, PREFILL_CUT, WAY_IN

        self.step_phase: dict[str, Histogram] = {
            p: Histogram(PHASE_BUCKETS) for p in PHASES
        }
        self.slow_steps_total = 0
        # The step loops' clocks by loop tag (engine/stepstats.py LoopClock;
        # the scheduler registers each as its loop makes it): where every
        # second of a loop thread went, served as loop_seconds_total.
        self.loop_clocks: dict = {}
        # Programs built (engine/compilelog.py): the ledger is the
        # process's, this engine serves what was built since it was made.
        self._compile_base = compilelog.counters()
        # A token's way out, from the event queue to the socket
        # (engine/streamstats.py; written by the HTTP event loop alone), and
        # the process's collector clock (hoststats.py).
        self.stream = StreamStats()
        self.gc = watch_gc()
        # A request's way in (docs/tracing.md): seconds by stage
        # (stepstats.WAY_IN) summed over the requests whose first token
        # reached the host, and their count; written by the step loop at
        # each first token (record_first_token).
        self.way_in_seconds_total = dict.fromkeys(WAY_IN, 0.0)
        self.way_in_requests_total = 0
        # The stage `prefill` cut by what it waited for
        # (stepstats.PREFILL_CUT), summed over the requests that have a cut
        # (not a prompt that rode a burst, not a restored one), their count
        # and their prefill dispatches.
        self.prefill_cut_seconds_total = dict.fromkeys(PREFILL_CUT, 0.0)
        self.prefill_cut_requests_total = 0
        self.prefill_cut_chunks_total = 0

    # ------------------------------------------------------------ recorders

    def record_first_token(self, ttft_s: float, stages: dict[str, float],
                           cut: dict[str, float] | None = None,
                           chunks: int = 0) -> None:
        """A request's first token reached the host: its time to first
        token, its way in by stage (a stage it never passed is not among
        `stages`) and, where it has one, the cut of its `prefill` stage
        with the prefill dispatches it took."""
        with self._lock:
            self.ttft.observe(ttft_s)
            self.way_in_requests_total += 1
            for stage, seconds in stages.items():
                self.way_in_seconds_total[stage] += seconds
            if cut:
                self.prefill_cut_requests_total += 1
                self.prefill_cut_chunks_total += chunks
                for part, seconds in cut.items():
                    self.prefill_cut_seconds_total[part] += seconds

    def record_itl(self, seconds: float) -> None:
        with self._lock:
            self.itl.observe(seconds)

    def record_emit(self, itl_seconds: float | None) -> None:
        """One locked update for the per-token hot path: a token plus its
        inter-token latency (None for a slot's first emitted token)."""
        with self._lock:
            self.tokens_total += 1
            if itl_seconds is not None:
                self.itl.observe(itl_seconds)

    def record_prefill_step(self, seconds: float) -> None:
        with self._lock:
            self.prefill_step.observe(seconds)

    def record_decode_step(self, seconds: float, active_slots: int) -> None:
        with self._lock:
            self.decode_step.observe(seconds)
            self.batch_occupancy = active_slots

    def set_batch_occupancy(self, active_slots: int) -> None:
        with self._lock:
            self.batch_occupancy = active_slots

    def record_prefix_hit(self, cached_tokens: int) -> None:
        """One cache-hit insert serving `cached_tokens` prompt tokens from
        copied KV rows instead of prefill."""
        with self._lock:
            self.prefix_hits_total += 1
            self.prefix_cached_tokens_total += cached_tokens

    def record_prefix_miss(self) -> None:
        with self._lock:
            self.prefix_misses_total += 1

    def record_prefix_insert(self, tokens: int) -> None:
        with self._lock:
            self.prefix_insertions_total += 1
            self.prefix_inserted_tokens_total += tokens

    def record_prefix_eviction(self) -> None:
        with self._lock:
            self.prefix_evictions_total += 1

    def record_structured_request(self) -> None:
        with self._lock:
            self.structured_requests_total += 1

    def record_masked_decode_step(self) -> None:
        with self._lock:
            self.masked_decode_steps_total += 1

    def record_constraint_violation(self) -> None:
        """A constrained request terminated without grammar acceptance
        (max_tokens/capacity cut it short, or a vocabulary gap forced EOS)."""
        with self._lock:
            self.constraint_violations_total += 1

    def record_schema_compile(self, seconds: float) -> None:
        with self._lock:
            self.schema_compile.observe(seconds)

    def record_mask_cache_hit(self) -> None:
        with self._lock:
            self.mask_cache_hits_total += 1

    def record_mask_cache_miss(self) -> None:
        with self._lock:
            self.mask_cache_misses_total += 1

    def record_mask_cache_eviction(self) -> None:
        with self._lock:
            self.mask_cache_evictions_total += 1

    def record_spec_step(self, drafted: int, accepted: int,
                         emitted: int) -> None:
        """One speculative verify dispatch: `drafted` tokens proposed across
        the batch, `accepted` of them matched by the model's own samples,
        `emitted` tokens delivered (accepted + 1 per speculating slot)."""
        with self._lock:
            self.spec_verify_steps_total += 1
            self.spec_draft_tokens_total += drafted
            self.spec_accepted_tokens_total += accepted
            self.spec_emitted_tokens_total += emitted

    def record_decode_dispatches(self, n: int, fused: bool = False) -> None:
        """Device dispatches issued by one decode-loop step (decode or
        verify kind). `fused` marks steps served by the single-program
        path; legacy steps report their honest multi-dispatch count."""
        with self._lock:
            self.decode_dispatches_total += max(0, int(n))
            if fused:
                self.fused_decode_steps_total += 1

    def record_decode_burst(self, blocked_by: str | None,
                            queued: bool = False) -> None:
        """One decode burst: queued behind its predecessor before that
        one's fetch (`queued`), dispatched ahead of its predecessor's emit
        (`blocked_by` None), or neither, and why not."""
        with self._lock:
            self.decode_bursts_total += 1
            if queued:
                self.decode_bursts_queued_behind_total += 1
            elif blocked_by is None:
                self.decode_bursts_dispatched_ahead_total += 1
            else:
                self.decode_bursts_not_ahead_total[blocked_by] += 1

    def record_prefill_dispatch(self, ahead: bool = False) -> None:
        """One prefill dispatch; `ahead`: it left before the burst in front
        of it was emitted."""
        with self._lock:
            self.prefill_dispatches_total += 1
            if ahead:
                self.prefills_dispatched_ahead_total += 1

    def record_mixed_admission(self) -> None:
        """One arrival admitted inside a decode burst, its prompt in the
        burst's first step: no prefill dispatch, no activation."""
        with self._lock:
            self.mixed_admissions_total += 1

    def record_decode_kv_pages(self, kv_pages_live: int,
                               kv_pages_window: int) -> None:
        """One decode step's page counts (scheduler._kv_pages)."""
        with self._lock:
            self.decode_kv_pages_live_total += kv_pages_live
            self.decode_kv_pages_window_total += kv_pages_window

    def record_block_passes(self, counts: dict) -> None:
        """One burst of block passes (a `decode` record of a block
        family)."""
        with self._lock:
            for name in BLOCK_COUNTS:
                self.block_totals[name] += counts.get(name, 0)

    def record_step_counters(self, counters: dict) -> None:
        """One dispatch's step counters (a burst's are already reduced over
        its steps): totals add, a "max" counter keeps the largest seen."""
        with self._lock:
            self.moe_counted_steps_total += 1
            totals = self.step_counter_totals
            for name, (reduce, export) in self._step_counters.items():
                n = counters.get(name, 0)
                totals[export] = (max(totals[export], n) if reduce == "max"
                                  else totals[export] + n)
            hist = counters.get("expert_load_hist")
            if hist:
                if not self.moe_expert_load_hist:
                    self.moe_expert_load_hist = [[0] * len(row)
                                                 for row in hist]
                for total, row in zip(self.moe_expert_load_hist, hist):
                    for i, n in enumerate(row):
                        total[i] += n

    def record_constrained_burst_fallback(self) -> None:
        """A constrained slot forced the decode loop off the fused/burst
        path into single-step legacy decode this step."""
        with self._lock:
            self.constrained_burst_fallback_total += 1

    def record_step_phases(self, phases: dict[str, float],
                           slow: bool = False) -> None:
        """One locked update per step: every phase duration plus the
        anomaly flag. Skipping zero-duration phases keeps absent phases
        (e.g. fetch on a prefill record) out of the histograms."""
        with self._lock:
            for name, seconds in phases.items():
                hist = self.step_phase.get(name)
                if hist is not None and seconds > 0.0:
                    hist.observe(seconds)
            if slow:
                self.slow_steps_total += 1

    def record_preemption(self) -> None:
        with self._lock:
            self.preemptions_total += 1

    def record_resume(self) -> None:
        with self._lock:
            self.preempt_resumes_total += 1

    def record_deadline_shed(self) -> None:
        with self._lock:
            self.deadline_shed_total += 1

    def record_handoff(self, kind: str, latency_s: float | None = None) -> None:
        """One prefill→decode handoff. `kind` is in_process / emitted /
        adopted; `latency_s` is the prefill-complete→decode-adoption gap
        (absent for 'emitted' — the prefill side cannot see adoption)."""
        with self._lock:
            if kind in self.handoff_total:
                self.handoff_total[kind] += 1
            if latency_s is not None and latency_s >= 0.0:
                self.handoff_latency.observe(latency_s)

    def set_handoff_backlog(self, n: int) -> None:
        with self._lock:
            self.handoff_backlog = n

    def record_lora_load(self, seconds: float) -> None:
        with self._lock:
            self.lora_loads_total += 1
            self.lora_load.observe(seconds)

    def record_lora_eviction(self) -> None:
        with self._lock:
            self.lora_evictions_total += 1

    def record_lora_cp_fallback(self) -> None:
        """A LoRA request's long prompt skipped the context-parallel
        prefill mesh and took chunked prefill instead."""
        with self._lock:
            self.lora_cp_fallback_total += 1

    def record_lora_request(self, adapter: str) -> None:
        """Per-adapter request counter (docs/lora.md). Label cardinality is
        bounded: past _LORA_LABEL_CAP distinct adapters, further names fold
        into the "_other" label instead of growing /metrics without bound."""
        with self._lock:
            if (adapter not in self.lora_requests_total
                    and len(self.lora_requests_total) >= self._LORA_LABEL_CAP):
                adapter = "_other"
            self.lora_requests_total[adapter] = (
                self.lora_requests_total.get(adapter, 0) + 1
            )

    def set_drain_state(self, state: int) -> None:
        with self._lock:
            self.drain_state = int(state)

    def record_kv_ship(self, nbytes: int, seconds: float) -> None:
        """One KV page payload serialized D2H for transport (handoff
        export, resume export, or an offload-tier spill)."""
        with self._lock:
            self.kv_ship_total += 1
            self.kv_ship_bytes_total += max(0, int(nbytes))
            self.kv_ship_seconds_total += max(0.0, float(seconds))

    def record_kv_restore(self, nbytes: int) -> None:
        """One serialized payload landed H2D into the page pool — a state
        movement that dispatched zero prefill work."""
        with self._lock:
            self.kv_restored_total += 1
            self.kv_restored_bytes_total += max(0, int(nbytes))

    def record_kv_ship_fallback(self, reason: str) -> None:
        """A movement path replayed instead of transferring pages. Unknown
        reasons fold into "error" so the label set stays closed."""
        with self._lock:
            if reason not in self.kv_ship_fallback_total:
                reason = "error"
            self.kv_ship_fallback_total[reason] += 1

    def record_drain_park(self) -> None:
        with self._lock:
            self.drain_parked_total += 1

    def record_request_done(self, finish: str) -> None:
        with self._lock:
            self.requests_total += 1
            if finish == "cancelled":
                self.cancelled_total += 1
            elif finish == "error":
                self.errors_total += 1

    # ----------------------------------------------------------- exposition

    def loop_seconds(self) -> dict[str, dict[str, float]]:
        """Cumulative seconds per loop tag and bucket (step, admit,
        control, record, idle, other): they sum to the loop thread's life."""
        return {tag: {b: round(v, 6) for b, v in clock.snapshot().items()}
                for tag, clock in list(self.loop_clocks.items())}

    def compile_info(self, builds: int = 0) -> dict:
        """Programs built since this engine was made: totals by stage and
        by thread class, and the newest `builds` builds by name."""
        out = compilelog.summary(self._compile_base)
        if builds:
            out["builds"] = compilelog.recent(builds, self._compile_base)
        return out

    def host_info(self, current: str | None = None) -> dict:
        """What is read only when somebody asks: the stream path's
        counters, the collector's, and CPU seconds by thread class
        (`current` is the caller's class: the HTTP handlers say
        "http_loop")."""
        return {"stream": self.stream.snapshot(),
                "gc": self.gc.snapshot(),
                "cpu_seconds_total": cpu_seconds(THREAD_CLASSES, current)}

    def summary(self, current: str | None = None) -> dict:
        """Compact JSON figures for /api/health consumers (the gateway's
        scheduler and dashboard)."""
        loop_seconds = self.loop_seconds()
        compiled = self.compile_info()
        host = self.host_info(current)
        with self._lock:
            return {
                "loop_seconds_total": loop_seconds,
                "compile": compiled,
                **host,
                "way_in": {
                    "requests_total": self.way_in_requests_total,
                    "seconds_total": {
                        stage: round(v, 6)
                        for stage, v in self.way_in_seconds_total.items()},
                    "prefill_cut_requests_total":
                        self.prefill_cut_requests_total,
                    "prefill_cut_chunks_total": self.prefill_cut_chunks_total,
                    "prefill_cut_seconds_total": {
                        part: round(v, 6) for part, v
                        in self.prefill_cut_seconds_total.items()}},
                "requests_total": self.requests_total,
                "tokens_total": self.tokens_total,
                "errors_total": self.errors_total,
                "cancelled_total": self.cancelled_total,
                "ttft_p50_s": self.ttft.percentile(50),
                "ttft_p99_s": self.ttft.percentile(99),
                "itl_p50_s": self.itl.percentile(50),
                "itl_p99_s": self.itl.percentile(99),
                "prefix_hits_total": self.prefix_hits_total,
                "prefix_misses_total": self.prefix_misses_total,
                "prefix_cached_tokens_total": self.prefix_cached_tokens_total,
                "prefix_evictions_total": self.prefix_evictions_total,
                "structured_requests_total": self.structured_requests_total,
                "constraint_violations_total":
                    self.constraint_violations_total,
                "schema_compile_p50_s": self.schema_compile.percentile(50),
                "spec_verify_steps_total": self.spec_verify_steps_total,
                "spec_draft_tokens_total": self.spec_draft_tokens_total,
                "spec_accepted_tokens_total": self.spec_accepted_tokens_total,
                "spec_acceptance_rate": (
                    round(self.spec_accepted_tokens_total
                          / self.spec_draft_tokens_total, 4)
                    if self.spec_draft_tokens_total else None
                ),
                "fused_decode_steps_total": self.fused_decode_steps_total,
                "decode_dispatches_total": self.decode_dispatches_total,
                "decode_bursts_total": self.decode_bursts_total,
                "decode_bursts_dispatched_ahead_total":
                    self.decode_bursts_dispatched_ahead_total,
                "decode_bursts_queued_behind_total":
                    self.decode_bursts_queued_behind_total,
                "decode_bursts_not_ahead_total":
                    dict(self.decode_bursts_not_ahead_total),
                "prefill_dispatches_total": self.prefill_dispatches_total,
                "prefills_dispatched_ahead_total":
                    self.prefills_dispatched_ahead_total,
                "mixed_admissions_total": self.mixed_admissions_total,
                "decode_kv_pages_live_total": self.decode_kv_pages_live_total,
                "decode_kv_pages_window_total":
                    self.decode_kv_pages_window_total,
                "constrained_burst_fallback_total":
                    self.constrained_burst_fallback_total,
                "sampling": dict(self.sampling),
                **{f"{name}_total": n
                   for name, n in self.block_totals.items()},
                "moe_counted_steps_total": self.moe_counted_steps_total,
                **self.step_counter_totals,
                "moe_expert_load_hist": [list(row) for row in
                                         self.moe_expert_load_hist],
                "preemptions_total": self.preemptions_total,
                "preempt_resumes_total": self.preempt_resumes_total,
                "deadline_shed_total": self.deadline_shed_total,
                "handoff_total": dict(self.handoff_total),
                "handoff_backlog": self.handoff_backlog,
                "handoff_latency_p50_s": self.handoff_latency.percentile(50),
                "drain_state": self.drain_state,
                "drain_parked_total": self.drain_parked_total,
                "kv_ship_total": self.kv_ship_total,
                "kv_ship_bytes_total": self.kv_ship_bytes_total,
                "kv_restored_total": self.kv_restored_total,
                "kv_ship_fallback_total": dict(self.kv_ship_fallback_total),
                "lora_loads_total": self.lora_loads_total,
                "lora_evictions_total": self.lora_evictions_total,
            }

    def render(self, *, queue_depth: int, active_slots: int,
               num_slots: int, prefix_cache: dict | None = None,
               kv_cache: dict | None = None,
               structured: dict | None = None,
               perf: dict | None = None,
               quant: dict | None = None,
               sched: dict | None = None,
               lora: dict | None = None,
               flightrec: dict | None = None,
               kv_offload: dict | None = None,
               current: str | None = None) -> str:
        """Prometheus text exposition format. `prefix_cache` is the
        scheduler's prefix_cache_info() block (pinned-state gauges live
        there; the event counters live here); `kv_cache` is its
        kv_cache_info() block — page-pool gauges render when the paged
        layout is active; `structured` is the constraint compiler's info()
        block (mask-cache size gauges); `perf` is its perf_info() block —
        MFU / HBM-bandwidth gauges render when the chip is in the peak-spec
        table and decode traffic has flowed; `quant` is its quant_info()
        block (active int8 mode + honest byte footprints); `flightrec` is
        the flight recorder's counters() block (docs/tracing.md) — the
        queue/service seconds pair feeds the Grafana queue-vs-compute
        panel; `current` is the calling thread's class for the CPU seconds
        (host_info)."""
        with self._lock:
            lines = [
                "# TYPE llmlb_engine_requests_total counter",
                f"llmlb_engine_requests_total {self.requests_total}",
                "# TYPE llmlb_engine_tokens_total counter",
                f"llmlb_engine_tokens_total {self.tokens_total}",
                "# TYPE llmlb_engine_errors_total counter",
                f"llmlb_engine_errors_total {self.errors_total}",
                "# TYPE llmlb_engine_cancelled_total counter",
                f"llmlb_engine_cancelled_total {self.cancelled_total}",
                "# TYPE llmlb_engine_queue_depth gauge",
                f"llmlb_engine_queue_depth {queue_depth}",
                "# TYPE llmlb_engine_active_slots gauge",
                f"llmlb_engine_active_slots {active_slots}",
                "# TYPE llmlb_engine_num_slots gauge",
                f"llmlb_engine_num_slots {num_slots}",
                "# TYPE llmlb_engine_batch_occupancy gauge",
                f"llmlb_engine_batch_occupancy {self.batch_occupancy}",
                "# TYPE llmlb_engine_prefix_cache_hits_total counter",
                f"llmlb_engine_prefix_cache_hits_total {self.prefix_hits_total}",
                "# TYPE llmlb_engine_prefix_cache_misses_total counter",
                "llmlb_engine_prefix_cache_misses_total "
                f"{self.prefix_misses_total}",
                "# TYPE llmlb_engine_prefix_cache_cached_tokens_total counter",
                "llmlb_engine_prefix_cache_cached_tokens_total "
                f"{self.prefix_cached_tokens_total}",
                "# TYPE llmlb_engine_prefix_cache_insertions_total counter",
                "llmlb_engine_prefix_cache_insertions_total "
                f"{self.prefix_insertions_total}",
                "# TYPE llmlb_engine_prefix_cache_inserted_tokens_total "
                "counter",
                "llmlb_engine_prefix_cache_inserted_tokens_total "
                f"{self.prefix_inserted_tokens_total}",
                "# TYPE llmlb_engine_prefix_cache_evictions_total counter",
                "llmlb_engine_prefix_cache_evictions_total "
                f"{self.prefix_evictions_total}",
                "# TYPE llmlb_engine_structured_requests_total counter",
                "llmlb_engine_structured_requests_total "
                f"{self.structured_requests_total}",
                "# TYPE llmlb_engine_masked_decode_steps_total counter",
                "llmlb_engine_masked_decode_steps_total "
                f"{self.masked_decode_steps_total}",
                "# TYPE llmlb_engine_constraint_violations_total counter",
                "llmlb_engine_constraint_violations_total "
                f"{self.constraint_violations_total}",
                "# TYPE llmlb_engine_mask_cache_hits_total counter",
                f"llmlb_engine_mask_cache_hits_total {self.mask_cache_hits_total}",
                "# TYPE llmlb_engine_mask_cache_misses_total counter",
                "llmlb_engine_mask_cache_misses_total "
                f"{self.mask_cache_misses_total}",
                "# TYPE llmlb_engine_mask_cache_evictions_total counter",
                "llmlb_engine_mask_cache_evictions_total "
                f"{self.mask_cache_evictions_total}",
                "# TYPE llmlb_engine_slow_steps_total counter",
                f"llmlb_engine_slow_steps_total {self.slow_steps_total}",
                "# TYPE llmlb_engine_spec_verify_steps_total counter",
                "llmlb_engine_spec_verify_steps_total "
                f"{self.spec_verify_steps_total}",
                "# TYPE llmlb_engine_spec_draft_tokens_total counter",
                "llmlb_engine_spec_draft_tokens_total "
                f"{self.spec_draft_tokens_total}",
                "# TYPE llmlb_engine_spec_accepted_tokens_total counter",
                "llmlb_engine_spec_accepted_tokens_total "
                f"{self.spec_accepted_tokens_total}",
                "# TYPE llmlb_engine_spec_emitted_tokens_total counter",
                "llmlb_engine_spec_emitted_tokens_total "
                f"{self.spec_emitted_tokens_total}",
                "# TYPE llmlb_engine_fused_decode_steps_total counter",
                "llmlb_engine_fused_decode_steps_total "
                f"{self.fused_decode_steps_total}",
                "# TYPE llmlb_engine_decode_dispatches_total counter",
                "llmlb_engine_decode_dispatches_total "
                f"{self.decode_dispatches_total}",
                "# TYPE llmlb_engine_decode_bursts_total counter",
                f"llmlb_engine_decode_bursts_total {self.decode_bursts_total}",
                "# TYPE llmlb_engine_decode_bursts_dispatched_ahead_total "
                "counter",
                "llmlb_engine_decode_bursts_dispatched_ahead_total "
                f"{self.decode_bursts_dispatched_ahead_total}",
                "# TYPE llmlb_engine_decode_bursts_queued_behind_total "
                "counter",
                "llmlb_engine_decode_bursts_queued_behind_total "
                f"{self.decode_bursts_queued_behind_total}",
                "# TYPE llmlb_engine_decode_bursts_not_ahead_total counter",
                *(f'llmlb_engine_decode_bursts_not_ahead_total'
                  f'{{reason="{reason}"}} {n}' for reason, n
                  in self.decode_bursts_not_ahead_total.items()),
                "# TYPE llmlb_engine_prefill_dispatches_total counter",
                "llmlb_engine_prefill_dispatches_total "
                f"{self.prefill_dispatches_total}",
                "# TYPE llmlb_engine_prefills_dispatched_ahead_total counter",
                "llmlb_engine_prefills_dispatched_ahead_total "
                f"{self.prefills_dispatched_ahead_total}",
                "# TYPE llmlb_engine_mixed_admissions_total counter",
                "llmlb_engine_mixed_admissions_total "
                f"{self.mixed_admissions_total}",
                "# TYPE llmlb_engine_decode_kv_pages_live_total counter",
                "llmlb_engine_decode_kv_pages_live_total "
                f"{self.decode_kv_pages_live_total}",
                "# TYPE llmlb_engine_decode_kv_pages_window_total counter",
                "llmlb_engine_decode_kv_pages_window_total "
                f"{self.decode_kv_pages_window_total}",
                *(line for name, n in self.block_totals.items()
                  for line in (
                      f"# TYPE llmlb_engine_{name}_total counter",
                      f"llmlb_engine_{name}_total {n}")),
                "# TYPE llmlb_engine_moe_counted_steps_total counter",
                "llmlb_engine_moe_counted_steps_total "
                f"{self.moe_counted_steps_total}",
                # the totals first, then the largest-seen gauges
                *(line for kind, gauge in (("counter", False),
                                           ("gauge", True))
                  for reduce, export in self._step_counters.values()
                  if (reduce == "max") == gauge
                  for line in (
                      f"# TYPE llmlb_engine_{export} {kind}",
                      f"llmlb_engine_{export} "
                      f"{self.step_counter_totals[export]}")),
                "# TYPE llmlb_engine_moe_expert_load_experts_total counter",
                *(f'llmlb_engine_moe_expert_load_experts_total{{layer="{l}",'
                  f'bucket="{b}"}} {n}'
                  for l, row in enumerate(self.moe_expert_load_hist)
                  for b, n in enumerate(row)),
                "# TYPE llmlb_engine_constrained_burst_fallback_total "
                "counter",
                "llmlb_engine_constrained_burst_fallback_total "
                f"{self.constrained_burst_fallback_total}",
                "# TYPE llmlb_engine_preemptions_total counter",
                f"llmlb_engine_preemptions_total {self.preemptions_total}",
                "# TYPE llmlb_engine_preempt_resumes_total counter",
                "llmlb_engine_preempt_resumes_total "
                f"{self.preempt_resumes_total}",
                "# TYPE llmlb_engine_deadline_shed_total counter",
                f"llmlb_engine_deadline_shed_total {self.deadline_shed_total}",
                "# TYPE llmlb_engine_handoff_total counter",
            ]
            for kind in ("in_process", "emitted", "adopted"):
                lines.append(
                    f'llmlb_engine_handoff_total{{kind="{kind}"}} '
                    f"{self.handoff_total[kind]}"
                )
            lines += [
                "# TYPE llmlb_engine_handoff_backlog gauge",
                f"llmlb_engine_handoff_backlog {self.handoff_backlog}",
                "# TYPE llmlb_engine_drain_state gauge",
                f"llmlb_engine_drain_state {self.drain_state}",
                "# TYPE llmlb_engine_drain_parked_total counter",
                f"llmlb_engine_drain_parked_total {self.drain_parked_total}",
                "# TYPE llmlb_engine_kv_ship_total counter",
                f"llmlb_engine_kv_ship_total {self.kv_ship_total}",
                "# TYPE llmlb_engine_kv_ship_bytes_total counter",
                f"llmlb_engine_kv_ship_bytes_total {self.kv_ship_bytes_total}",
                "# TYPE llmlb_engine_kv_ship_seconds_total counter",
                "llmlb_engine_kv_ship_seconds_total "
                f"{self.kv_ship_seconds_total}",
                "# TYPE llmlb_engine_kv_restored_total counter",
                f"llmlb_engine_kv_restored_total {self.kv_restored_total}",
                "# TYPE llmlb_engine_kv_restored_bytes_total counter",
                "llmlb_engine_kv_restored_bytes_total "
                f"{self.kv_restored_bytes_total}",
                "# TYPE llmlb_engine_kv_ship_fallback_total counter",
            ]
            for reason in KV_FALLBACK_REASONS:
                lines.append(
                    f'llmlb_engine_kv_ship_fallback_total{{reason="{reason}"}}'
                    f" {self.kv_ship_fallback_total[reason]}"
                )
            if kv_offload is not None and kv_offload.get("enabled"):
                lines += [
                    "# TYPE llmlb_engine_kv_offload_budget_bytes gauge",
                    "llmlb_engine_kv_offload_budget_bytes "
                    f"{kv_offload.get('budget_bytes', 0)}",
                    "# TYPE llmlb_engine_kv_offload_bytes gauge",
                    f"llmlb_engine_kv_offload_bytes {kv_offload.get('bytes', 0)}",
                    "# TYPE llmlb_engine_kv_offload_entries gauge",
                    "llmlb_engine_kv_offload_entries "
                    f"{kv_offload.get('entries', 0)}",
                    "# TYPE llmlb_engine_kv_offload_hits_total counter",
                    f"llmlb_engine_kv_offload_hits_total {kv_offload.get('hits', 0)}",
                    "# TYPE llmlb_engine_kv_offload_misses_total counter",
                    "llmlb_engine_kv_offload_misses_total "
                    f"{kv_offload.get('misses', 0)}",
                    "# TYPE llmlb_engine_kv_offload_spills_total counter",
                    "llmlb_engine_kv_offload_spills_total "
                    f"{kv_offload.get('spills', 0)}",
                    "# TYPE llmlb_engine_kv_offload_evictions_total counter",
                    "llmlb_engine_kv_offload_evictions_total "
                    f"{kv_offload.get('evictions', 0)}",
                    "# TYPE llmlb_engine_kv_offload_spilled_bytes_total counter",
                    "llmlb_engine_kv_offload_spilled_bytes_total "
                    f"{kv_offload.get('spilled_bytes', 0)}",
                    "# TYPE llmlb_engine_kv_offload_restored_bytes_total "
                    "counter",
                    "llmlb_engine_kv_offload_restored_bytes_total "
                    f"{kv_offload.get('restored_bytes', 0)}",
                ]
            if sched is not None:
                lines.append(
                    "# TYPE llmlb_engine_queue_depth_class gauge"
                )
                for name, depth in sorted(
                    (sched.get("queued_by_class") or {}).items()
                ):
                    lines.append(
                        f'llmlb_engine_queue_depth_class'
                        f'{{priority="{name}"}} {depth}'
                    )
                by_role = sched.get("queued_by_role")
                if by_role:
                    # split-mode engines only: work waiting for a prefill
                    # slot vs prefilled work waiting for decode adoption
                    lines.append(
                        "# TYPE llmlb_engine_queue_depth_role gauge"
                    )
                    for name, depth in sorted(by_role.items()):
                        lines.append(
                            f'llmlb_engine_queue_depth_role'
                            f'{{role="{name}"}} {depth}'
                        )
            if lora is not None and lora.get("enabled"):
                # Multi-LoRA serving (docs/lora.md): residency gauges scrape
                # the manager's live state; load/evict counters and the
                # per-adapter request counter are event-sourced above.
                lines += [
                    "# TYPE llmlb_engine_lora_loaded gauge",
                    "llmlb_engine_lora_loaded "
                    f"{len(lora.get('resident') or ())}",
                    "# TYPE llmlb_engine_lora_available gauge",
                    "llmlb_engine_lora_available "
                    f"{len(lora.get('available') or ())}",
                    "# TYPE llmlb_engine_lora_max_adapters gauge",
                    "llmlb_engine_lora_max_adapters "
                    f"{lora.get('max_adapters', 0)}",
                    "# TYPE llmlb_engine_lora_loads_total counter",
                    f"llmlb_engine_lora_loads_total {self.lora_loads_total}",
                    "# TYPE llmlb_engine_lora_evictions_total counter",
                    "llmlb_engine_lora_evictions_total "
                    f"{self.lora_evictions_total}",
                    "# TYPE llmlb_engine_lora_cp_fallback_total counter",
                    "llmlb_engine_lora_cp_fallback_total "
                    f"{self.lora_cp_fallback_total}",
                ]
                if self.lora_requests_total:
                    lines.append(
                        "# TYPE llmlb_engine_lora_requests_total counter"
                    )
                    for name_, count in sorted(
                        self.lora_requests_total.items()
                    ):
                        lines.append(
                            'llmlb_engine_lora_requests_total'
                            f'{{adapter="{name_}"}} {count}'
                        )
                hname = "llmlb_engine_lora_load_seconds"
                lines.append(f"# TYPE {hname} histogram")
                _render_histogram(lines, hname, self.lora_load)
            if flightrec is not None and flightrec.get("enabled"):
                lines += [
                    "# TYPE llmlb_engine_flightrec_events_total counter",
                    "llmlb_engine_flightrec_events_total "
                    f"{flightrec.get('events_total', 0)}",
                    "# TYPE llmlb_engine_flightrec_events_dropped_total "
                    "counter",
                    "llmlb_engine_flightrec_events_dropped_total "
                    f"{flightrec.get('events_dropped_total', 0)}",
                    "# TYPE llmlb_engine_flightrec_requests_tracked gauge",
                    "llmlb_engine_flightrec_requests_tracked "
                    f"{flightrec.get('requests_tracked', 0)}",
                    "# TYPE llmlb_engine_flightrec_queue_seconds_total "
                    "counter",
                    "llmlb_engine_flightrec_queue_seconds_total "
                    f"{flightrec.get('queue_seconds_total', 0.0)}",
                    "# TYPE llmlb_engine_flightrec_service_seconds_total "
                    "counter",
                    "llmlb_engine_flightrec_service_seconds_total "
                    f"{flightrec.get('service_seconds_total', 0.0)}",
                ]
            if perf is not None and perf.get("available"):
                lines += [
                    "# TYPE llmlb_engine_mfu_ratio gauge",
                    f"llmlb_engine_mfu_ratio {perf['mfu']}",
                    "# TYPE llmlb_engine_hbm_bw_utilization_ratio gauge",
                    "llmlb_engine_hbm_bw_utilization_ratio "
                    f"{perf['hbm_bw_utilization']}",
                    "# TYPE llmlb_engine_model_flops_per_token gauge",
                    "llmlb_engine_model_flops_per_token "
                    f"{perf['flops_per_token']}",
                    "# TYPE llmlb_engine_model_bytes_per_token gauge",
                    "llmlb_engine_model_bytes_per_token "
                    f"{perf['bytes_per_token']}",
                ]
            if structured is not None and structured.get("enabled"):
                lines += [
                    "# TYPE llmlb_engine_mask_cache_entries gauge",
                    "llmlb_engine_mask_cache_entries "
                    f"{structured['mask_cache_entries']}",
                    "# TYPE llmlb_engine_mask_cache_bytes gauge",
                    "llmlb_engine_mask_cache_bytes "
                    f"{structured['mask_cache_bytes']}",
                ]
            if prefix_cache is not None and prefix_cache.get("enabled"):
                lines += [
                    "# TYPE llmlb_engine_prefix_cache_entries gauge",
                    "llmlb_engine_prefix_cache_entries "
                    f"{prefix_cache['entries']}",
                    "# TYPE llmlb_engine_prefix_cache_pinned_hbm_bytes gauge",
                    "llmlb_engine_prefix_cache_pinned_hbm_bytes "
                    f"{prefix_cache['pinned_hbm_bytes']}",
                    "# TYPE llmlb_engine_prefix_cache_pinned_pages gauge",
                    "llmlb_engine_prefix_cache_pinned_pages "
                    f"{prefix_cache['pinned_pages']}",
                ]
            if quant is not None:
                # info-style gauge: one series per mode, active one = 1, so
                # dashboards can legend the running quantization mode
                lines.append("# TYPE llmlb_engine_quant_mode gauge")
                for mode in ("off", "weights", "kv", "all"):
                    lines.append(
                        f'llmlb_engine_quant_mode{{mode="{mode}"}} '
                        f'{1 if quant.get("mode") == mode else 0}'
                    )
                lines += [
                    "# TYPE llmlb_engine_param_bytes gauge",
                    f"llmlb_engine_param_bytes {quant.get('param_bytes', 0)}",
                    "# TYPE llmlb_engine_state_bytes gauge",
                    f"llmlb_engine_state_bytes {quant.get('state_bytes', 0)}",
                ]
            if kv_cache is not None:
                # honest-dtype KV footprint, so capacity dashboards never
                # fall back to implied-bf16 math
                lines += [
                    "# TYPE llmlb_engine_kv_hbm_bytes gauge",
                    f"llmlb_engine_kv_hbm_bytes {kv_cache.get('hbm_bytes', 0)}",
                ]
            if kv_cache is not None and kv_cache.get("layout") == "paged":
                lines += [
                    "# TYPE llmlb_engine_kv_bytes_per_page gauge",
                    "llmlb_engine_kv_bytes_per_page "
                    f"{kv_cache.get('bytes_per_page', 0)}",
                    "# TYPE llmlb_engine_kv_bytes_per_token gauge",
                    "llmlb_engine_kv_bytes_per_token "
                    f"{kv_cache.get('bytes_per_token', 0)}",
                    "# TYPE llmlb_engine_kv_pages_total gauge",
                    f"llmlb_engine_kv_pages_total {kv_cache['pages_total']}",
                    "# TYPE llmlb_engine_kv_pages_free gauge",
                    f"llmlb_engine_kv_pages_free {kv_cache['pages_free']}",
                    "# TYPE llmlb_engine_kv_pages_active gauge",
                    f"llmlb_engine_kv_pages_active {kv_cache['pages_active']}",
                    "# TYPE llmlb_engine_kv_pages_pinned gauge",
                    f"llmlb_engine_kv_pages_pinned {kv_cache['pages_pinned']}",
                    "# TYPE llmlb_engine_kv_page_size_tokens gauge",
                    f"llmlb_engine_kv_page_size_tokens {kv_cache['page_size']}",
                    "# TYPE llmlb_engine_kv_pool_utilization_ratio gauge",
                    "llmlb_engine_kv_pool_utilization_ratio "
                    f"{kv_cache['utilization']}",
                    "# TYPE llmlb_engine_kv_page_fragmentation_ratio gauge",
                    "llmlb_engine_kv_page_fragmentation_ratio "
                    f"{kv_cache['fragmentation']}",
                    "# TYPE llmlb_engine_kv_page_waste_tokens_mean gauge",
                    "llmlb_engine_kv_page_waste_tokens_mean "
                    f"{kv_cache['waste_tokens_mean']}",
                ]
            for name, hist in (
                ("llmlb_engine_ttft_seconds", self.ttft),
                ("llmlb_engine_itl_seconds", self.itl),
                ("llmlb_engine_prefill_step_seconds", self.prefill_step),
                ("llmlb_engine_decode_step_seconds", self.decode_step),
                ("llmlb_engine_schema_compile_seconds", self.schema_compile),
                ("llmlb_engine_handoff_latency_seconds",
                 self.handoff_latency),
            ):
                lines.append(f"# TYPE {name} histogram")
                _render_histogram(lines, name, hist)
            # per-phase step breakdown: one histogram family labeled by
            # phase (engine/stepstats.py taxonomy); empty phases still
            # render so dashboards see a complete label set
            name = "llmlb_engine_step_phase_seconds"
            lines.append(f"# TYPE {name} histogram")
            for phase, hist in self.step_phase.items():
                _render_histogram(lines, name, hist, label=f'phase="{phase}"')
            # a request's way in, by stage (docs/tracing.md)
            lines.append("# TYPE llmlb_engine_way_in_requests_total counter")
            lines.append("llmlb_engine_way_in_requests_total "
                         f"{self.way_in_requests_total}")
            lines.append("# TYPE llmlb_engine_way_in_seconds_total counter")
            for stage, seconds in self.way_in_seconds_total.items():
                lines.append(
                    f'llmlb_engine_way_in_seconds_total{{stage="{stage}"}} '
                    f'{round(seconds, 6)}')
            # ... and its `prefill` stage by what it waited for
            for name, n in (("requests", self.prefill_cut_requests_total),
                            ("chunks", self.prefill_cut_chunks_total)):
                lines.append(
                    f"# TYPE llmlb_engine_prefill_cut_{name}_total counter")
                lines.append(f"llmlb_engine_prefill_cut_{name}_total {n}")
            lines.append(
                "# TYPE llmlb_engine_prefill_cut_seconds_total counter")
            for part, seconds in self.prefill_cut_seconds_total.items():
                lines.append(
                    f'llmlb_engine_prefill_cut_seconds_total{{part="{part}"}} '
                    f'{round(seconds, 6)}')
        # where the step loops' time went, and the programs built: read
        # outside the lock (each has its own)
        lines.append("# TYPE llmlb_engine_loop_seconds_total counter")
        for tag, buckets in self.loop_seconds().items():
            for bucket, seconds in buckets.items():
                lines.append(
                    f'llmlb_engine_loop_seconds_total{{loop="{tag}",'
                    f'bucket="{bucket}"}} {seconds}')
        compiled = self.compile_info()
        lines.append("# TYPE llmlb_engine_programs_built_total counter")
        for cls, block in compiled["by_thread"].items():
            lines.append(f'llmlb_engine_programs_built_total{{thread="{cls}"}} '
                         f'{block["programs_total"]}')
        lines.append("# TYPE llmlb_engine_compile_seconds_total counter")
        for stage, seconds in compiled["seconds_total"].items():
            lines.append(
                f'llmlb_engine_compile_seconds_total{{stage="{stage}"}} '
                f'{seconds}')
        # a token's way out, the collector and CPU by thread class
        # (docs/tracing.md): read here, at scrape time, and nowhere else
        host = self.host_info(current)
        for key, value in host["stream"].items():
            kind = "counter" if key.endswith("_total") else "gauge"
            lines.append(f"# TYPE llmlb_engine_stream_{key} {kind}")
            lines.append(f"llmlb_engine_stream_{key} {value}")
        lines.append("# TYPE llmlb_engine_gc_collections_total counter")
        for gen, n in host["gc"]["collections_total"].items():
            lines.append(
                f'llmlb_engine_gc_collections_total{{generation="{gen}"}} {n}')
        lines.append("# TYPE llmlb_engine_gc_seconds_total counter")
        lines.append(
            f'llmlb_engine_gc_seconds_total {host["gc"]["seconds_total"]}')
        lines.append("# TYPE llmlb_engine_cpu_seconds_total counter")
        for cls, seconds in host["cpu_seconds_total"].items():
            lines.append(
                f'llmlb_engine_cpu_seconds_total{{class="{cls}"}} {seconds}')
        return "\n".join(lines) + "\n"
