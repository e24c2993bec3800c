"""Step-loop introspection: one measured host timeline for the step loop.

Answers "where does the loop's time go?" for the engine's serving loop, with
one clock (``time.perf_counter()``) read once at each boundary:

* **A step is a span.** Every scheduler step (prefill dispatch, decode
  dispatch, speculative verify) is opened by ``LoopClock.begin`` and cut
  into named spans by ``StepSpan.mark``; each mark is one clock read, ends
  the span before it and starts the next, so a step has no holes. Each span
  is also a ``jax.profiler.TraceAnnotation`` (an inactive TraceMe unless a
  capture runs), inside one ``llmlb.step`` annotation that carries the
  record's ``seq``: a capture shows the steps on the host plane of the same
  trace as the device ops, joined to ``/api/steps`` by ``seq``.
* **The time between steps is accounted.** The loop switches a bucket
  (``LoopClock.switch``) as it goes: ``admit`` (``_try_insert``),
  ``control`` (lockstep tick, drain/park/flush, split-mode lock waits),
  ``record`` (closing the previous record: histograms, flight-recorder
  emits), ``idle`` (the 1 ms sleep when there is nothing to do) and
  ``other`` (whatever none of these holds). Every record carries the split
  of the time since the previous record of its loop (``since_prev``), and
  the cumulative buckets, ``step`` included, are served as
  ``loop_seconds_total``: over any interval they sum to the interval.
* **Programs built inside a step** are named on its record (``builds``,
  engine/compilelog.py).
* **Work or waiting.** The loop thread's CPU clock is read four times a step
  (``LoopClock.begin``, the marks into and out of ``compute``,
  ``LoopClock.close``): ``host_cpu_s`` is the CPU the thread spent in the
  step outside ``compute`` and ``gap_cpu_s`` what it spent since the
  previous record closed. A step whose host spans take 8 ms of wall time
  and 3 ms of CPU waited 5 ms for the GIL or the kernel's scheduler.
  ``gc_s`` is what the collector took (hoststats.py: it stalls every
  thread) since the previous record closed: a 100 ms gap with ``gc_s`` 0.09
  has its cause.

Span names (``SPANS``), in the order a decode step runs them:

  draft     — speculative drafting: n-gram lookup, FSM lookahead
  host_sync — host→device state refresh before a dispatch: page allocation,
              block-table rows and grammar-mask rows changed since the last
              step, the PRNG split, the choice of the context window
  dispatch  — the jitted step call returning its (async) futures: python +
              jax dispatch overhead, no device time
  compute   — the host WAITING in jax.block_until_ready. Not device
              execution time: the device may have started earlier (async
              dispatch) and idles inside it whenever the host was late; the
              device's own time comes from a profiler trace
  fetch     — device→host token readback (the per-step D2H sync)
  emit      — host-side token delivery: stop checks, grammar FSM advance,
              event-queue puts (detokenization itself runs on the service
              layer's consumer threads, off the step loop)
  activate  — a prefilled group entering decode (_activate_group): filling
              the group's host rows, the dispatch of the one activation
              program (first-token sample and the writes of the per-slot
              state), the first-token fetch where a grammar needs it, and
              the slot bookkeeping
  counters  — a prefill's step counters read back to the host
              (scheduler._prefill_counters: one blocking read an array,
              the last thing of a prefill step served in today's order;
              a burst's counters ride its token fetch and have no span).
              The instrument's own cost on the hot path, under its own
              name: absent where the family has no counters, and where the
              prefill left ahead and is recorded with a burst in flight
              (the reads hide behind the device's work there)

Host work done WITH A PROGRAM OF THIS LOOP ON THE DEVICE, between a
dispatch's return and the wait for it (scheduler._decode_bursts), has span
names of its own (``INFLIGHT_SPANS``), so that the names above keep meaning
"the device has nothing from this loop":

  emit_inflight      — delivery of the burst BEFORE the one in flight and
                       the closing of its record (what `emit` and the
                       `record` bucket hold in the other order); behind a
                       prefill dispatched ahead, the closing of the
                       prefill's record too
  host_sync_inflight — what `host_sync` does, done for the NEXT burst from
                       the lengths its rows will have: page growth from the
                       free list, the block tables, the window, the live rows
  activate_inflight  — what `activate` does, with the group's own prefill
                       still computing: the activation of a prefill that
                       was dispatched ahead (scheduler._admit_ahead)
  dispatch_inflight  — the call of the burst behind such a prefill and its
                       activation, which the device runs before it: the
                       block tables with the new rows, the key split, the
                       call; and the call of a burst QUEUED BEHIND the one
                       in flight, before the wait for that one (the key
                       split, the call): it stands in the record of the
                       burst it is queued behind
  fetch_inflight     — what `fetch` does, with the fetched burst's successor
                       queued behind it and on the device by now

A decode burst runs in one of five orders. Today's: ``host_sync, dispatch,
[host_sync_inflight,] compute, fetch, emit``. Dispatched ahead (it left
right after its predecessor's fetch): ``dispatch, emit_inflight,
[host_sync_inflight,] compute, fetch`` and, where the next burst does not
leave ahead in its turn, ``emit``. Where one does, the record ends at the
stamp the next begins at (``LoopClock.handover``): records never overlap.
Admission ahead (an arrival was placed right after the predecessor's fetch):
the prefill's record is ``dispatch, activate_inflight`` behind a stretch of
``admit``, and the burst's ``dispatch_inflight, emit_inflight,
[host_sync_inflight,] compute, fetch[, emit]``; the three records — the
predecessor's, the prefill's, the burst's — end and begin at one stamp each.
Queued behind (it left BEFORE the wait for its predecessor, whose record
holds its ``dispatch_inflight`` and ends in ``fetch_inflight``): the record
begins at the predecessor's fetch, ``emit_inflight, host_sync_inflight,
compute, fetch[, emit]``, and where the next burst is queued in its turn,
``emit_inflight, host_sync_inflight, dispatch_inflight, compute,
fetch_inflight``: a record with no span in which the device has nothing
from this loop. An arrival rode the burst (its prompt was the burst's first
step's: no prefill record): the spans of a burst that left ahead, behind a
stretch of ``admit``, and ``admitted`` on the record.

The legacy ``phases_s`` keep their meaning: ``plan`` is the admission time
since the previous record (``since_prev.admit_s``), ``emit`` still covers
activation and the ``counters`` span, the in-flight spans count under
``compute`` — which is the interval from the dispatch's return to the
device's completion, whatever the host did in it — and ``total_s`` is
their sum. Records land in a bounded ring
buffer served at the engine's ``/api/steps`` plus per-phase histograms in
``/metrics``. A slow-step anomaly detector keeps an EMA per kind of the time
a step took since the previous one ended (idle sleep left out), and flags
steps that exceed a configurable multiple of it — so a stall outside every
span (a lock, a queue drain, the collector) is flagged too, and the record
names the span or bucket that held the time (``slow_in``).

The recorder is deliberately dumb and allocation-light: one clock read and
one inactive TraceMe per boundary, one dict append per step. The guarantee
(tested in tests/engine/test_step_introspection.py) is < 1% of step time on
the CPU debug engine, whose steps are orders of magnitude shorter than any
real TPU step; on the v5e chip, where a decode burst takes 190 ms, parent
and change read the same to within the spread of the runs (PERF.md §6, PR 24).
"""

from __future__ import annotations

import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from llmlb_tpu import hoststats
from llmlb_tpu.engine import compilelog

# the one clock of every stamp below (a name of its own, so that a test can
# put made-up stamps in its place without touching the process's clock)
_now = time.perf_counter
# the loop thread's own CPU clock: read where a step begins, where it starts
# and stops waiting for the device, and where it ends, never per token
_cpu = time.thread_time

PHASES = ("plan", "draft", "host_sync", "dispatch", "compute", "fetch",
          "emit")
# The closed set of span names a step is cut into (StepSpan.mark).
SPANS = ("draft", "host_sync", "dispatch", "compute", "fetch", "emit",
         "activate", "counters", "host_sync_inflight", "emit_inflight",
         "activate_inflight", "dispatch_inflight", "fetch_inflight")
# Host work with a program of this loop on the device: `compute` in the
# legacy phases.
INFLIGHT_SPANS = ("host_sync_inflight", "emit_inflight", "activate_inflight",
                  "dispatch_inflight", "fetch_inflight")
# Where the loop's time goes between steps, and with "step" all of it.
GAP_BUCKETS = ("admit", "control", "record", "idle", "other")
LOOP_BUCKETS = ("step",) + GAP_BUCKETS

# A request's way in (docs/tracing.md): the stages its time to first token
# is cut into, each named for the wait it holds, in the order a request
# passes them. Stage i runs from stamp i to stamp i + 1 of WAY_IN_STAMPS
# (fields of scheduler.Request, all read from `_now`): `accept` lies before
# `submitted_at`, where time to first token starts, so the other four sum
# to it.
WAY_IN = ("accept", "inbox", "place", "prefill", "first_fetch")
WAY_IN_STAMPS = ("received_at", "submitted_at", "taken_at", "prefill_at",
                 "activated_at", "first_token_at")


# The stage `prefill` of a chunked prompt holds four different waits, each
# with a cure of its own: PREFILL_CUT names them, seconds each, and they sum
# to the stage by construction (LoopClock.close_cut).
#   own     the prompt's own prefill steps (dispatch, compute, emit of each
#           chunk; the last one up to `activated_at`)
#   others  the prefill steps of OTHER prompts inside the stage (the
#           rotation among the prefilling slots)
#   decode  the decode and verify steps between its chunks
#   loop    the loop between steps: every gap bucket (admit, control,
#           record, idle, other)
PREFILL_CUT = ("own", "others", "decode", "loop")


def way_in_stages(request) -> dict[str, float]:
    """Seconds by stage of WAY_IN from a request's stamps. A stage this
    process never ran (one of its two stamps is None: no handler, no
    prefill of a restored request) is absent, not zero."""
    stamps = [getattr(request, name) for name in WAY_IN_STAMPS]
    return {stage: round(end - start, 6)
            for stage, start, end in zip(WAY_IN, stamps, stamps[1:])
            if start is not None and end is not None}


# Step kinds the scheduler dispatches. Each kind keeps its OWN EMA baseline
# in the slow-step detector: a K+1-token speculative verify step is
# legitimately several times a single-token decode step, so folding them
# into one baseline would either flag every verify step or mask genuinely
# slow decodes.
#   prefill — prompt KV fill (one-shot group, chunked extend, or CP pass)
#   decode  — 1-token (or burst-scanned k-token) step, one token/slot/step
#   verify  — speculative K+1-token verification (llmlb_tpu/spec): scores
#             the drafts in one extend-style dispatch; `tokens` on its
#             records counts tokens actually EMITTED (accepted + 1 per
#             slot), not positions scored
KINDS = ("prefill", "decode", "verify")

# EMA smoothing for the per-kind step-time baseline. Small alpha: the
# baseline should drift with load, not chase a single outlier.
_EMA_ALPHA = 0.05
# A step is anomalous when it exceeds max(ratio x EMA, floor). The floor
# keeps microsecond-scale CPU steps from flagging scheduler jitter.
_SLOW_RATIO = 4.0
_SLOW_FLOOR_S = 0.020
# Steps observed before the detector arms (the first steps of a fresh
# engine include XLA compiles and would all flag).
_WARMUP_STEPS = 16


class StepSpan:
    """One open step: its stamps and spans, made by LoopClock.begin and
    handed to StepRecorder.observe once LoopClock.close has stamped its
    end. All times are time.perf_counter() seconds."""

    __slots__ = ("kind", "seq", "loop", "t0", "t1", "spans", "since_prev",
                 "builds", "slow_in", "host_cpu_s", "gap_cpu_s", "gc_s",
                 "_name", "_start", "_ann", "_step_ann", "_resume",
                 "_legacy_spans", "_cpu_mark")

    def __init__(self, loop: str, seq: int, t0: float, first_span: str,
                 since_prev: dict[str, float], resume: str,
                 cpu0: float = 0.0, gap_cpu_s: float = 0.0):
        self.loop = loop
        self.seq = seq
        self.t0 = self._start = t0
        self.spans: list[tuple[str, float, float]] = []
        self.since_prev = since_prev
        self.host_cpu_s = 0.0  # CPU of this thread outside `compute`
        self.gap_cpu_s = gap_cpu_s  # ... since the previous record closed
        self.gc_s = 0.0  # the collector, since the previous record closed
        self._cpu_mark = cpu0
        self.slow_in: str | None = None  # set by StepRecorder.observe
        self._name = first_span
        self._resume = resume
        self._legacy_spans: int | None = None
        self._step_ann = TraceAnnotation("llmlb.step", seq=seq)
        self._step_ann.__enter__()
        self._ann = TraceAnnotation(first_span)
        self._ann.__enter__()

    def mark(self, name: str) -> float:
        """End the running span and start `name` at one clock read, which
        is returned."""
        now = _now()
        if name == "compute":
            if self._name != "compute":
                self.host_cpu_s += _cpu() - self._cpu_mark
        elif self._name == "compute":
            self._cpu_mark = _cpu()
        self.spans.append((self._name, self._start, now - self._start))
        self._ann.__exit__(None, None, None)
        self._name = name
        self._start = now
        self._ann = TraceAnnotation(name)
        self._ann.__enter__()
        return now

    def freeze_phases(self) -> None:
        """Spans from the next mark on appear in `spans` and `wall_s` but
        not in the legacy `phases_s` (the context-parallel prefill, whose
        record used to close before its activation)."""
        self._legacy_spans = len(self.spans) + 1

    def phases(self) -> dict[str, float]:
        """The legacy phase durations of a closed step: spans summed by
        name, `activate` and `counters` counted as `emit`, the in-flight
        spans as `compute`, and the admission time since the previous
        record as `plan`."""
        out = {"plan": self.since_prev["admit"]}
        spans = self.spans
        if self._legacy_spans is not None:
            spans = spans[:self._legacy_spans]
        for name, _start, dur in spans:
            if name in ("activate", "counters"):
                name = "emit"
            elif name in INFLIGHT_SPANS:
                name = "compute"
            out[name] = out.get(name, 0.0) + dur
        return out


class PrefillCut:
    """A request's `prefill` stage while it is open: made where `step`,
    just begun on `clock`, is the request's FIRST prefill dispatch (the
    stage begins at step.t0, up to which the clock's cumulative sums stand:
    begin switched there, so no clock is read), closed by
    LoopClock.close_cut at `activated_at`. It holds the clock that stamps
    it, that clock's sums where the stage began, the walls of the request's
    own prefill steps that are closed, and its newest one. `parts` is the
    closed cut (PREFILL_CUT, seconds), None until then and for a stage that
    two clocks stamped."""

    __slots__ = ("clock", "base", "own_s", "step", "parts")

    def __init__(self, clock: "LoopClock", step: StepSpan):
        self.clock = clock
        self.base = clock.cut_marks()
        self.own_s = 0.0
        self.step = step
        self.parts: dict[str, float] | None = None

    def next_step(self, step: StepSpan) -> None:
        """`step` is the request's next prefill step: the one before it
        is closed by now, and its wall is the request's own."""
        self.own_s += self.step.t1 - self.step.t0
        self.step = step


class LoopClock:
    """The clock of ONE step-loop thread: at every instant the thread is
    either inside a step or in one of the GAP_BUCKETS, and every switch is
    one perf_counter read, so the buckets sum to the thread's lifetime.
    `acc` is cumulative (served as loop_seconds_total); the gap since the
    previous record's end goes onto the next record as `since_prev`."""

    def __init__(self, recorder: "StepRecorder", tag: str):
        self.recorder = recorder
        self.tag = tag
        self.acc = dict.fromkeys(LOOP_BUCKETS, 0.0)
        # acc["step"] by the kind each step was closed as: between two
        # stamps of this clock, elapsed = the three kinds' difference + the
        # gap buckets' + the open step's part (PREFILL_CUT)
        self.step_seconds_by_kind = dict.fromkeys(KINDS, 0.0)
        self._gap = dict.fromkeys(GAP_BUCKETS, 0.0)
        self._bucket = "other"
        self._mark = _now()
        self._step: StepSpan | None = None
        # the thread's CPU clock and the collector's total where the last
        # record closed (this thread makes the clock: _clock())
        self._cpu_closed = _cpu()
        self._gc_closed = hoststats.GC.seconds_total

    def switch(self, bucket: str, now: float | None = None) -> None:
        """The loop moves on to `bucket` (no-op while a step is open: the
        step's own spans hold that time), at `now` if the caller has read
        the clock for it."""
        if self._step is not None:
            return
        if now is None:
            now = _now()
        dt = now - self._mark
        self.acc[self._bucket] += dt
        self._gap[self._bucket] += dt
        self._bucket = bucket
        self._mark = now

    def begin(self, first_span: str, *, after: StepSpan | None = None,
              at: float | None = None) -> StepSpan:
        """Open a step whose first span is `first_span`. Its kind is given
        when it is closed (a decode step may turn into a verify). `after`:
        a step that is closed and not recorded yet (handover; or close and
        a stretch of `admit`, where an arrival is placed ahead): this one
        takes the seq behind it and resumes where it would have. `at`: the
        clock read it begins at, where the caller has one."""
        resume = self._bucket if after is None else after._resume
        self.switch("step", at)
        # steps are serialized (one loop, or split mode's lock), so the
        # record this step will become is the recorder's next, or the one
        # behind `after`'s
        seq = self.recorder.seq + 1 if after is None else after.seq + 1
        cpu = _cpu()
        step = StepSpan(self.tag, seq, self._mark,
                        first_span, self._gap, resume, cpu,
                        cpu - self._cpu_closed)
        self._gap = dict.fromkeys(GAP_BUCKETS, 0.0)
        compilelog.enter_step(step.seq)
        self._step = step
        return step

    def mark(self, name: str) -> None:
        """Start span `name` in the open step, if there is one (for code
        that runs inside some steps and between others: activation)."""
        if self._step is not None:
            self._step.mark(name)

    def _end(self, step: StepSpan, kind: str) -> float:
        now = _now()
        step.spans.append((step._name, step._start, now - step._start))
        step._ann.__exit__(None, None, None)
        step._step_ann.set_metadata(kind=kind)
        step._step_ann.__exit__(None, None, None)
        step.kind = kind
        step.t1 = now
        step.builds = compilelog.leave_step()
        self._step = None
        return now

    def close(self, step: StepSpan, kind: str) -> None:
        """Stamp the step's end. Until resume() the loop is in `record`;
        after it, back in the bucket the step was opened from."""
        now = self._end(step, kind)
        self._cpu_closed = cpu = _cpu()
        if step._name != "compute":
            step.host_cpu_s += cpu - step._cpu_mark
        gc_total = hoststats.GC.seconds_total
        step.gc_s = gc_total - self._gc_closed
        self._gc_closed = gc_total
        self.acc["step"] += now - self._mark
        self.step_seconds_by_kind[kind] += now - self._mark
        self._bucket = "record"
        self._mark = now

    def resume(self, step: StepSpan) -> None:
        self.switch(step._resume)

    def handover(self, step: StepSpan, kind: str,
                 first_span: str) -> StepSpan:
        """Close `step` and open the next one where it ends: a burst that
        leaves before its predecessor is recorded. Nothing runs in `record`
        — the caller hands `step` to the recorder inside the new step, under
        `emit_inflight` — and the new step resumes where `step` would have."""
        self.close(step, kind)
        # the two steps end and begin at ONE clock read: no stretch between
        return self.begin(first_span, after=step, at=step.t1)

    def abandon(self) -> None:
        """Drop the open step, if any, without a record (a step that found
        nothing to dispatch, or one that raised): its time is `other`."""
        step = self._step
        if step is None:
            return
        now = self._end(step, "abandoned")
        self._gap = step.since_prev
        dt = now - self._mark
        self.acc["other"] += dt
        self._gap["other"] += dt
        self._bucket = "other"
        self._mark = now

    def cut_marks(self) -> tuple[float, float, float]:
        """Cumulative seconds of this loop's closed prefill steps, of its
        closed decode and verify steps, and of its gap buckets up to the
        last switch: what a PrefillCut is the difference of."""
        kinds, acc = self.step_seconds_by_kind, self.acc
        return (kinds["prefill"], kinds["decode"] + kinds["verify"],
                sum(acc[b] for b in GAP_BUCKETS))

    def close_cut(self, cut: PrefillCut, now: float) -> None:
        """The stage ends at `now` (`activated_at`, the caller's read):
        `cut.parts` = its seconds by PREFILL_CUT. Steps of one loop never
        overlap and the buckets sum to the thread's lifetime, so the four
        sum to now - the first step's t0. The running stretch is `own`
        where the request's newest prefill step is the open one (today's
        order: the activation is inside it), else that step is closed and
        the stretch belongs to the burst behind it (a prefill that left
        ahead) or, between steps, to the loop. A stage that another
        loop's clock began (split mode's handoff) has no cut."""
        if cut.clock is not self:
            return
        prefill, decode, loop = (
            b - a for a, b in zip(cut.base, self.cut_marks()))
        own, step, running = cut.own_s, cut.step, now - self._mark
        if self._step is step:
            own += running
            prefill += running
        else:
            own += step.t1 - step.t0
            if self._step is None:
                loop += running
            else:
                decode += running
        cut.parts = {"own": round(own, 6), "others": round(prefill - own, 6),
                     "decode": round(decode, 6), "loop": round(loop, 6)}

    def snapshot(self) -> dict[str, float]:
        """The cumulative buckets with the running stretch added to the
        bucket it is in (read from another thread: one stretch may land
        in the neighbouring bucket if the loop switches meanwhile)."""
        out = dict(self.acc)
        out[self._bucket] += max(0.0, _now() - self._mark)
        return out


class StepRecorder:
    """Bounded ring of per-step phase breakdowns + slow-step detection +
    a sliding window of (tokens, busy seconds) for live MFU math.

    Thread-safety: observe() runs on the step loop only; snapshot()/window()
    may run on scrape threads — everything mutable sits behind one lock
    held for microseconds.
    """

    def __init__(self, capacity: int = 512, *, slow_ratio: float = _SLOW_RATIO,
                 slow_floor_s: float = _SLOW_FLOOR_S,
                 window: int = 128):
        self.capacity = max(1, capacity)
        self.slow_ratio = slow_ratio
        self.slow_floor_s = slow_floor_s
        # the one anchor that puts perf_counter stamps on the wall clock
        self._wall_anchor = time.time() - _now()
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self._seq = 0
        self._ema: dict[str, float] = {}  # kind -> EMA of the judged time
        self._seen: dict[str, int] = {}
        self.slow_steps_total = 0
        # sliding window of decode steps for throughput-derived figures
        self._window: deque[tuple[float, int]] = deque(maxlen=max(1, window))

    # -------------------------------------------------------------- recording

    def observe(self, kind: str, phases: dict[str, float], *,
                active_slots: int = 0, tokens: int = 0,
                request_ids: dict[str, str] | None = None,
                dispatches: int = 0, span: StepSpan | None = None,
                extra: dict[str, int] | None = None) -> bool:
        """Record one step; returns True when it was flagged anomalous.
        `phases` maps phase name -> seconds (missing phases count as 0);
        `tokens` is the number of tokens this step delivered to the host
        (decode: burst x active slots); `request_ids` maps slot id ->
        gateway request id for the requests riding this dispatch, so a
        flagged record NAMES its victims (/api/steps?slow=1); `dispatches`
        counts the device programs this step launched (the fused-decode
        invariant — scripts/check_fused_dispatch.py — asserts exactly 1 on
        decode/verify records when LLMLB_FUSED_DECODE is on). `span` is the
        closed StepSpan the phases came from (a flagged one is told where its
        time went: `span.slow_in`); without it (unit tests) the record's
        stamps are rebuilt from the durations, ending now, and the detector
        judges their sum. `extra` is a kind's own counts, put on the record
        as they are (a decode step's `kv_pages_live` and `kv_pages_window`:
        the pages its live rows hold, and the pages of slots x window)."""
        total = sum(phases.values())
        if span is not None:
            t0, t1 = span.t0, span.t1
            gap, spans = span.since_prev, span.spans
            builds, loop = span.builds, span.loop
            cpu = {"host_cpu_s": span.host_cpu_s,
                   "gap_cpu_s": span.gap_cpu_s, "gc_s": span.gc_s}
            # everything since the previous record ended but the idle
            # sleep: a stall between steps is judged like one inside
            judged = (t1 - t0 + gap["admit"] + gap["control"]
                      + gap["record"] + gap["other"])
        else:
            t1 = _now()
            gap = dict.fromkeys(GAP_BUCKETS, 0.0)
            gap["admit"] = phases.get("plan", 0.0)
            at = t0 = t1 - total + gap["admit"]
            spans = []
            for p in PHASES[1:]:
                if phases.get(p):
                    spans.append((p, at, phases[p]))
                    at += phases[p]
            builds, loop = [], "main"
            cpu = {}
            judged = total
        record = {
            "ts": self._wall_anchor + t1,
            "kind": kind,
            "total_s": total,
            "phases_s": {p: phases.get(p, 0.0) for p in PHASES},
            "active_slots": active_slots,
            "tokens": tokens,
            "dispatches": dispatches,
            "request_ids": dict(request_ids) if request_ids else {},
            "slow_in": None,
            "loop": loop,
            "t0_s": t0,
            "t1_s": t1,
            "spans": spans,
            "since_prev": gap,
            "builds": builds,
            **cpu,
            **(extra or {}),
        }
        with self._lock:
            seen = self._seen.get(kind, 0)
            ema = self._ema.get(kind)
            slow = False
            if seen >= _WARMUP_STEPS and ema is not None:
                threshold = max(self.slow_ratio * ema, self.slow_floor_s)
                slow = judged > threshold
                if slow:
                    self.slow_steps_total += 1
                    record["slow_in"] = self._slow_in(record)
                    if span is not None:
                        span.slow_in = record["slow_in"]
            # anomalous steps do not feed the baseline: one 40x step must
            # not drag the EMA up and mask the next one
            if ema is None:
                self._ema[kind] = judged
            elif not slow:
                self._ema[kind] = ema + _EMA_ALPHA * (judged - ema)
            self._seen[kind] = seen + 1
            self._seq += 1
            record["seq"] = self._seq
            record["slow"] = slow
            self._ring.append(record)
            # decode AND verify steps feed the throughput window: both
            # deliver committed tokens, and live MFU must see speculative
            # throughput or it would collapse the moment speculation engages
            if kind in ("decode", "verify") and tokens > 0:
                self._window.append((total, tokens))
        return slow

    @staticmethod
    def _parts(record: dict) -> dict[str, float]:
        """A record's time by span name and by gap bucket (idle left out)."""
        parts = {f"{b}_s": v for b, v in record["since_prev"].items()
                 if b != "idle"}
        for name, _start, dur in record["spans"]:
            parts[name] = parts.get(name, 0.0) + dur
        return parts

    def _slow_in(self, record: dict) -> str:
        """The span or bucket that holds a slow step's time: the part that
        exceeds by most its mean over the last (up to 8) steps of the same
        kind that were not slow. Lock held; runs for flagged steps only."""
        usual: dict[str, float] = {}
        n = 0
        for r in reversed(self._ring):
            if r["kind"] == record["kind"] and not r["slow"]:
                for k, v in self._parts(r).items():
                    usual[k] = usual.get(k, 0.0) + v
                n += 1
                if n == 8:
                    break
        parts = self._parts(record)
        return max(parts, key=lambda k: parts[k] - usual.get(k, 0.0) / max(n, 1))

    # --------------------------------------------------------------- reading

    @property
    def seq(self) -> int:
        """Sequence number of the most recent record (0 before the first).
        Lock-free read of an int the GIL keeps coherent."""
        return self._seq

    def window_throughput(self) -> tuple[float, int]:
        """(busy seconds, tokens) over the sliding decode window — the
        denominator/numerator for live MFU. Busy seconds exclude idle loop
        sleeps: MFU is measured against time the device was actually
        stepping, which is the figure an operator tunes kernels by."""
        with self._lock:
            if not self._window:
                return 0.0, 0
            secs = sum(s for s, _ in self._window)
            toks = sum(t for _, t in self._window)
        return secs, toks

    def snapshot(self, limit: int = 64, *, slow_only: bool = False) -> dict:
        """JSON-safe view for /api/steps: recent records (newest first),
        per-kind EMA baselines, and the anomaly counter."""
        limit = max(0, min(limit, self.capacity))
        with self._lock:
            records = list(self._ring)
            ema = dict(self._ema)
            slow_total = self.slow_steps_total
            seq = self._seq
        if slow_only:
            records = [r for r in records if r["slow"]]
        records = records[-limit:]
        records.reverse()
        # copies: the ring's dicts stay untouched for concurrent snapshots
        records = [
            {**r,
             "total_s": round(r["total_s"], 6),
             "phases_s": {k: round(v, 6) for k, v in r["phases_s"].items()},
             "t0_s": round(r["t0_s"], 6), "t1_s": round(r["t1_s"], 6),
             "wall_s": round(r["t1_s"] - r["t0_s"], 6),
             **{k: round(r[k], 6)
                for k in ("host_cpu_s", "gap_cpu_s", "gc_s") if k in r},
             "spans": [[name, round(start - r["t0_s"], 6), round(dur, 6)]
                       for name, start, dur in r["spans"]],
             "since_prev": {f"{b}_s": round(v, 6)
                            for b, v in r["since_prev"].items()},
             "builds": {"count": len(r["builds"]),
                        "names": list(r["builds"])}}
            for r in records
        ]
        return {
            "steps_total": seq,
            "buffered": len(self._ring) if not slow_only else None,
            "capacity": self.capacity,
            "slow_steps_total": slow_total,
            "ema_step_s": {k: round(v, 6) for k, v in ema.items()},
            "slow_ratio": self.slow_ratio,
            "slow_floor_s": self.slow_floor_s,
            "records": records,
        }
