"""In-process disaggregated prefill/decode: two step loops, one PagePool.

`--role split` partitions an engine's slots into a PREFILL pool and a DECODE
pool and runs one step-loop thread per pool (docs/disaggregation.md):

- The prefill loop owns admission (`_try_insert`) and chunked prefill
  (`_advance_prefill`). When a slot's prompt KV is fully landed the slot is
  STAGED — its final logits row is held on device — instead of activated.
- The handoff pump adopts staged requests into free decode slots. The
  transfer is a page-id exchange: the block-table row moves from the prefill
  slot to the decode slot and not one KV byte is copied (refcounts are
  untouched — ownership moves with the row, exactly a pin/unpin pair
  collapsed). The grammar-constraint cursor and the prompt-lookup drafter
  move with the request, and activation then runs the standard PR 10
  resume-shaped path, so adopted streams are token-identical to
  `--role both` for greedy and seeded-stochastic sampling.
- The decode loop runs `_decode_active` only. The tier-1 acceptance
  invariant — ZERO prefill dispatches on the decode loop — is enforced by
  construction and asserted over `EngineCore.prefill_dispatch_by_loop`.

Both loops serialize device work through one lock (a single host has one
device; the split removes SCHEDULING contention, not compute), with a
decode-first turnstile so a decoder's inter-token latency is bounded by one
prefill chunk rather than a whole admission+prefill iteration. Under decode
pressure the handoff pump may preempt: a staged request of a more important
class parks the least-important decoding victim (the PR 10 machinery), which
later resumes through the prefill pool and hands off again.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from llmlb_tpu.engine import compilelog

log = logging.getLogger("llmlb_tpu.disagg")


class SplitRuntime:
    """The split-mode scheduler runtime attached to one EngineCore."""

    def __init__(self, core, prefill_slots: int | None = None):
        self.core = core
        n = core.num_slots
        if n < 2:
            raise ValueError(
                "--role split needs at least 2 slots (1 prefill + 1 decode)"
            )
        if prefill_slots is None:
            env = os.environ.get("LLMLB_DISAGG_PREFILL_SLOTS")
            if env:
                try:
                    prefill_slots = int(env)
                except ValueError:
                    log.warning(
                        "LLMLB_DISAGG_PREFILL_SLOTS=%r is not an integer; "
                        "using the default split", env,
                    )
        if prefill_slots is None:
            # prefill is bursty, decode is the steady state: a 1:3 split
            # keeps most capacity serving tokens
            prefill_slots = max(1, n // 4)
        p = min(max(1, int(prefill_slots)), n - 1)
        self.prefill_pool: tuple[int, ...] = tuple(range(p))
        self.decode_pool: tuple[int, ...] = tuple(range(p, n))
        # One lock serializes device dispatches across the two loops (the
        # caches are donated per dispatch — concurrent dispatch would
        # consume the same buffers twice).
        self.lock = threading.Lock()
        # Decode-first turnstile: the decode loop raises this before taking
        # the lock and the prefill loop backs off while it is up, so a
        # decode step never waits behind more than the in-flight chunk.
        self._decode_wants = threading.Event()
        self._threads: list[threading.Thread] = []
        log.info(
            "split mode: %d prefill slot(s) %s, %d decode slot(s) %s",
            len(self.prefill_pool), list(self.prefill_pool),
            len(self.decode_pool), list(self.decode_pool),
        )

    # ------------------------------------------------------------------ loops

    def start(self) -> None:
        self._threads = [
            threading.Thread(target=self._prefill_loop,
                             name="engine-prefill-pool", daemon=True),
            threading.Thread(target=self._decode_loop,
                             name="engine-decode-pool", daemon=True),
        ]
        for t in self._threads:
            t.start()

    def join(self, timeout: float | None = None) -> None:
        for t in self._threads:
            t.join(timeout=timeout)

    def _yield_to_decode(self) -> None:
        while self._decode_wants.is_set() and self.core._running:
            time.sleep(0.0002)

    def _fail_reset(self) -> None:
        core = self.core
        log.exception("split step failed; resetting engine state")
        with self.lock:
            core._fail_all("engine step error")
            core._reset_caches()

    def _prefill_loop(self) -> None:
        core = self.core
        core._tls.tag = "prefill"
        compilelog.set_thread_class("loop")
        # the loop's clock (engine/stepstats.py): waiting for the decode
        # loop and for the lock is `control`, adoption and admission `admit`
        clock = core._clock()
        while core._running:
            did = False
            try:
                clock.switch("control")
                self._yield_to_decode()
                with self.lock:
                    clock.switch("admit")
                    did |= self.pump_handoffs()
                    did |= core._try_insert()
                    clock.switch("control")
                self._yield_to_decode()
                with self.lock:
                    clock.switch("other")
                    did |= core._advance_prefill()
            except Exception:  # pragma: no cover - fail loud, keep serving
                clock.abandon()
                self._fail_reset()
            if not did:
                clock.switch("idle")
                time.sleep(0.001)

    def _decode_loop(self) -> None:
        core = self.core
        core._tls.tag = "decode"
        compilelog.set_thread_class("loop")
        clock = core._clock()
        while core._running:
            did = False
            try:
                clock.switch("control")
                self._decode_wants.set()
                try:
                    with self.lock:
                        self._decode_wants.clear()
                        clock.switch("other")
                        did |= core._decode_active()
                        # a finished/parked slot frees capacity: adopt the
                        # oldest staged request before the next decode step
                        clock.switch("admit")
                        did |= self.pump_handoffs()
                finally:
                    self._decode_wants.clear()
            except Exception:  # pragma: no cover - fail loud, keep serving
                clock.abandon()
                self._fail_reset()
            if not did:
                clock.switch("idle")
                time.sleep(0.001)

    # -------------------------------------------------------------- admission

    def free_prefill_slots(self) -> list[int]:
        return [
            i for i in self.prefill_pool
            if self.core.slots[i].request is None
        ]

    def backlog(self) -> int:
        return sum(
            1 for i in self.prefill_pool
            if self.core.slots[i].handoff_ready
        )

    # --------------------------------------------------------------- handoff

    def stage_group(self, group, logits) -> None:
        """A prefill-loop activation lands here instead: pin the finished
        prompt KV in the prefill slot's pages, hold the final logits row
        (the first token samples from it at adoption), and park the device
        seq_len at capacity-1 so batched decode's garbage writes for this
        row stay in the never-read last cell until the pages move."""
        core = self.core
        rows = []
        for row, (slot_id, request, n) in enumerate(group):
            slot = core.slots[slot_id]
            slot.prefilling = True
            slot.prefill_pos = n
            slot.handoff_ready = True
            slot.handoff_logits = logits[row:row + 1]
            slot.handoff_ready_at = time.monotonic()
            core._seq_lens[slot_id] = 0
            rows.append(slot_id)
            core._fr_emit(request, "staged", tokens=n, slot=slot_id)
        import jax.numpy as jnp

        core._d_seq_lens = core._d_seq_lens.at[
            jnp.asarray(rows, jnp.int32)
        ].set(core.slot_capacity - 1)
        core.metrics.set_handoff_backlog(self.backlog())

    def _drop_staged(self, slot_id: int, reason: str) -> None:
        # the scheduler's one terminal-teardown helper clears every slot
        # field (handoff_* included) — no second copy of that invariant
        self.core._finish_slot(slot_id, reason)

    def _acquire_decode_slot(self, prio: int) -> int | None:
        """A free decode slot, or one freed by parking a less-important
        decoding victim (the split-mode preemption point — admission-time
        slot-pressure preemption cannot free a prefill slot)."""
        core = self.core
        for j in self.decode_pool:
            if core.slots[j].request is None:
                return j
        cands = [c for c in core._preempt_candidates(prio)
                 if c in self.decode_pool]
        if cands:
            core._park_slot(cands[0])
            return cands[0]
        return None

    def _adopt(self, i: int, j: int) -> None:
        """Move one staged request from prefill slot `i` to decode slot `j`:
        block-table row exchange (zero KV copy), host cursors (grammar FSM,
        drafter) ride along, then the standard activation runs against the
        decode slot — for a resumed (previously parked) request this IS the
        PR 10 resume, so the stream stays token-identical."""
        core = self.core
        slot_i = core.slots[i]
        request = slot_i.request
        n = slot_i.prefill_pos
        logits = slot_i.handoff_logits
        latency = time.monotonic() - slot_i.handoff_ready_at
        slot_j = core.slots[j]
        assert slot_j.request is None, "adoption into an occupied decode slot"

        # page-id exchange: the row moves, ownership moves with it, no
        # refcount traffic and no KV bytes
        core._slot_pages[j] = core._slot_pages[i]
        core._slot_pages[i] = []
        core._block_tables[j, :] = core._block_tables[i, :]
        core._block_tables[i, :] = 0
        core._tables_dirty = True

        # host-side cursors travel with the request (a fresh grammar FSM
        # would re-mask from the string start — the PR 10 park bug)
        slot_j.constraint = slot_i.constraint
        if slot_j.constraint is not None:
            core._set_mask_row(j, slot_j.constraint)
            if core._mask_bias is not None:
                core._mask_bias[i] = 0.0
                core._mask_dirty_rows.add(i)
        slot_j.drafter = slot_i.drafter
        slot_j.spec_k = slot_i.spec_k
        slot_j.cache_entry = slot_i.cache_entry

        slot_i.request = None
        slot_i.constraint = None  # moved: _constrained_count is unchanged
        slot_i.cache_entry = None
        slot_i.drafter = None
        slot_i.spec_k = 0
        slot_i.generated = 0
        slot_i.out_tokens = []
        slot_i.first_pending = False
        slot_i.prefilling = False
        slot_i.prefill_pos = 0
        slot_i.handoff_ready = False
        slot_i.handoff_logits = None
        slot_i.handoff_ready_at = 0.0
        core._seq_lens[i] = 0

        prev = core._loop_tag()
        core._tls.tag = "handoff"
        try:
            core._activate_group(
                [(j, request, n)],
                np.asarray([j], np.int32),
                np.asarray([n], np.int32),
                logits,
            )
        finally:
            core._tls.tag = prev
        core.metrics.record_handoff("in_process", latency)
        core._fr_emit(request, "adopted", in_process=True,
                      staged_s=round(latency, 6))

    def pump_handoffs(self) -> bool:
        """Adopt staged requests into decode slots, most important class
        first (FIFO by readiness within a class, slot id as the final tie).
        Strictly ordered: a blocked head blocks everything behind it — a
        later request must not steal the slot an earlier one is owed."""
        core = self.core
        ready = [i for i in self.prefill_pool
                 if core.slots[i].handoff_ready]
        if not ready:
            core.metrics.set_handoff_backlog(0)
            return False
        ready.sort(key=lambda i: (
            core._priority_of(core.slots[i].request),
            core.slots[i].handoff_ready_at, i,
        ))
        progress = False
        for i in ready:
            slot = core.slots[i]
            request = slot.request
            if core._is_cancelled(request):
                self._drop_staged(i, "cancelled")
                progress = True
                continue
            j = self._acquire_decode_slot(core._priority_of(request))
            if j is None:
                break
            self._adopt(i, j)
            progress = True
        core.metrics.set_handoff_backlog(self.backlog())
        return progress
