"""Paged KV cache: PagePool bookkeeping, scheduler edge cases, zero-copy.

Three layers, mirroring the implementation split:
- PagePool unit tests (pure host-side: alloc/ref/unref free-list math,
  double-free detection, trash-page reservation).
- EngineCore integration (CPU backend): pool exhaustion at insert queues
  requests instead of crashing, exhaustion mid-decode evicts prefix pages
  then degrades to an early 'length' finish, cancellation releases pages,
  and the engine keeps serving after every one of those paths.
- The zero-copy guarantee: a prefix-cache hit builds and dispatches NO
  program of its own (tests.support.assert_hit_is_zero_copy) — the
  counterpart of test_prefix_cache's no-re-prefill guard.
"""

import queue

import numpy as np
import pytest

from llmlb_tpu.engine.paging import PageError, PagePool
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from tests.support import assert_hit_is_zero_copy
from tests.support import collect as _collect

# ------------------------------------------------------------------ page pool


def test_alloc_is_all_or_nothing():
    pool = PagePool(6)  # 5 usable (page 0 reserved)
    assert pool.total == 5
    got = pool.alloc(3)
    assert got is not None and len(got) == 3
    assert pool.available() == 2
    assert pool.alloc(3) is None  # only 2 free: nothing handed out
    assert pool.available() == 2
    assert pool.alloc(2) is not None
    assert pool.available() == 0
    assert pool.alloc(0) == []


def test_unref_returns_page_and_double_free_raises():
    pool = PagePool(4)
    (page,) = pool.alloc(1)
    pool.unref(page)
    assert pool.available() == 3
    with pytest.raises(PageError):
        pool.unref(page)  # double free must never silently pass


def test_ref_shares_ownership():
    pool = PagePool(4)
    (page,) = pool.alloc(1)
    pool.ref(page)  # second owner (prefix cache / sharing slot)
    pool.unref(page)
    assert pool.available() == 2  # still held by the other owner
    pool.unref(page)
    assert pool.available() == 3
    with pytest.raises(PageError):
        pool.ref(page)  # a free page has no owners to join


def test_reserved_trash_page_is_untouchable():
    pool = PagePool(4)
    pages = pool.alloc(3)
    assert 0 not in pages  # page 0 never allocated
    with pytest.raises(PageError):
        pool.unref(0)
    with pytest.raises(PageError):
        pool.unref(99)


def test_reset_reclaims_everything():
    pool = PagePool(5)
    pool.alloc(4)
    pool.reset()
    assert pool.available() == 4
    assert pool.refcount(0) == 1  # trash page stays pinned


# ---------------------------------------------------------------- engine core


def _req(prompt, max_tokens=4, temperature=0.0):
    return Request(prompt_ids=list(prompt),
                   sampling=SamplingParams(temperature=temperature,
                                           max_tokens=max_tokens))


def _core(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("slot_capacity", 64)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("seed", 0)
    kw.setdefault("kv_page_size", 16)
    return EngineCore(get_preset("debug-tiny"), **kw)


@pytest.fixture(scope="module")
def prompt():
    rng = np.random.default_rng(11)
    cfg = get_preset("debug-tiny")
    return list(rng.integers(1, cfg.vocab_size, size=(48,)))


def test_prefix_hit_is_zero_copy(prompt):
    """Acceptance guard: a hit writes donor page ids into the new slot's
    block table — it builds no program and dispatches only its 16-token
    suffix chunk."""
    core = _core()
    core.start()
    try:
        _collect(core.submit(_req(prompt)))
        with assert_hit_is_zero_copy(core, suffix_tokens=16):
            _collect(core.submit(_req(prompt)))
        assert core.metrics.prefix_hits_total == 1
        assert core.metrics.prefix_cached_tokens_total == 32
    finally:
        core.stop()


def test_donor_slot_frees_immediately_in_paged_mode(prompt):
    """The occupancy win: donating a prefix pins PAGES, not the slot — every
    slot returns to the serving pool on completion."""
    core = _core(num_slots=2, prefix_cache_slots=1)
    core.start()
    try:
        _collect(core.submit(_req(prompt)))
        assert len(core.prefix_cache) == 1
        assert len(core._free_slots()) == 2  # both slots serve traffic
        info = core.prefix_cache_info()
        assert info["pinned_pages"] == 3  # 48-token head / 16-token pages
    finally:
        core.stop()


def test_pool_exhaustion_at_insert_queues_request():
    """More concurrent prompts than the pool covers: the overflow request
    waits (held on the pool) and completes once pages free — never an error,
    never a crash."""
    cfg = get_preset("debug-tiny")
    rng = np.random.default_rng(3)
    # 4 slots but only ~2 requests' worth of pages: 2 pages per 20-token
    # prompt (+1 page of decode growth), 5 usable pages in the pool
    core = _core(num_slots=4, kv_pages=6, prefix_cache=False)
    core.start()
    try:
        reqs = [_req(rng.integers(1, cfg.vocab_size, size=(20,)), max_tokens=4)
                for _ in range(6)]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            _toks, finish = _collect(r)
            assert finish in ("stop", "length")
        # pool fully reclaimed once everything finished
        assert core.page_pool.available() == core.page_pool.total
    finally:
        core.stop()


def test_pool_exhaustion_mid_decode_finishes_early_and_keeps_serving():
    """Decode growth that the pool cannot cover finishes that request with
    'length' instead of crashing the step loop, and the engine serves new
    requests afterwards."""
    core = _core(num_slots=2, kv_pages=5, prefix_cache=False)
    core.start()
    try:
        # two growing requests race for 4 usable pages; each wants
        # ceil((8 + 40)/16) = 3 — at least one must be cut short
        a = core.submit(_req([3] * 8, max_tokens=40))
        b = core.submit(_req([5] * 8, max_tokens=40))
        toks_a, fin_a = _collect(a)
        toks_b, fin_b = _collect(b)
        assert {fin_a, fin_b} <= {"stop", "length"}
        assert len(toks_a) >= 1 and len(toks_b) >= 1
        # the loop survived: a fresh request still completes
        toks_c, fin_c = _collect(core.submit(_req([7] * 8, max_tokens=4)))
        assert fin_c in ("stop", "length")
        assert core.page_pool.available() == core.page_pool.total
    finally:
        core.stop()


def test_cancel_releases_pages(prompt):
    """Client cancel mid-suffix-prefill returns every page the request held
    (shared prefix pages drop to the donor's refcount, fresh ones free).
    Driven inline so the cancellation lands between insert and the first
    suffix chunk."""
    core = _core()
    # warm the cache: one completed request donates its prompt head
    warm = _req(prompt, max_tokens=2)
    core.pending.put(warm)
    for _ in range(500):
        core._try_insert()
        core._advance_prefill()
        core._decode_active()
        try:
            if warm.events.get_nowait()[0] == "done":
                break
        except queue.Empty:
            pass
    assert len(core.prefix_cache) == 1
    pinned = core._prefix_pinned_pages
    used_before = core.page_pool.used()
    assert used_before == pinned  # only the donated pages are held

    r = _req(prompt, max_tokens=8)
    core.pending.put(r)
    core._try_insert()  # zero-copy hit: shares 2 pages, allocs the rest
    assert core.metrics.prefix_hits_total == 1
    assert core.page_pool.used() > used_before
    r.cancel()
    core._advance_prefill()  # observes the cancellation
    assert r.events.get_nowait() == ("done", "cancelled")
    assert core.page_pool.used() == used_before  # every page returned
    (entry,) = core.prefix_cache.entries()
    assert entry.refcount == 0  # reader released the donor entry too


def test_hit_under_pool_pressure_never_evicts_its_own_donor(prompt):
    """Regression: reserving suffix pages for a hit must not LRU-evict the
    matched donor itself — that would free (and possibly recycle as 'fresh')
    the very pages the hit is about to share. The donor is pinned across the
    reservation, so the request waits on the pool instead."""
    core = _core(num_slots=4, kv_pages=7)  # 6 usable pages
    # donor: 48-token prompt -> 3 pages pinned, 3 free
    warm = _req(prompt, max_tokens=2)
    core.pending.put(warm)
    for _ in range(500):
        core._try_insert()
        core._advance_prefill()
        core._decode_active()
        try:
            if warm.events.get_nowait()[0] == "done":
                break
        except queue.Empty:
            pass
    assert core._prefix_pinned_pages == 3
    (donor,) = core.prefix_cache.entries()

    # occupy the 3 free pages with a request that stays active (max_tokens
    # keeps it within 3 pages, so its own decode growth never needs a 4th —
    # the only eviction pressure in play is the hit's reservation)
    blocker = _req([p + 1 for p in prompt[:33]], max_tokens=8)
    core.pending.put(blocker)
    core._try_insert()
    assert core.page_pool.available() == 0

    # a hit on the donor needs 1 fresh page; the only refcount-0 entry is
    # the donor itself — it must NOT be sacrificed to serve its own hit
    r = _req(prompt, max_tokens=2)
    core.pending.put(r)
    core._try_insert()
    assert core._held_request is r  # parked on the pool, not inserted
    assert core.prefix_cache.entries(), "donor was evicted to serve its hit"
    assert donor.refcount == 0  # the pin did not leak past the attempt

    # once the blocker finishes, pages free and the held hit completes
    for _ in range(2000):
        core._try_insert()
        core._advance_prefill()
        core._decode_active()
        try:
            kind, value = r.events.get_nowait()
            if kind == "done":
                break
            assert kind == "tokens"
        except queue.Empty:
            pass
    else:
        raise AssertionError("held hit never completed")
    assert core.metrics.prefix_hits_total == 1


def test_pool_pressure_evicts_prefix_pages(prompt):
    """A new request that the free pages cannot cover reclaims prefix-cache
    pages LRU before queueing — cached history never starves live traffic."""
    core = _core(num_slots=2, kv_pages=9, prefix_cache_slots=2)
    core.start()
    try:
        _collect(core.submit(_req(prompt)))  # donates 3 pages of 8 usable
        assert core._prefix_pinned_pages == 3
        # a fat prompt wants 4 pages; free = 8 - 3 pinned = 5 — fits without
        # eviction. Follow with another: 5 - 4 = 1 free, next wants 4 ->
        # must evict the donor's 3 pages.
        other = [p + 1 for p in prompt]  # no shared prefix
        third = [p + 2 for p in prompt]
        a = core.submit(_req(other[:47], max_tokens=2))
        b = core.submit(_req(third[:47], max_tokens=2))
        _collect(a)
        _collect(b)
        assert core.metrics.prefix_evictions_total >= 1
    finally:
        core.stop()


def test_paged_gauges_in_metrics_and_system(prompt):
    core = _core()
    core.start()
    try:
        _collect(core.submit(_req(prompt)))
        info = core.kv_cache_info()
        assert info["layout"] == "paged"
        assert info["pages_total"] == 4 * 4  # 4 slots x 4 pages/slot
        assert info["pages_pinned"] == 3
        assert 0.0 <= info["fragmentation"] <= 1.0
        stats = core.stats()
        text = core.metrics.render(
            queue_depth=stats.queued, active_slots=stats.active_slots,
            num_slots=stats.num_slots, prefix_cache=core.prefix_cache_info(),
            kv_cache=info,
        )
        for name in ("llmlb_engine_kv_pages_total", "llmlb_engine_kv_pages_free",
                     "llmlb_engine_kv_pages_pinned",
                     "llmlb_engine_kv_page_fragmentation_ratio",
                     "llmlb_engine_kv_pool_utilization_ratio",
                     "llmlb_engine_kv_page_waste_tokens_mean"):
            assert name in text, name
    finally:
        core.stop()


def test_paged_goldens_identical_with_quantize_off(prompt):
    """Golden run over the quantization knob: an explicit quantize="off"
    engine produces the exact token streams (greedy AND seeded stochastic)
    and the exact kv gauges the default engine does — the int8 plumbing is
    provably zero-cost when disabled (docs/quantization.md)."""
    results = {}
    for quantize in (None, "off"):
        core = _core(quantize=quantize)
        core.start()
        try:
            greedy = _req(prompt, max_tokens=8)
            seeded = Request(prompt_ids=list(prompt),
                            sampling=SamplingParams(temperature=0.9,
                                                    max_tokens=8, seed=5))
            core.submit(greedy)
            core.submit(seeded)
            toks_g, _ = _collect(greedy)
            toks_s, _ = _collect(seeded)
            results[quantize] = (toks_g, toks_s, core.kv_cache_info())
        finally:
            core.stop()
    assert results[None] == results["off"]
