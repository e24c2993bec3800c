"""Prefix KV cache: radix-tree bookkeeping, scheduler reuse, perf smoke.

Three layers, mirroring the implementation split:
- PrefixCache unit tests (pure host-side: insert/match/refcount/evict,
  bucket alignment, LRU order, edge splitting).
- EngineCore integration (CPU backend): cache hits serve the shared head
  from the donor's pages, outputs stay greedy-identical to the cold path,
  cancellation mid-suffix-prefill releases the donor, disabled flag
  restores the old behavior.
- A fast perf smoke asserting a cache-hit insert dispatches NO prefill
  device step for the cached region — the tier-1 guard against silent
  re-prefill regressions.
"""

import queue

import numpy as np
import pytest

from llmlb_tpu.engine.prefix_cache import PrefixCache
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from tests.support import (
    RUN_LIFTED,
    InlineLoop,
    assert_hit_is_zero_copy,
    collect,
)

# ----------------------------------------------------------------- radix tree


def make_cache(**kw):
    kw.setdefault("max_entries", 4)
    kw.setdefault("min_len", 4)
    kw.setdefault("align", 4)
    return PrefixCache(**kw)


def _evict(c):
    """Pages of the LRU entry evicted, or None when every entry is held."""
    entry = c.evict_lru_entry()
    return None if entry is None else entry.pages


def test_insert_and_exact_match():
    c = make_cache()
    assert c.insert((1, 2, 3, 4, 5, 6, 7, 8), pages=(0,)) is not None
    got = c.match([1, 2, 3, 4, 5, 6, 7, 8, 9], max_len=8)
    assert got is not None
    entry, use_len = got
    assert entry.pages == (0,)
    assert use_len == 8
    assert [e.pages for e in c.entries()] == [(0,)]
    assert c.cached_tokens() == 8


def test_match_uses_partial_head_of_longer_entry():
    """KV rows for [0, m) depend only on tokens [0, m): a stored prefix can
    donate any of its own prefixes, including partway into a radix edge."""
    c = make_cache()
    c.insert(tuple(range(100, 112)), pages=(1,))  # 12 tokens
    # query shares only the first 6 tokens, then diverges
    got = c.match(list(range(100, 106)) + [999, 998], max_len=7)
    assert got is not None
    entry, use_len = got
    assert entry.pages == (1,)
    assert use_len == 4  # 6 matched, aligned down to the 4-token quantum


def test_match_respects_max_len_and_min_len():
    c = make_cache()
    c.insert((1, 2, 3, 4, 5, 6, 7, 8), pages=(0,))
    # an identical prompt must leave >= 1 suffix token: max_len = n - 1
    entry, use_len = c.match([1, 2, 3, 4, 5, 6, 7, 8], max_len=7)
    assert use_len == 4  # 7 aligned down
    # matches shorter than min_len are worthless
    assert c.match([1, 2, 3, 9], max_len=3) is None


def test_edge_split_on_divergent_insert():
    c = make_cache()
    c.insert((1, 2, 3, 4, 5, 6, 7, 8), pages=(0,))
    c.insert((1, 2, 3, 4, 9, 9, 9, 9), pages=(1,))  # splits the edge at depth 4
    e0, u0 = c.match([1, 2, 3, 4, 5, 6, 7, 8, 0], max_len=8)
    e1, u1 = c.match([1, 2, 3, 4, 9, 9, 9, 9, 0], max_len=8)
    assert (e0.pages, u0) == ((0,), 8)
    assert (e1.pages, u1) == ((1,), 8)
    assert len(c) == 2


def test_covers_blocks_duplicate_coverage_but_allows_extension():
    c = make_cache()
    c.insert((1, 2, 3, 4), pages=(0,))
    assert c.covers((1, 2, 3, 4))
    assert c.insert((1, 2, 3, 4), pages=(1,)) is None  # no new coverage
    # a LONGER prefix is new coverage
    assert c.insert((1, 2, 3, 4, 5, 6, 7, 8), pages=(1,)) is not None
    # ...and the short one is now covered by the long one too
    assert c.covers((1, 2, 3, 4))


def test_refcount_blocks_eviction():
    c = make_cache()
    e = c.insert((1, 2, 3, 4), pages=(0,))
    c.acquire(e)
    assert _evict(c) is None  # in-flight reader pins it
    c.release(e)
    assert _evict(c) == (0,)
    assert len(c) == 0
    assert c.match([1, 2, 3, 4, 5], max_len=4) is None


def test_lru_eviction_order_and_match_refreshes():
    c = make_cache()
    c.insert((1,) * 8, pages=(0,))
    c.insert((2,) * 8, pages=(1,))
    c.insert((3,) * 8, pages=(2,))
    c.match([1] * 9, max_len=8)  # a match refreshes the first entry's clock
    assert _evict(c) == (1,)     # the second is now the oldest untouched
    assert _evict(c) == (2,)
    assert _evict(c) == (0,)
    assert _evict(c) is None


def test_evict_subsumed_reclaims_ancestor_donors():
    """A longer prefix covers every match its ancestors could serve; the
    ancestors' entries are reclaimed instead of bleeding the budget one
    entry per conversation turn."""
    c = make_cache()
    e1 = c.insert((1, 2, 3, 4), pages=(0,))
    turn2 = (1, 2, 3, 4, 5, 6, 7, 8)
    assert [e.pages for e in c.evict_subsumed_entries(turn2)] == [(0,)]
    c.insert(turn2, pages=(1,))
    assert [e.pages for e in c.entries()] == [(1,)]
    # coverage is preserved: the short head still matches via the long entry
    entry, use_len = c.match([1, 2, 3, 4, 9], max_len=4)
    assert entry.pages == (1,) and use_len == 4
    # an acquired ancestor is NOT reclaimed (in-flight reader)
    e2 = c.insert((9, 9, 9, 9), pages=(2,))
    c.acquire(e2)
    assert c.evict_subsumed_entries((9, 9, 9, 9, 1, 1, 1, 1)) == []
    c.release(e2)
    assert e1.node is None  # removed entry is fully detached


def test_budget_rejects_insert_when_full():
    c = make_cache(max_entries=1)
    assert c.insert((1, 2, 3, 4), pages=(0,)) is not None
    assert c.insert((5, 6, 7, 8), pages=(1,)) is None  # caller must evict first
    assert _evict(c) == (0,)
    assert c.insert((5, 6, 7, 8), pages=(1,)) is not None


def test_clear_drops_everything():
    c = make_cache()
    c.insert((1, 2, 3, 4), pages=(0,))
    c.insert((1, 2, 3, 4, 5, 6, 7, 8), pages=(1,))
    c.clear()
    assert len(c) == 0
    assert c.match([1, 2, 3, 4, 5], max_len=4) is None


# ---------------------------------------------------------------- engine core


def _run(core, prompt_ids, *, max_tokens=4, temperature=0.0):
    r = Request(prompt_ids=list(prompt_ids),
                sampling=SamplingParams(temperature=temperature,
                                        max_tokens=max_tokens))
    core.submit(r)
    return collect(r)


@pytest.fixture(scope="module")
def prompt():
    rng = np.random.default_rng(7)
    cfg = get_preset("debug-tiny")
    return list(rng.integers(1, cfg.vocab_size, size=(48,)))


# Every engine-core test runs over two page geometries: a page equal to the
# prefill bucket (16), and a page that is a multiple of it (32), where the
# sharing quantum prefix_align = lcm(bucket, page) is the page and no longer
# the bucket — a 48-token prompt then donates 32 tokens, not 48.
@pytest.fixture(params=[16, 32], ids=["paged", "paged-page32"])
def kv_page(request):
    return request.param


def make_core(kv_page, **kw):
    return EngineCore(get_preset("debug-tiny"), kv_page_size=kv_page, **kw)


def _head(n, kv_page):
    """Tokens of an n-token prompt that can be donated: whole sharing
    quanta, lcm(bucket 16, page)."""
    return n // kv_page * kv_page


def test_cache_hit_reuses_prefix_and_matches_cold_output(prompt, kv_page):
    """Warm identical prompt: hit counters move, cached tokens are the
    aligned head, and greedy output equals the cold run's (the shared
    pages hold the same numbers the cold prefill computed)."""
    core = make_core(kv_page, num_slots=4, slot_capacity=64,
                     prefill_buckets=(16,), seed=0)
    core.start()
    try:
        cold_toks, cold_fin = _run(core, prompt)
        m = core.metrics
        assert m.prefix_misses_total == 1
        assert m.prefix_insertions_total == 1
        info = core.prefix_cache_info()
        assert info["enabled"] and info["entries"] == 1
        assert info["cached_tokens"] == _head(48, kv_page)

        warm_toks, warm_fin = _run(core, prompt)
        assert m.prefix_hits_total == 1
        # 48-token prompt: reusable head is min(47, ...) aligned to 16 -> 32
        assert m.prefix_cached_tokens_total == 32
        assert (warm_toks, warm_fin) == (cold_toks, cold_fin)
    finally:
        core.stop()


@pytest.mark.parametrize("todays_order", [False, True],
                         ids=["ahead", "todays-order"])
def test_a_donors_pages_are_whole_with_a_burst_in_flight(prompt, todays_order):
    """A request meets its EOS inside burst n while burst n+1, which left
    before n was emitted, still writes its row: what it donates are whole
    pages below its prompt's length, which n+1 never writes (it writes at
    and past the final length). A later request with the same head is a
    zero-copy hit and reads what a cold engine computes (docs/kv-cache.md
    "A burst in flight and pages already released")."""
    def serve(eos, *, cache):
        core = make_core(16, num_slots=2, slot_capacity=96,
                         prefill_buckets=(16, 32, 64), seed=0, decode_burst=4,
                         eos_id=eos, prefix_cache=cache)
        loop = InlineLoop(core, todays_order=todays_order,
                          queued_run=RUN_LIFTED)  # bursts 2 and 3 queue
        donor, beside, reader = (Request(
            prompt_ids=list(ids), sampling=SamplingParams(
                temperature=0.0, max_tokens=n))
            for ids, n in ((prompt, 30), (prompt[:20][::-1], 40),
                           (prompt[:40] + [3, 1, 4], 12)))
        core.pending.put(donor)
        core.pending.put(beside)
        # the donor ends in burst 2; the reader comes while 3 is in flight
        loop.during[3] = [lambda: core.pending.put(reader)]
        loop.run()
        return [collect(r, None) for r in (donor, beside, reader)], loop

    streams = [tokens for tokens, _ in serve(-1, cache=False)[0]]
    probe, everything = streams[0], sum(streams, [])
    # inside burst 2 (decode tokens 5 to 8), and no other row's token
    at = next(i for i in (5, 6, 7) if everything.count(probe[i]) == 1)
    eos = probe[at]
    cold, _ = serve(eos, cache=False)
    warm, loop = serve(eos, cache=True)
    assert warm == cold
    assert warm[0] == (probe[:at], "stop")
    assert [len(t) for t, _ in warm[1:]] == [40, 12]
    m = loop.core.metrics
    assert m.prefix_insertions_total >= 1 and m.prefix_hits_total == 1
    assert m.prefix_cached_tokens_total == 32  # the reader's 40, aligned
    if not todays_order:
        # both slots held: burst 3 left before burst 2, the donor's last,
        # was even fetched (queued behind it), the donor's row in it
        third = loop.decode_records()[2]
        assert third["queued_behind"] and third["active_slots"] == 2


def test_divergent_tail_still_hits_shared_head(prompt, kv_page):
    core = make_core(kv_page, num_slots=4, slot_capacity=64,
                     prefill_buckets=(16,), seed=0)
    core.start()
    try:
        _run(core, prompt)
        # tail diverges at position 40 (p-1 stays in vocab: prompt ids >= 1)
        other = prompt[:40] + [p - 1 for p in prompt[40:]]
        _run(core, other)
        assert core.metrics.prefix_hits_total == 1
        assert core.metrics.prefix_cached_tokens_total == 32  # 40 aligned
    finally:
        core.stop()


def test_slot_pressure_evicts_donors_for_live_traffic(kv_page):
    """Donors pin pages, not slots: under an entry budget of one, a run of
    distinct prompts churns ENTRIES (LRU) and every slot stays free."""
    cfg = get_preset("debug-tiny")
    rng = np.random.default_rng(3)
    core = make_core(kv_page, num_slots=2, slot_capacity=64,
                     prefill_buckets=(16,), prefix_cache_slots=1, seed=0)
    core.start()
    try:
        prompts = [list(rng.integers(1, cfg.vocab_size, size=(40,)))
                   for _ in range(4)]
        for p in prompts:
            _run(core, p)  # each completion pins (budget 1 -> evictions)
        assert core.metrics.prefix_evictions_total >= 1
        assert core.stats().active_slots == 0
        assert len(core.prefix_cache) <= 1
    finally:
        core.stop()


def _drive_to_completion(core, request, limit=500):
    """Run the step loop inline (core not started) until `request` finishes —
    the same call sequence _loop makes, but deterministic for tests."""
    core.pending.put(request)
    for _ in range(limit):
        core._try_insert()
        core._advance_prefill()
        core._decode_active()
        try:
            while True:
                kind, value = request.events.get_nowait()
                if kind in ("done", "error"):
                    return kind, value
        except queue.Empty:
            pass
    raise AssertionError("request did not finish")


def test_cancel_mid_suffix_prefill_releases_entry(prompt, kv_page):
    """A cache-hit request cancelled during its suffix prefill must release
    the donor entry (refcount back to 0) so it stays evictable. Driven
    inline — the loop thread is never started — so the cancellation lands
    exactly between the page-table hit and the first suffix chunk."""
    core = make_core(kv_page, num_slots=4, slot_capacity=64,
                     prefill_buckets=(16,), seed=0)
    # warm the cache with one completed request
    kind, _ = _drive_to_completion(
        core, Request(prompt_ids=list(prompt),
                      sampling=SamplingParams(temperature=0.0, max_tokens=2)))
    assert kind == "done"
    (entry,) = core.prefix_cache.entries()

    r = Request(prompt_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=8))
    core.pending.put(r)
    core._try_insert()  # hit: shares pages, acquires the donor, prefilling
    assert core.metrics.prefix_hits_total == 1
    assert entry.refcount == 1
    assert _evict(core.prefix_cache) is None  # reader pins the donor

    r.cancel()
    core._advance_prefill()  # observes the cancellation mid-suffix-prefill
    assert r.events.get_nowait() == ("done", "cancelled")
    assert entry.refcount == 0
    assert _evict(core.prefix_cache) is not None  # evictable again


def test_multi_turn_conversation_reuses_one_donor_slot(prompt, kv_page):
    """Growing-conversation shape: each turn extends the last prompt. The
    cache must hold ONE entry for the conversation (ancestors reclaimed),
    not one pinned page set per turn."""
    core = make_core(kv_page, num_slots=4, slot_capacity=64,
                     prefill_buckets=(16,), prefix_cache_slots=3, seed=0)
    core.start()
    try:
        turn = list(prompt[:16])
        for growth in (16, 16):  # 16 -> 32 -> 48 tokens
            _run(core, turn)
            turn = turn + [p - 1 for p in prompt[:growth]]
        _run(core, turn)
        assert len(core.prefix_cache) == 1  # one donor covers all turns
        (entry,) = core.prefix_cache.entries()
        assert entry.length == _head(48, kv_page)
    finally:
        core.stop()


def test_env_var_disables_prefix_cache(monkeypatch):
    """LLMLB_PREFIX_CACHE accepts the same off vocabulary as the CLI flag —
    an operator's emergency disable must not silently no-op."""
    for value in ("0", "false", "off", "no"):
        monkeypatch.setenv("LLMLB_PREFIX_CACHE", value)
        core = EngineCore(get_preset("debug-tiny"), num_slots=2,
                          slot_capacity=64, prefill_buckets=(16,), seed=0)
        assert core.prefix_cache is None, value
    monkeypatch.setenv("LLMLB_PREFIX_CACHE", "1")
    core = EngineCore(get_preset("debug-tiny"), num_slots=2,
                      slot_capacity=64, prefill_buckets=(16,), seed=0)
    assert core.prefix_cache is not None


def test_disabled_flag_restores_plain_scheduler(prompt, kv_page):
    core = make_core(kv_page, num_slots=2, slot_capacity=64,
                     prefill_buckets=(16,), prefix_cache=False, seed=0)
    core.start()
    try:
        assert core.prefix_cache is None
        assert core.prefix_cache_info() == {"enabled": False}
        _run(core, prompt)
        _run(core, prompt)
        m = core.metrics
        assert (m.prefix_hits_total, m.prefix_misses_total,
                m.prefix_insertions_total) == (0, 0, 0)
    finally:
        core.stop()


def test_prefix_metrics_in_prometheus_and_summary(prompt, kv_page):
    core = make_core(kv_page, num_slots=4, slot_capacity=64,
                     prefill_buckets=(16,), seed=0)
    core.start()
    try:
        _run(core, prompt)
        _run(core, prompt)
        stats = core.stats()
        text = core.metrics.render(
            queue_depth=stats.queued, active_slots=stats.active_slots,
            num_slots=stats.num_slots, prefix_cache=core.prefix_cache_info(),
        )
        assert "llmlb_engine_prefix_cache_hits_total 1" in text
        assert "llmlb_engine_prefix_cache_misses_total 1" in text
        assert "llmlb_engine_prefix_cache_cached_tokens_total 32" in text
        assert "llmlb_engine_prefix_cache_evictions_total 0" in text
        pinned = _head(48, kv_page) // kv_page
        assert f"llmlb_engine_prefix_cache_pinned_pages {pinned}" in text
        assert "llmlb_engine_prefix_cache_pinned_hbm_bytes" in text
        summary = core.metrics.summary()
        assert summary["prefix_hits_total"] == 1
        assert summary["prefix_cached_tokens_total"] == 32
    finally:
        core.stop()


# ----------------------------------------------------------------- perf smoke


def test_cache_hit_skips_prefill_for_cached_region(prompt, kv_page):
    """Tier-1 regression guard: a hit must dispatch prefill steps ONLY for
    the uncached suffix. 48-token prompt over 16-token chunks: 3 dispatches
    cold, exactly 1 warm (32 tokens ride the donor's shared pages — and the
    hit builds no program of its own: there is no copy to dispatch)."""
    core = make_core(kv_page, num_slots=4, slot_capacity=64,
                     prefill_buckets=(16,), seed=0)
    core.start()
    try:
        m = core.metrics
        _run(core, prompt)
        cold_steps = m.prefill_step.n
        assert cold_steps == 3
        with assert_hit_is_zero_copy(core, suffix_tokens=16):
            _run(core, prompt)
        warm_steps = m.prefill_step.n - cold_steps
        assert m.prefix_hits_total == 1
        assert warm_steps == 1, (
            f"cache hit re-prefilled the cached region: {warm_steps} "
            f"dispatches for a 16-token suffix"
        )
    finally:
        core.stop()


def test_engine_health_and_system_carry_prefix_block():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine

    async def run():
        engine = Engine.from_preset(
            "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,)
        )
        client = TestClient(TestServer(create_engine_app(engine)))
        await client.start_server()
        try:
            health = await (await client.get("/api/health")).json()
            assert health["prefix_cache"]["enabled"] is True
            assert health["prefix_cache"]["budget_slots"] == 1
            assert "prefix_hits_total" in health["metrics"]
            system = await (await client.get("/api/system")).json()
            assert system["prefix_cache"]["enabled"] is True
            metrics_text = await (await client.get("/metrics")).text()
            assert "llmlb_engine_prefix_cache_hits_total" in metrics_text
        finally:
            await client.close()
            engine.core.stop()

    asyncio.run(run())
