"""Speculative decoding through the real scheduler on CPU JAX.

The load-bearing guarantees:
- greedy parity: speculation changes WHEN tokens are computed, never WHICH —
  spec-on output is token-exact vs the non-speculative engine, including a
  mixed batch where only some slots speculate;
- seeded parity: per-request seeded sampling folds the PRNG key by global
  position, so seeded streams are bit-identical with speculation on or off;
- constrained bursts: a JSON-mode request rides the verify path multi-token
  (no batch-wide single-step penalty) and stays 100% schema-valid with
  masked-step accounting intact;
- KV-page rollback: rejected drafts release over-allocated pages exactly
  once (the PagePool double-free guard stays armed), including the
  page-boundary case where the rollback empties the slot's last page.
"""

import asyncio
import json

import jsonschema
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, SamplingParams
from llmlb_tpu.engine.service import Engine

PROMPT = "count: 1 2 3 4 5 6 7 8 9 then repeat: 1 2 3 4 5"

SCHEMA = {
    "type": "object",
    "properties": {
        "ok": {"type": "boolean"},
        "tag": {"enum": ["alpha", "beta"]},
    },
    "required": ["ok", "tag"],
}

# An array of identical items: the grammar plus greedy decode makes the
# continuation maximally predictable, so prompt-lookup drafts accept at a
# high rate — the shape speculation exists to accelerate.
ARRAY_SCHEMA = {
    "type": "array",
    "items": {"enum": ["aa"]},
    "minItems": 6,
    "maxItems": 6,
}


def _engine(spec: bool, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("slot_capacity", 256)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    return Engine.from_preset("debug-tiny", spec_decode=spec, **kw)


def _ids(eng, text=PROMPT):
    return eng.encode_chat([{"role": "user", "content": text}])


@pytest.mark.parametrize("kv_page", [128, 4], ids=["paged", "paged-page4"])
def test_greedy_parity_token_exact(kv_page):
    """Spec on == spec off, token for token — at the default page and at a
    4-token page, where a K+1-token verify chunk crosses page boundaries and
    a rejected suffix rolls pages back."""
    async def collect(spec):
        eng = _engine(spec, kv_page_size=kv_page)
        try:
            r = await eng.complete(
                _ids(eng), SamplingParams(temperature=0.0, max_tokens=120)
            )
            steps = eng.core.metrics.spec_verify_steps_total
            return r.text, r.finish_reason, steps
        finally:
            eng.shutdown()

    base_text, base_fin, base_steps = asyncio.run(collect(False))
    spec_text, spec_fin, spec_steps = asyncio.run(collect(True))
    assert base_steps == 0  # spec off: the verify path never dispatches
    assert spec_steps > 0  # spec on: it actually ran, this is not a no-op
    assert (spec_text, spec_fin) == (base_text, base_fin)


def test_greedy_parity_mixed_batch_some_slots_speculate():
    """Per-request opt-out: slots with speculation disabled share the batch
    with speculating slots and still produce the exact baseline tokens."""
    prompts = [PROMPT, "alpha beta alpha beta alpha", "once upon a time",
               "aa bb aa bb aa bb"]

    async def collect(engine_spec, per_request):
        eng = _engine(engine_spec)
        try:
            outs = await asyncio.gather(*(
                eng.complete(
                    _ids(eng, p),
                    SamplingParams(temperature=0.0, max_tokens=48,
                                   speculative=knob),
                )
                for p, knob in zip(prompts, per_request)
            ))
            return [r.text for r in outs], eng.core.metrics
        finally:
            eng.shutdown()

    baseline, _ = asyncio.run(collect(False, [None] * 4))
    mixed_knobs = [{"enabled": True}, {"enabled": False},
                   {"enabled": True, "max_draft_tokens": 2}, None]
    mixed, metrics = asyncio.run(collect(True, mixed_knobs))
    assert mixed == baseline
    assert metrics.spec_verify_steps_total > 0


def test_seeded_sampling_bit_identical_with_speculation():
    """temperature>0 with a seed: the per-position key fold makes the token
    stream a pure function of (seed, position), so speculation cannot
    change it — the strongest distribution-preservation check available."""
    async def collect(spec):
        eng = _engine(spec)
        try:
            r = await eng.complete(
                _ids(eng),
                SamplingParams(temperature=1.0, max_tokens=96, seed=1234),
            )
            return r.text, r.finish_reason
        finally:
            eng.shutdown()

    assert asyncio.run(collect(True)) == asyncio.run(collect(False))


def test_constrained_json_decodes_multi_token_via_speculation():
    """A JSON-mode request must ride the verify path (multi-token steps with
    per-position masks) instead of forcing batch-wide single-step decode:
    drafts are accepted, output stays schema-valid, and masked-step
    accounting still fires."""
    async def run():
        eng = _engine(True)
        try:
            constrained = [
                eng.complete(
                    _ids(eng, f"emit array {i}"),
                    SamplingParams(temperature=0.0, max_tokens=64,
                                   constraint={"type": "json_schema",
                                               "schema": ARRAY_SCHEMA}),
                )
                for i in range(2)
            ]
            free = [
                eng.complete(_ids(eng, f"free {i}"),
                             SamplingParams(temperature=0.0, max_tokens=24))
                for i in range(2)
            ]
            results = await asyncio.gather(*constrained, *free)
            return results, eng.core.metrics, eng.core.spec_info()
        finally:
            eng.shutdown()

    results, metrics, info = asyncio.run(run())
    for r in results[:2]:
        assert r.finish_reason == "stop"
        jsonschema.validate(json.loads(r.text), ARRAY_SCHEMA)
    assert metrics.constraint_violations_total == 0
    # the verify path ran with grammar masks applied (each masked verify
    # dispatch counts exactly like a masked single-step decode)
    assert metrics.masked_decode_steps_total > 0
    assert metrics.spec_verify_steps_total > 0
    # multi-token for constrained output: accepted drafts mean at least one
    # step emitted >= 2 tokens for a speculating (constrained) slot
    assert metrics.spec_accepted_tokens_total > 0
    assert info["acceptance_rate"] > 0


def test_constrained_schema_valid_mixed_with_object_schema():
    """Object-schema JSON under speculation: output identical to the
    non-speculative constrained baseline under greedy decode."""
    async def collect(spec):
        eng = _engine(spec)
        try:
            r = await eng.complete(
                _ids(eng, "produce json"),
                SamplingParams(temperature=0.0, max_tokens=64,
                               constraint={"type": "json_schema",
                                           "schema": SCHEMA}),
            )
            return r.text, r.finish_reason
        finally:
            eng.shutdown()

    base = asyncio.run(collect(False))
    spec = asyncio.run(collect(True))
    assert spec == base
    jsonschema.validate(json.loads(spec[0]), SCHEMA)


def test_verify_steps_have_their_own_kind_and_phase_records():
    """stepstats: verify dispatches record kind='verify' with a draft phase,
    keep their own EMA baseline, and the spec series reach /metrics."""
    async def run():
        eng = _engine(True)
        try:
            await eng.complete(
                _ids(eng), SamplingParams(temperature=0.0, max_tokens=120)
            )
            snap = eng.core.step_stats.snapshot(limit=256)
            stats = eng.core.stats()
            text = eng.core.metrics.render(
                queue_depth=stats.queued, active_slots=stats.active_slots,
                num_slots=stats.num_slots,
            )
            return snap, text
        finally:
            eng.shutdown()

    snap, exposition = asyncio.run(run())
    kinds = {r["kind"] for r in snap["records"]}
    assert "verify" in kinds
    assert "verify" in snap["ema_step_s"]
    verify = [r for r in snap["records"] if r["kind"] == "verify"]
    assert all("draft" in r["phases_s"] for r in verify)
    # emitted tokens ride the record (decode-tokens accounting for MFU)
    assert any(r["tokens"] >= 1 for r in verify)
    for series in ("llmlb_engine_spec_verify_steps_total",
                   "llmlb_engine_spec_draft_tokens_total",
                   "llmlb_engine_spec_accepted_tokens_total",
                   "llmlb_engine_spec_emitted_tokens_total"):
        assert series in exposition


def test_spec_info_surfaces_in_health():
    eng = _engine(True)
    try:
        health = eng.health()
        assert health["spec"]["enabled"] is True
        assert health["spec"]["available"] is True
        assert health["spec"]["max_draft_tokens"] >= 1
    finally:
        eng.shutdown()


# --------------------------------------------------------- page rollback edges


def _paged_core(**kw):
    cfg = get_preset("debug-tiny")
    kw.setdefault("num_slots", 2)
    kw.setdefault("slot_capacity", 64)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("kv_page_size", 4)
    kw.setdefault("prefix_cache", False)
    return EngineCore(cfg, **kw)


def test_trim_releases_over_allocated_pages_exactly_once():
    core = _paged_core()
    pool = core.page_pool
    free0 = pool.available()
    pages = core._try_reserve_pages(4)  # covers 16 tokens at page_size 4
    core._assign_slot_pages(0, (), pages)
    core._seq_lens[0] = 9  # committed 9 tokens; next write at 9
    # keep pages covering committed+1 = 10 tokens -> 3 pages, release 1
    core._trim_slot_pages(0, 10)
    assert pool.available() == free0 - 3
    assert len(core._slot_pages[0]) == 3
    assert core._block_tables[0, 3] == 0
    # trimming again is a no-op, NOT a double free
    core._trim_slot_pages(0, 10)
    assert pool.available() == free0 - 3
    # freeing the slot releases the remaining pages exactly once
    core._free_slot_kv(0)
    assert pool.available() == free0


def test_trim_page_boundary_rollback_empties_last_page():
    """Rollback landing exactly on a page boundary: the last page holds only
    rejected-draft garbage and must be released in full."""
    core = _paged_core()
    pool = core.page_pool
    free0 = pool.available()
    pages = core._try_reserve_pages(3)  # 12 tokens of room
    core._assign_slot_pages(0, (), pages)
    core._seq_lens[0] = 7  # committed 7; keep = pages_for(8) = 2 pages
    core._trim_slot_pages(0, 8)
    assert len(core._slot_pages[0]) == 2
    assert pool.available() == free0 - 2
    core._free_slot_kv(0)
    assert pool.available() == free0


def test_spec_traffic_leaves_page_pool_clean():
    """End to end on a tiny page size: rejected drafts across many verify
    steps must leave zero leaked or double-freed pages once traffic drains
    (the engine would raise PageError mid-loop on any double free)."""
    async def run():
        eng = Engine.from_preset(
            "debug-tiny", spec_decode=True, num_slots=4, slot_capacity=128,
            prefill_buckets=(16, 32), kv_page_size=4,
            prefix_cache=False,
        )
        try:
            outs = await asyncio.gather(*(
                eng.complete(_ids(eng, f"{PROMPT} v{i}"),
                             SamplingParams(temperature=0.0, max_tokens=40))
                for i in range(6)
            ))
            assert all(r.finish_reason in ("stop", "length") for r in outs)
            assert eng.core.metrics.spec_verify_steps_total > 0
            # drained: every page back in the pool
            return eng.core.page_pool.used()
        finally:
            eng.shutdown()

    assert asyncio.run(run()) == 0


async def test_engine_http_speculative_knob_and_validation():
    """The OpenAI-dialect `speculative` body knob reaches the scheduler
    (spec engages on an engine defaulting OFF) and malformed knobs 400
    with the offending field named."""
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app

    eng = _engine(False)  # engine default off; the request opts in
    client = TestClient(TestServer(create_engine_app(eng, owns_engine=False)))
    await client.start_server()
    try:
        payload = {
            "model": eng.model_id,
            "messages": [{"role": "user", "content": PROMPT}],
            "max_tokens": 100, "temperature": 0.0,
            "speculative": {"enabled": True, "max_draft_tokens": 4},
        }
        resp = await client.post("/v1/chat/completions", json=payload)
        assert resp.status == 200, await resp.text()
        await resp.json()
        assert eng.core.metrics.spec_verify_steps_total > 0

        for bad in ("yes", {"enabled": "yes"}, {"max_draft_tokens": 0},
                    {"max_draft_tokens": True}):
            resp = await client.post("/v1/chat/completions", json={
                **payload, "speculative": bad,
            })
            assert resp.status == 400, bad
            err = await resp.json()
            assert "speculative" in err["error"]["message"]
    finally:
        await client.close()
        eng.shutdown()
