"""Multi-LoRA serving (llmlb_tpu/lora, docs/lora.md).

The acceptance invariant: a MIXED-adapter batch (several adapters plus an
adapter-free row) decodes together in single dispatches — no per-adapter
serialization — with each row's output byte-identical to a solo run of that
adapter, greedy and seeded, over both KV layouts; and an engine with LoRA
enabled but unused is bit-identical to a LoRA-free engine (the
test_quantize_off_bit_identical contract, adapter edition).
"""

import asyncio
import threading

import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.lora import save_adapter
from tests.support import collect as _drain

CFG = get_preset("debug-tiny")
PROMPT = [3, 5, 7, 9, 11, 2, 4, 6]
ADAPTERS = ("acme", "globex", "initech")


@pytest.fixture(scope="module")
def lora_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("adapters")
    for n in ADAPTERS:
        save_adapter(str(d), n, CFG, rank=4)
    return str(d)


def _run(core, lora=None, seed=None, temp=0.0, max_tokens=12,
         prompt=PROMPT):
    r = Request(prompt_ids=list(prompt),
                sampling=SamplingParams(temperature=temp, seed=seed,
                                        max_tokens=max_tokens, lora=lora))
    core.submit(r)
    return _drain(r)[0]


def _core(lora_dir=None, *, kv_page_size=16, num_slots=5, **kw):
    return EngineCore(CFG, num_slots=num_slots, slot_capacity=128,
                      prefill_buckets=(8, 16), kv_page_size=kv_page_size,
                      seed=0, lora_dir=lora_dir, **kw)


@pytest.mark.parametrize("kv_page", [16, 4], ids=["paged", "paged-page4"])
def test_mixed_adapter_batch_byte_identical_to_solo(lora_dir, kv_page):
    """≥3 adapters + 1 adapter-free row decode TOGETHER; every row matches
    its solo run exactly. Greedy and seeded-stochastic (one engine session
    covers both — the jit compiles dominate tier-1 cost), at a page twice
    the smallest prefill bucket and at one half of it.
    """
    core = _core(lora_dir, kv_page_size=kv_page)
    core.start()
    try:
        for kw in ({}, dict(temp=0.8, seed=77)):
            solo = {n: _run(core, n, **kw) for n in (None,) + ADAPTERS}
            # distinct adapters must actually produce distinct streams, or
            # the byte-identity assertions below would be vacuous
            assert len({tuple(v) for v in solo.values()}) == 4

            steps_before = core.metrics.decode_step.n
            results: dict = {}

            def worker(name, kw=kw):
                results[name] = _run(core, name, **kw)

            threads = [threading.Thread(target=worker, args=(n,))
                       for n in (None,) + ADAPTERS]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for name in (None,) + ADAPTERS:
                assert results[name] == solo[name], f"row {name} diverged"
            # decoded together: the step records show 4-wide decode
            # dispatches, and the whole batch took far fewer dispatches
            # than 4 solo runs would (per-adapter serialization would
            # double the step count)
            occupancies = [
                r["active_slots"]
                for r in core.step_stats.snapshot(limit=512)["records"]
                if r["kind"] == "decode"
            ]
            assert max(occupancies, default=0) >= 4, (
                "mixed-adapter batch never decoded 4-wide"
            )
            mixed_steps = core.metrics.decode_step.n - steps_before
            assert mixed_steps <= 20, (
                f"{mixed_steps} decode dispatches for a 12-token 4-row "
                "batch — adapters are being serialized"
            )
    finally:
        core.stop()


def test_lora_enabled_but_unused_bit_identical(lora_dir):
    """The pinned default-off contract, adapter edition: an engine with the
    adapter pool compiled in but NO adapter on any request emits exactly
    the streams a LoRA-free engine does (identity row 0 adds exact 0.0)."""
    plain = _core(None)
    plain.start()
    try:
        ref_greedy = _run(plain, None)
        ref_seeded = _run(plain, None, seed=9, temp=0.9)
    finally:
        plain.stop()
    withlora = _core(lora_dir)
    withlora.start()
    try:
        assert _run(withlora, None) == ref_greedy
        assert _run(withlora, None, seed=9, temp=0.9) == ref_seeded
    finally:
        withlora.stop()


def test_adapter_hot_load_evict_under_pool_pressure(lora_dir):
    """Pool of 2 serving 3 adapters sequentially: the LRU idle adapter
    evicts, the request still serves, and outputs stay solo-identical
    after reload (eviction must not corrupt rows)."""
    core = _core(lora_dir, lora_max_adapters=2)
    core.start()
    try:
        first = _run(core, "acme")
        _run(core, "globex")
        _run(core, "initech")  # evicts one idle adapter
        assert core.metrics.lora_evictions_total >= 1
        assert _run(core, "acme") == first  # reload is exact
        assert core.metrics.lora_loads_total >= 4
    finally:
        core.stop()


def test_prefix_cache_never_shared_across_adapters(lora_dir):
    """Two adapters (and the base model) sharing one prompt must never
    share cached KV: each first use of the prompt under a new adapter is
    a prefix MISS, and outputs stay solo-identical afterward. An
    adapter-blind hit would silently serve adapter A's prompt KV to
    adapter B (the prompt KV depends on wq/wk/wv deltas)."""
    core = _core(lora_dir, min_prefix_len=8)
    core.start()
    prompt = list(range(2, 50))  # long enough to cache (align 16)
    try:
        base_1 = _run(core, None, prompt=prompt)
        hits0 = core.metrics.prefix_hits_total
        base_2 = _run(core, None, prompt=prompt)
        assert core.metrics.prefix_hits_total == hits0 + 1  # warm: base hit
        assert base_2 == base_1

        a_1 = _run(core, "acme", prompt=prompt)
        assert core.metrics.prefix_hits_total == hits0 + 1, (
            "adapter request HIT the base model's cached prompt KV"
        )
        a_2 = _run(core, "acme", prompt=prompt)  # same-adapter reuse is fine
        assert core.metrics.prefix_hits_total == hits0 + 2
        assert a_2 == a_1

        b_1 = _run(core, "globex", prompt=prompt)
        assert core.metrics.prefix_hits_total == hits0 + 2, (
            "adapter B HIT adapter A's (or base) cached prompt KV"
        )
        assert b_1 != a_1  # distinct adapters, distinct continuations
    finally:
        core.stop()


def test_unknown_adapter_rejected_before_slot(lora_dir):
    core = _core(lora_dir)
    try:
        with pytest.raises(ValueError, match="'lora' names unknown adapter"):
            core.submit(Request(prompt_ids=PROMPT,
                                sampling=SamplingParams(lora="nope")))
        with pytest.raises(ValueError, match="not enabled"):
            plain = _core(None)
            try:
                plain.submit(Request(prompt_ids=PROMPT,
                                     sampling=SamplingParams(lora="acme")))
            finally:
                plain.stop()
    finally:
        core.stop()


async def _server_client(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app

    client = TestClient(TestServer(create_engine_app(engine,
                                                     owns_engine=False)))
    await client.start_server()
    return client


def test_server_surfaces_and_400s(lora_dir):
    """HTTP layer: unknown adapter → 400 naming the field (chat and
    completions), model-suffix selection works, /v1/models advertises the
    lora capability + resident adapters, /metrics renders the lora
    family, /api/health carries the lora block."""
    engine = Engine.from_preset(
        "debug-tiny", num_slots=4, slot_capacity=128,
        prefill_buckets=(8, 16), seed=0, lora_dir=lora_dir,
    )

    async def run():
        client = await _server_client(engine)
        try:
            msgs = [{"role": "user", "content": "hi"}]
            resp = await client.post("/v1/chat/completions", json={
                "model": "debug-tiny", "messages": msgs, "lora": "nope",
                "max_tokens": 4,
            })
            assert resp.status == 400
            body = await resp.json()
            assert "'lora'" in body["error"]["message"]

            resp = await client.post("/v1/completions", json={
                "model": "debug-tiny:nope", "prompt": "hi",
                "max_tokens": 4,
            })
            assert resp.status == 400
            assert "'lora'" in (await resp.json())["error"]["message"]

            # suffix selection serves and differs from the base model
            resp = await client.post("/v1/chat/completions", json={
                "model": "debug-tiny:acme", "messages": msgs,
                "max_tokens": 8, "temperature": 0,
            })
            assert resp.status == 200
            with_adapter = (await resp.json())["choices"][0]["message"]
            resp = await client.post("/v1/chat/completions", json={
                "model": "debug-tiny", "messages": msgs,
                "max_tokens": 8, "temperature": 0,
            })
            base = (await resp.json())["choices"][0]["message"]
            assert with_adapter["content"] != base["content"]

            models = await (await client.get("/v1/models")).json()
            by_id = {m["id"]: m for m in models["data"]}
            assert "lora" in by_id["debug-tiny"]["capabilities"]
            assert "debug-tiny:acme" in by_id  # resident → advertised
            assert by_id["debug-tiny:acme"]["lora"] == "acme"

            health = await (await client.get("/api/health")).json()
            assert health["lora"]["enabled"]
            assert "acme" in health["lora"]["resident"]

            metrics = await (await client.get("/metrics")).text()
            assert "llmlb_engine_lora_loaded 1" in metrics
            assert 'llmlb_engine_lora_requests_total{adapter="acme"}' \
                in metrics
            assert "llmlb_engine_lora_load_seconds_count" in metrics
        finally:
            await client.close()

    try:
        asyncio.run(run())
    finally:
        engine.core.stop()


def test_spec_decode_with_adapter_token_identical(lora_dir):
    """Speculative decoding on: a repetitive prompt drafts n-grams, and the
    adapter stream with spec ON equals the same engine-config stream with
    spec OFF (verify dispatches carry the adapter indices)."""
    prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]
    on = _core(lora_dir, spec_decode=True)
    on.start()
    try:
        got_on = _run(on, "acme", prompt=prompt, max_tokens=16)
        assert on.metrics.spec_verify_steps_total > 0
    finally:
        on.stop()
    off = _core(lora_dir, spec_decode=False)
    off.start()
    try:
        got_off = _run(off, "acme", prompt=prompt, max_tokens=16)
    finally:
        off.stop()
    assert got_on == got_off
