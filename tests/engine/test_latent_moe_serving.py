"""A latent-attention mixture (models/deepseek_v3.py) through the
continuous-batching engine: the same scheduler loop, burst, page allocator,
block tables and prefix cache as every family; the expert-load counters on
the step records and in the metrics, carried out in the burst's one fetch;
the page gauges asking the family for a token's bytes; an int8 latent pool
refused at start-up."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.programs import (
    _pack_step_counters,
    _unpack_step_counters,
)
from llmlb_tpu.engine.scheduler import (
    EngineCore,
    SamplingParams,
    kv_page_bytes,
)
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.models import deepseek_v3

CFG = get_preset("debug-mla-tiny")
GREEDY = dict(temperature=0.0)


@pytest.fixture(scope="module")
def engine():
    eng = Engine.from_preset(
        "debug-mla-tiny", num_slots=4, slot_capacity=128,
        prefill_buckets=(16, 32), kv_page_size=16, decode_burst=4, seed=0)
    yield eng
    eng.shutdown()


def test_the_engine_serves_the_family_on_the_one_loop(engine):
    assert engine.core.family is deepseek_v3
    assert engine.core.cache_k.shape[-1] == CFG.kv_lora_rank
    assert engine.core.cache_v.shape[-1] == deepseek_v3.ROPE_CELL

    async def run():
        ids = engine.tokenizer.encode("latent attention, routed experts " * 2)
        outs = await asyncio.gather(*[
            engine.complete(ids[:20 + 7 * i],
                            SamplingParams(max_tokens=20, **GREEDY))
            for i in range(6)])
        assert [o.completion_tokens for o in outs] == [20] * 6
        a = await engine.complete(ids, SamplingParams(max_tokens=12, **GREEDY))
        hits = engine.core.metrics.summary()["prefix_hits_total"]
        b = await engine.complete(ids, SamplingParams(max_tokens=12, **GREEDY))
        assert a.text == b.text
        # the second asks the prefix cache: shared latent pages, no copy
        assert engine.core.metrics.summary()["prefix_hits_total"] == hits + 1

    asyncio.run(run())


def test_step_records_and_metrics_carry_the_expert_load(engine):
    recs = engine.core.step_stats.snapshot(limit=256)["records"]
    lm, k = CFG.num_moe_layers, CFG.experts_per_token
    for kind in ("decode", "prefill"):
        counted = [r for r in recs if r["kind"] == kind]
        assert counted and all(
            {"experts_touched", "expert_assignments", "expert_load_max"}
            <= set(r) for r in counted), kind
    for r in recs:
        if r["kind"] == "decode":  # rows x steps x layers x k, live rows only
            assert r["expert_assignments"] == r["tokens"] * lm * k
        if r["kind"] == "prefill":
            assert r["expert_assignments"] == r["tokens"] * lm * k
        assert 0 < r["experts_touched"] <= r["expert_assignments"]
        assert 1 <= r["expert_load_max"] <= r["tokens"]
    m = engine.core.metrics.summary()
    assert m["moe_counted_steps_total"] == len(recs) or len(recs) == 256
    assert m["moe_expert_assignments_total"] >= sum(
        r["expert_assignments"] for r in recs)
    hist = np.asarray(m["moe_expert_load_hist"])
    assert hist.shape == (lm, len(deepseek_v3.LOAD_BUCKETS) + 1)
    text = engine.core.metrics.render(queue_depth=0, active_slots=0,
                                      num_slots=4,
                                      kv_cache=engine.core.kv_cache_info())
    for name in ("llmlb_engine_moe_experts_touched_total",
                 "llmlb_engine_moe_expert_assignments_total",
                 "llmlb_engine_moe_expert_load_max",
                 'llmlb_engine_moe_expert_load_experts_total{layer="1",bucket="0"}',
                 "llmlb_engine_kv_bytes_per_token"):
        assert name in text, name


def test_page_gauges_ask_the_family_for_a_tokens_bytes(engine):
    cell = (CFG.kv_lora_rank + deepseek_v3.ROPE_CELL) * 4  # float32 preset
    info = engine.core.kv_cache_info()
    assert info["bytes_per_token"] == CFG.num_layers * cell
    assert info["bytes_per_page"] == 16 * CFG.num_layers * cell
    assert info["bytes_per_page"] == kv_page_bytes(CFG, 16)
    assert info["hbm_bytes"] == engine.core.kv_num_pages * info["bytes_per_page"]
    pool = engine.core.cache_k.nbytes + engine.core.cache_v.nbytes
    assert info["hbm_bytes"] == pool
    # a GQA family's figure is what it was: K and V of every kv head
    dense = get_preset("debug-tiny")
    assert kv_page_bytes(dense, 16) == (
        dense.num_layers * 16 * dense.num_kv_heads * 2 * dense.head_dim_ * 4)


def test_the_latent_pool_has_no_wire_form_and_ships_nothing(engine):
    from llmlb_tpu.engine.kv_transfer import KV_WIRE_VERSION, KVWireHeader

    core = engine.core
    assert core._kv_wire_cell() is None
    header = KVWireHeader(version=KV_WIRE_VERSION, layers=CFG.num_layers,
                          page_size=16, num_kv_heads=1, head_dim=160,
                          kv_dtype="float32", tokens=3, num_pages=1)
    assert core.kv_restore_reason(header) == "geometry"


def test_an_int8_latent_pool_is_refused_at_start_up():
    with pytest.raises(NotImplementedError, match="int8 latent page pool"):
        EngineCore(CFG, None, eos_id=-1, num_slots=2, slot_capacity=64,
                   prefill_buckets=(16,), kv_page_size=16,
                   quantize="kv")


def test_counters_ride_behind_the_tokens_in_one_array():
    shapes = {"a_sum": (), "b_max": (), "hist": (2, 3)}
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    stats = {"a_sum": jnp.asarray([1, 2]), "b_max": jnp.asarray([5, 3]),
             "hist": jnp.asarray([[[1, 0, 2], [0, 0, 1]],
                                  [[1, 1, 1], [2, 0, 0]]])}
    flat = np.asarray(_pack_step_counters(tokens, stats, shapes, ("b_max",)))
    assert flat.shape == (12 + 1 + 1 + 6,)
    got, counters = _unpack_step_counters(flat, 3, 4, shapes)
    np.testing.assert_array_equal(got, np.asarray(tokens))
    assert counters == {"a_sum": 3, "b_max": 5,
                        "hist": [[2, 1, 3], [2, 0, 1]]}


@pytest.mark.parametrize("kwargs,match", [
    (dict(quantize="weights"), "int8 weights"),
    (dict(quantize="all"), "int8 weights"),
    (dict(lora_dir="/nonexistent-adapters"), "adapter pools"),
])
def test_what_the_family_does_not_serve_is_refused_at_start_up(kwargs, match):
    with pytest.raises(NotImplementedError, match=match):
        EngineCore(CFG, None, eos_id=-1, num_slots=2, slot_capacity=64,
                   prefill_buckets=(16,), kv_page_size=16, **kwargs)
