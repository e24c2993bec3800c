"""A shortcut-connected mixture over double layers (models/longcat_flash.py,
docs/longcat-flash.md) through the continuous-batching engine at a CI size:
the same scheduler loop, burst, page allocator and prefix cache as every
family; greedy tokens equal to the plain reference's argmax with more
requests than slots, prompts longer than the widest bucket (the deferred
branch through chunked extends beside rows that decode in bursts) and a
prefix-cache hit; the three assignment counters on the step records, in
`/api/health`'s metrics and in the exposition, accounting for EVERY
assignment; the page gauges counting attention sub-layers; and what the
family does not serve refused by name at engine start."""

import asyncio

import jax
import numpy as np
import pytest

from benchmark.reference import longcat_flash as ref
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import (
    EngineCore,
    Request,
    SamplingParams,
    kv_page_bytes,
)
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.models import deepseek_v3, longcat_flash
from tests.engine.test_shortcut_family import HF
from tests.support import collect_events

CFG = get_preset("debug-longcat-tiny")
PARAMS = longcat_flash.init_params(CFG, jax.random.PRNGKey(0))
ARGS = dict(num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
            kv_page_size=16, decode_burst=4, eos_id=-1)
MARGIN = 1e-3  # of the reference's top two logits: wider than rounding


@pytest.fixture(scope="module")
def core():
    core = EngineCore(CFG, PARAMS, **ARGS)
    core.start()
    yield core
    core.stop()


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(8, 500, size=n).tolist()


def _submit(core, prompt, max_tokens):
    return core.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
        max_tokens=max_tokens, temperature=0.0)))


def _assert_greedy(prompt, tokens):
    """The tokens are the reference's argmax, one forward pass over prompt
    + tokens, wherever its top two logits are not a tie."""
    logits, _ = ref.forward(PARAMS, HF, np.asarray(prompt + tokens))
    rows = np.asarray(logits)[len(prompt) - 1:-1]
    top = np.sort(rows, axis=-1)
    wide = top[:, -1] - top[:, -2] > MARGIN
    assert wide.sum() >= len(tokens) - 1
    assert (np.argmax(rows, -1)[wide] == np.asarray(tokens)[wide]).all(), (
        len(prompt), tokens, np.argmax(rows, -1).tolist())


def test_tokens_equal_the_references_argmax_on_every_path(core):
    """Seven requests on four slots, all at once: 70 and 40 tokens prefill
    in chunks of 32 while other rows decode in bursts of 4, the short ones
    are admitted as a group, and the fifth to seventh take a slot another
    request left. Then the longest again: a prefix-cache hit extends behind
    its cached pages."""
    assert core.family is longcat_flash and core.prefix_cache is not None
    prompts = [_prompt(n, 10 + n) for n in (17, 40, 5, 70, 33, 20, 9)]
    requests = [_submit(core, p, 14) for p in prompts]
    for prompt, request in zip(prompts, requests):
        tokens, reason, _ = collect_events(request, 300)
        assert reason == "length" and len(tokens) == 14
        _assert_greedy(prompt, tokens)
    hits = core.metrics.summary()["prefix_hits_total"]
    tokens, _, _ = collect_events(_submit(core, prompts[3], 9), 300)
    assert core.metrics.summary()["prefix_hits_total"] == hits + 1
    _assert_greedy(prompts[3], tokens)


def test_the_three_counters_account_for_every_assignment(core):
    """zero + held + elsewhere = rows x experts_per_token a layer and step
    (at the cell's size: 384 at 32 rows), on every record."""
    recs = core.step_stats.snapshot(limit=512)["records"]
    layers, k = CFG.num_layers, CFG.experts_per_token
    for kind in ("decode", "prefill"):
        counted = [r for r in recs if r["kind"] == kind]
        assert counted, kind
        for r in counted:
            assert {"zero_assignments", "assignments_elsewhere",
                    "expert_assignments", "experts_touched",
                    "expert_load_max"} <= set(r)
            assert (r["zero_assignments"] + r["expert_assignments"]
                    + r["assignments_elsewhere"]) == r["tokens"] * layers * k
            assert 0 <= r["experts_touched"] <= r["expert_assignments"]
    total = {name: sum(r[name] for r in recs) for name in (
        "zero_assignments", "expert_assignments", "assignments_elsewhere")}
    assert all(total.values())  # 4 of 12 outputs each, seeded routing
    m = core.metrics.summary()
    assert m["moe_zero_assignments_total"] >= total["zero_assignments"]
    assert m["moe_assignments_elsewhere_total"] >= total[
        "assignments_elsewhere"]
    assert m["moe_expert_assignments_total"] >= total["expert_assignments"]
    assert m["moe_experts_touched_total"] > 0
    # the load histogram is over the 4 experts HELD, a row a layer
    hist = np.asarray(m["moe_expert_load_hist"])
    assert hist.shape == (layers, len(deepseek_v3.LOAD_BUCKETS) + 1)
    text = core.metrics.render(queue_depth=0, active_slots=0, num_slots=4,
                               kv_cache=core.kv_cache_info())
    for name in ("llmlb_engine_moe_zero_assignments_total",
                 "llmlb_engine_moe_assignments_elsewhere_total",
                 "llmlb_engine_moe_expert_assignments_total",
                 "llmlb_engine_moe_experts_touched_total",
                 'llmlb_engine_moe_expert_load_experts_total{layer="1",'
                 'bucket="0"}'):
        assert name in text, name


def test_page_gauges_count_the_attention_sub_layers(core):
    cell = (CFG.kv_lora_rank + deepseek_v3.ROPE_CELL) * 4  # float32 preset
    info = core.kv_cache_info()
    assert info["bytes_per_token"] == 2 * CFG.num_layers * cell
    assert info["bytes_per_page"] == kv_page_bytes(CFG, 16)
    assert info["hbm_bytes"] == core.cache_k.nbytes + core.cache_v.nbytes
    assert core.cache_k.shape[0] == 2 * CFG.num_layers
    assert core._kv_wire_cell() is None  # ships nothing: replays instead


def test_the_engine_streams_the_family_and_health_has_the_counters():
    engine = Engine.from_preset("debug-longcat-tiny", **{
        k: v for k, v in ARGS.items() if k != "eos_id"}, seed=0)
    try:
        assert engine.core.family is longcat_flash

        async def run():
            ids = engine.tokenizer.encode("a shortcut across two attentions")
            out = await engine.complete(
                ids, SamplingParams(max_tokens=9, temperature=0.0))
            assert out.completion_tokens == 9

        asyncio.run(run())
        metrics = engine.core.metrics.summary()
        assert metrics["moe_zero_assignments_total"] > 0
        assert metrics["moe_counted_steps_total"] > 0
    finally:
        engine.shutdown()


@pytest.mark.parametrize("kwargs,error,match", [
    (dict(quantize="weights"), NotImplementedError, "int8 weights"),
    (dict(quantize="all"), NotImplementedError, "int8 weights"),
    (dict(quantize="kv"), NotImplementedError, "int8 latent page pool"),
    (dict(lora_dir="/nonexistent-adapters"), NotImplementedError,
     "adapter pools"),
])
def test_what_the_family_does_not_serve_is_refused_at_start_up(kwargs, error,
                                                               match):
    with pytest.raises(error, match=match):
        EngineCore(CFG, None, eos_id=-1, num_slots=2, slot_capacity=64,
                   prefill_buckets=(16,), kv_page_size=16, **kwargs)
