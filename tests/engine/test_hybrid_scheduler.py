"""A family with a recurrent state per slot (models/nemotron_h.py,
docs/hybrid-state.md) through the continuous-batching engine at a CI size:
greedy tokens equal to the plain reference's argmax with more requests than
slots (a slot used again), prompts longer than the widest bucket (a chunked
prefill beside rows that decode in bursts), prompts admitted as one prefill
group; park and resume token-identical; the counters on the step records,
in the totals and in the engine's info; `Engine.stream` end to end; and what
would serve the state wrong refused by name at engine start."""

import asyncio
import time

import jax
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.models import nemotron_h
from tests.engine.test_hybrid_family import HF
from tests.support import RUN_LIFTED, InlineLoop, collect_events

CFG = get_preset("debug-nemotron-h-tiny")
PARAMS = nemotron_h.init_params(CFG, jax.random.PRNGKey(0))
ARGS = dict(num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
            kv_page_size=16, decode_burst=4, eos_id=-1)
MARGIN = 1e-3  # of the reference's top two logits: wider than rounding


def _core(**kw):
    core = EngineCore(CFG, PARAMS, **{**ARGS, **kw})
    core.start()
    return core


@pytest.fixture(scope="module")
def core():
    core = _core()
    yield core
    core.stop()


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(8, 500, size=n).tolist()


def _submit(core, prompt, max_tokens, **sampling):
    sampling.setdefault("temperature", 0.0)
    return core.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
        max_tokens=max_tokens, **sampling)))


def _assert_greedy(prompt, tokens, params=PARAMS):
    """The tokens are the reference's argmax, one forward pass over prompt
    + tokens, wherever its top two logits are not a tie."""
    logits, _ = ref.forward(params, HF, np.asarray(prompt + tokens))
    rows = np.asarray(logits)[len(prompt) - 1:-1]
    top = np.sort(rows, axis=-1)
    wide = top[:, -1] - top[:, -2] > MARGIN
    assert wide.sum() >= len(tokens) - 1
    assert (np.argmax(rows, -1)[wide] == np.asarray(tokens)[wide]).all(), (
        len(prompt), tokens, np.argmax(rows, -1).tolist())


def test_tokens_equal_the_references_argmax_on_every_path_of_the_state(core):
    """Seven requests on four slots, all at once: 70 and 40 tokens prefill
    in chunks of 32 while other rows decode in bursts of 4 (a burst steps
    every slot: the prefilling slot's state must stay), the short ones are
    admitted as a group, and the fifth to seventh take a slot another
    request's state was left in."""
    prompts = [_prompt(n, 10 + n) for n in (17, 40, 5, 70, 33, 20, 9)]
    requests = [_submit(core, p, 14) for p in prompts]
    for prompt, request in zip(prompts, requests):
        tokens, reason, _ = collect_events(request, 300)
        assert reason == "length" and len(tokens) == 14
        _assert_greedy(prompt, tokens)
    recs = core.step_stats.snapshot(limit=512)["records"]
    extends = [r for r in recs if r["kind"] == "prefill"
               and r.get("scan_tokens") and r["tokens"] == 32]
    assert extends, "no chunk of a long prompt was recorded"


def test_the_step_records_and_the_totals_carry_the_counters(core):
    recs = core.step_stats.snapshot(limit=512)["records"]
    lm, k = CFG.num_moe_layers, CFG.experts_per_token
    decodes = [r for r in recs if r["kind"] == "decode"]
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert decodes and prefills
    for r in decodes:  # rows x steps of the burst, the live rows alone
        assert r["state_rows"] == r["tokens"]
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == r["tokens"] * lm * k)
        assert 0 < r["experts_touched"] <= lm * CFG.held_experts[1] * 4
        assert "scan_tokens" not in r
    for r in prefills:
        assert r["scan_tokens"] == r["tokens"] and r["scan_chunks"] >= 1
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == r["tokens"] * lm * k)
    m = core.metrics.summary()
    assert m["ssm_state_rows_total"] >= sum(r["state_rows"] for r in recs)
    assert m["moe_assignments_elsewhere_total"] >= sum(
        r["assignments_elsewhere"] for r in recs) > 0
    info = core.quant_info()
    assert info["state_bytes"] == 4 * nemotron_h.state_slot_bytes(CFG) > 0


def test_park_and_resume_is_token_identical():
    """One slot: a low-priority request parks mid-generation for a
    high-priority arrival and resumes by replaying prompt + tokens (nothing
    of the state is kept: scheduler.ParkedState); both streams are the
    reference's."""
    core = _core(num_slots=1, decode_burst=2)
    try:
        victim_prompt, other_prompt = _prompt(18, 80), _prompt(9, 81)
        victim = _submit(core, victim_prompt, 30, priority=2)
        deadline = time.monotonic() + 60
        while core.slots[0].generated < 6 and time.monotonic() < deadline:
            time.sleep(0.005)
        other = core.submit(Request(
            prompt_ids=other_prompt, sampling=SamplingParams(
                max_tokens=7, temperature=0.0, priority=0)))
        got_other, _, _ = collect_events(other, 300)
        got_victim, reason, _ = collect_events(victim, 300)
        assert core.metrics.preemptions_total >= 1
        assert reason == "length" and len(got_victim) == 30
        _assert_greedy(other_prompt, got_other)
        _assert_greedy(victim_prompt, got_victim)
    finally:
        core.stop()


def test_a_finished_rows_state_is_rewritten_by_the_next_activation():
    """A row that meets its EOS inside burst n is still a row of burst n+1,
    which left before n was emitted: n+1 advances the slot's state past the
    end. The next request in that slot starts its prefill from zeros,
    dispatched after n+1 (docs/kv-cache.md "A burst in flight and pages
    already released"): its tokens, and those of the row that decoded
    beside both, are the reference's."""
    ending, beside, taking = _prompt(11, 90), _prompt(14, 91), _prompt(9, 92)

    def serve(eos):
        core = EngineCore(CFG, PARAMS, **{**ARGS, "eos_id": eos,
                                          "num_slots": 2})
        loop = InlineLoop(core, queued_run=RUN_LIFTED)  # 2 and 3 queue
        requests = [Request(prompt_ids=p, sampling=SamplingParams(
            temperature=0.0, max_tokens=n))
            for p, n in ((ending, 30), (beside, 30), (taking, 10))]
        core.pending.put(requests[0])
        core.pending.put(requests[1])
        # the EOS is in burst 2; burst 3 left with the row in it before 2
        # was even fetched (both slots held: queued behind it); the arrival
        # comes while 3 is in flight
        loop.during[3] = [lambda: core.pending.put(requests[2])]
        loop.run()
        return [collect_events(r, None) for r in requests], loop

    streams = [tokens for tokens, _f, _s in serve(-1)[0]]
    probe, everything = streams[0], sum(streams, [])
    # inside burst 2 (decode tokens 5 to 8), and no other row's token
    at = next(i for i in (5, 6, 7) if everything.count(probe[i]) == 1)
    ((first, reason, _s), (second, _, _), (third, _, _)), loop = serve(
        probe[at])
    assert reason == "stop" and first == probe[:at]
    records = loop.decode_records()
    assert records[2]["queued_behind"]
    assert records[2]["active_slots"] == 2  # the ended row among them
    assert "0" in records[3]["request_ids"]  # its slot, taken at once
    assert len(second) == 30 and len(third) == 10
    _assert_greedy(beside, second)
    _assert_greedy(taking, third)


def test_the_engine_streams_the_family_end_to_end():
    engine = Engine.from_preset("debug-nemotron-h-tiny", **{
        k: v for k, v in ARGS.items() if k != "eos_id"}, seed=0)
    try:
        assert engine.core.family is nemotron_h
        assert engine.core.prefix_cache is None and not engine.core.kv_ship

        async def run():
            ids = engine.tokenizer.encode("a state per slot beside the pages")
            tokens: list[int] = []
            final = None
            async for delta in engine.stream(
                    ids, SamplingParams(max_tokens=10, temperature=0.0)):
                tokens.extend(delta.token_ids or [])
                final = delta
            assert final.finish_reason in ("length", "stop")
            return ids, tokens

        ids, tokens = asyncio.run(run())
        assert 0 < len(tokens) <= 10
        _assert_greedy(ids, tokens, engine.core.params)
    finally:
        engine.shutdown()


@pytest.mark.parametrize("kw,message", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(kv_ship=True), "kv_ship"),
    (dict(role="split"), "--role split"),
    (dict(quantize="kv"), "int8 page pool"),
    (dict(quantize="weights"), "does not serve int8 weights"),
    (dict(lora_dir="/nonexistent"), "no adapter pools"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_an_engine_that_would_serve_the_state_wrong_does_not_start(kw, message):
    with pytest.raises(NotImplementedError, match=message):
        EngineCore(CFG, PARAMS, **{**ARGS, **kw})


def test_the_offload_tier_is_refused_and_a_request_for_speculation_is_not_served_by_it(
        monkeypatch, core):
    monkeypatch.setenv("LLMLB_KV_OFFLOAD_BYTES", "1000000")
    with pytest.raises(NotImplementedError, match="the KV offload tier"):
        EngineCore(CFG, PARAMS, **ARGS)
    # a request that asks for speculation is decoded without it
    assert not core._spec_available
    prompt = _prompt(12, 5)
    request = _submit(core, prompt, 6, speculative={"enabled": True})
    tokens, reason, _ = collect_events(request, 300)
    assert reason == "length"
    _assert_greedy(prompt, tokens)
    assert core.spec_info()["available"] is False
