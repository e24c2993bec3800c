"""In-process disaggregated serving (`--role split`, docs/disaggregation.md).

The two acceptance invariants of the split architecture:

1. ISOLATION — during a mixed long-prompt/decode workload, ZERO prefill
   dispatches execute on the decode pool's step loop (asserted over the
   per-loop dispatch ledger `EngineCore.prefill_dispatch_by_loop`); decode
   ITL is structurally independent of arriving prompt size, not
   budget-bounded.
2. IDENTITY — streams served through the prefill→handoff→decode path are
   token-identical to `--role both` for greedy and seeded-stochastic
   sampling (the page-id exchange moves KV ownership without moving bytes,
   and adoption is the PR 10 resume-shaped activation).
"""

import asyncio
import time

import pytest

from llmlb_tpu.disagg import normalize_role
from llmlb_tpu.engine.scheduler import EngineCore, SamplingParams
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.service import Engine

KW = dict(num_slots=4, slot_capacity=256, prefill_buckets=(16, 32, 64),
          seed=0, kv_page_size=16, prefix_cache=False)


@pytest.fixture(scope="module")
def pair():
    both = Engine.from_preset("debug-tiny", **KW)
    split = Engine.from_preset("debug-tiny", role="split",
                               disagg_prefill_slots=1, **KW)
    yield both, split
    both.shutdown()
    split.shutdown()


async def _consume(agen, out):
    async for delta in agen:
        out.append(delta)


def _text(out):
    return "".join(d.text for d in out)


# ------------------------------------------------------------------ identity


def test_split_greedy_token_identity(pair):
    both, split = pair

    async def run():
        ids = both.tokenizer.encode("the quick brown fox jumps over")
        params = SamplingParams(temperature=0.0, max_tokens=32)
        ref = await both.complete(ids, params)
        got = await split.complete(ids, params)
        assert got.text == ref.text
        assert got.finish_reason == ref.finish_reason
    asyncio.run(run())


def test_split_seeded_stochastic_token_identity(pair):
    both, split = pair

    async def run():
        ids = both.tokenizer.encode("the quick brown fox jumps over")
        params = SamplingParams(temperature=0.9, seed=1234, max_tokens=32)
        ref = await both.complete(ids, params)
        got = await split.complete(ids, params)
        assert got.text == ref.text
    asyncio.run(run())


def test_split_long_prompt_chunked_prefill_identity(pair):
    """A prompt past the largest one-shot bucket runs the chunked prefill
    path in the prefill pool, then hands off — still token-identical."""
    both, split = pair

    async def run():
        ids = both.tokenizer.encode("z" * 150)  # > 64-token bucket
        params = SamplingParams(temperature=0.0, max_tokens=16)
        ref = await both.complete(ids, params)
        got = await split.complete(ids, params)
        assert got.text == ref.text
    asyncio.run(run())


# ----------------------------------------------------------------- isolation


def test_zero_prefill_dispatches_on_the_decode_loop(pair):
    """The acceptance criterion, verbatim: a mixed workload of background
    decoders and long-prompt arrivals runs prefill ONLY on the prefill
    loop. Handoffs flow (so the decode pool demonstrably served adopted
    work) and the decode-loop prefill ledger stays at zero."""
    _, split = pair

    async def run():
        handoffs_before = split.core.metrics.handoff_total["in_process"]
        bg_out: list = []
        bg = asyncio.create_task(_consume(
            split.stream(split.tokenizer.encode("background decoder"),
                         SamplingParams(temperature=0.0, max_tokens=160)),
            bg_out,
        ))
        deadline = time.monotonic() + 15.0
        while not bg_out and time.monotonic() < deadline:
            await asyncio.sleep(0.005)
        assert bg_out, "background decoder never started"
        # long prompts arrive WHILE the decoder streams
        results = await asyncio.gather(*[
            split.complete(split.tokenizer.encode("y" * 150),
                           SamplingParams(temperature=0.0, max_tokens=8))
            for _ in range(3)
        ])
        assert all(r.finish_reason in ("stop", "length") for r in results)
        bg.cancel()
        try:
            await bg
        except asyncio.CancelledError:
            pass
        ledger = split.core.prefill_dispatch_by_loop
        assert ledger["decode"] == 0, (
            f"decode pool ran prefill dispatches: {ledger}"
        )
        assert ledger["main"] == 0, "split mode must not use the main loop"
        assert ledger["prefill"] > 0
        assert (split.core.metrics.handoff_total["in_process"]
                - handoffs_before) >= 4  # 3 long + the background decoder
    asyncio.run(run())


def test_split_surfaces_role_and_queue_depths(pair):
    _, split = pair
    info = split.core.disagg_info()
    assert info["role"] == "split" and info["split"] is True
    assert info["prefill_slots"] == 1 and info["decode_slots"] == 3
    sched = split.core.sched_info()
    assert set(sched["queued_by_role"]) == {"prefill", "decode"}
    text = split.core.metrics.render(
        queue_depth=0, active_slots=0, num_slots=4, sched=sched,
    )
    assert 'llmlb_engine_queue_depth_role{role="decode"}' in text
    assert "llmlb_engine_handoff_total" in text
    assert "llmlb_engine_handoff_backlog" in text


# -------------------------------------------------------------- construction


def test_role_normalization():
    assert normalize_role(None) == "both"
    assert normalize_role("") == "both"
    assert normalize_role(" Split ") == "split"
    with pytest.raises(ValueError):
        normalize_role("shard")


def test_split_requires_paged_layout_and_two_slots():
    with pytest.raises(ValueError, match="2 slots"):
        EngineCore(get_preset("debug-tiny"), role="split",
                   num_slots=1, slot_capacity=64, prefill_buckets=(16,))


def test_split_flight_record_pairs_stage_with_adopt(pair):
    """Observability twin (docs/tracing.md): a split run's flight record
    shows the handoff as an emit/adopt pair — `staged` on the prefill
    loop, `adopted` on the decode loop — in causal order, inside one
    request timeline keyed by the gateway request id."""
    _, split = pair
    rid = "trace-split-fr-1"

    async def run():
        ids = split.tokenizer.encode("tell me about staged adoption")
        params = SamplingParams(temperature=0.0, max_tokens=8)
        got = await split.complete(ids, params, request_id=rid)
        assert got.text
    asyncio.run(run())

    tl = split.core.flightrec.timeline(rid)
    assert tl is not None, "split request left no flight record"
    names = [e["event"] for e in tl["events"]]
    assert "staged" in names and "adopted" in names
    assert names.index("staged") < names.index("adopted")
    assert names.count("staged") == names.count("adopted") == 1
    # the pair brackets the lifecycle: prefill before, finish after
    assert names.index("prefill_chunk") < names.index("staged")
    assert names[-1] == "finished"
    adopted = next(e for e in tl["events"] if e["event"] == "adopted")
    assert adopted["attrs"]["in_process"] is True
    assert adopted["attrs"]["staged_s"] >= 0
    tss = [e["ts"] for e in tl["events"]]
    assert tss == sorted(tss)
