"""The scheduler's block passes (engine/scheduler.py `_decode_blocks`): a
family that generates by diffusion over blocks, served through `EngineCore`
and the paged pool like any other, commits 0 or B tokens a row and pass —
and its tokens equal, token for token, what the plain reference's
`generate` (benchmark/reference/sdar_moe.py: one whole-sequence forward a
pass, no cache) produces for the same prompt and procedure. Float32 on the
CPU, small size, greedy or seeded. Docs: docs/block-diffusion.md.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.engine.tokenizer import ByteTokenizer
from llmlb_tpu.models import config_from_hf, sdar_moe
from llmlb_tpu.ops.sampling import sample_tokens
from tests.support import collect_events

B, MASK = 4, 500
HF = dict(
    model_type="sdar_moe", vocab_size=512, hidden_size=64,
    intermediate_size=192, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    rope_theta=1e6, rope_scaling=None, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, mlp_only_layers=[],
    decoder_sparse_step=1, tie_word_embeddings=False, attention_bias=False,
    hidden_act="silu", max_position_embeddings=4096, sliding_window=None,
    use_sliding_window=False,
    assumed=dict(block_length=B, mask_token_id=MASK))
CFG = config_from_hf(HF, jnp.float32)


def _params():
    params = sdar_moe.init_params(CFG, jax.random.PRNGKey(0))
    # a sharper head: some confidences pass a threshold, most do not, so
    # rows of one pass unmask different counts
    params["lm_head"] = params["lm_head"] * 6
    return params


PARAMS = _params()


def _core(**kw):
    args = dict(num_slots=4, slot_capacity=128, prefill_buckets=(16, 32, 64),
                kv_page_size=16, decode_burst=3, eos_id=-1, prefix_cache=True)
    core = EngineCore(CFG, PARAMS, **{**args, **kw})
    core.start()
    return core


@pytest.fixture(scope="module")
def core():
    core = _core()
    yield core
    core.stop()


@pytest.fixture(autouse=True)
def _the_reference_at_one_length(monkeypatch):
    """`ref.generate` makes a whole-sequence pass a pass of a block, each at
    its own length, and the reference's layers are jitted a length: every
    pass is made at the slot's 128 cells here. The padding forms later
    blocks, which no position of the open block or before it sees."""
    real = ref.forward

    def forward(params, hf, ids, **kw):
        padded = np.full(128, MASK, np.int32)
        padded[:len(ids)] = ids
        return real(params, hf, padded, **kw)

    monkeypatch.setattr(ref, "forward", forward)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(8, MASK, size=n).tolist()


def _collect(request, timeout=180):
    """(tokens, finish reason, tokens per content event)."""
    return collect_events(request, timeout)


def _submit(core, prompt, max_tokens, **sampling):
    sampling.setdefault("temperature", 0.0)
    return core.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
        max_tokens=max_tokens, **sampling)))


CASES = [  # prompt length (≢ 0 mod 4 among them), max_tokens, the procedure
    (17, 9, {}),
    (20, 5, {}),
    (3, 8, {}),  # shorter than a block: nothing to prefill
    (33, 12, dict(denoising_steps=2,
                  remasking_strategy="low_confidence_static")),
    (21, 16, dict(confidence_threshold=0.02)),
    (6, 1, {}),
    (30, 7, dict(denoising_steps=1)),  # the whole block in one pass
]


def test_tokens_equal_the_references_generate_for_every_procedure(core):
    """All at once on four slots with a burst of three passes: rows are
    admitted mid-burst, run at different passes of their blocks, and
    (threshold 0.02) unmask different counts in one pass."""
    sent = [(prompt := _prompt(n, seed), max_tokens, kw,
             _submit(core, prompt, max_tokens, **kw))
            for seed, (n, max_tokens, kw) in enumerate(CASES)]
    unmasked = []  # per case, what each unmasking pass of its blocks took
    for prompt, max_tokens, kw, request in sent:
        got, reason, frames = _collect(request)
        passes = []
        want = ref.generate(PARAMS, HF, prompt, max_tokens, passes=passes, **kw)
        assert got == want and reason == "length", (len(prompt), max_tokens, kw)
        assert len(got) == max_tokens  # exactly the tokens asked for
        # one content event a committed block, the given tokens left out
        assert sum(frames) == max_tokens and max(frames) <= B
        unmasked.append({before - after for _, before, after in passes
                         if before})
    # the procedures differ in what a pass unmasks: one position a pass; two
    # (one where a given token left an odd count); the block at once; and by
    # confidence a varying count
    assert unmasked[0] == {1}
    assert unmasked[3] == {1, 2}
    assert unmasked[6] <= {2, 4} and 4 in unmasked[6]
    assert len(unmasked[4]) > 1


def test_the_slots_capacity_ends_a_request(core):
    """max_tokens 512 in a slot of 128: the request ends with what the
    capacity leaves (a whole block short of the last cell), token for token
    the reference's."""
    prompt = _prompt(22, 40)
    room = 128 - B - 22
    got, reason, _ = _collect(_submit(core, prompt, 512))
    assert reason == "length" and len(got) == room
    assert got == ref.generate(PARAMS, HF, prompt, room)


def test_a_seeded_request_at_a_temperature_reproduces(core):
    """Seeded sampling folds (absolute position, masks left in the block):
    the reference draws with the same keys through the same sampler and
    takes its confidence at the temperature."""
    prompt, seed, temp = _prompt(18, 50), 1234, 0.7

    def sample(row, position, left):
        one = lambda x, dt: jnp.asarray([x], dt)  # noqa: E731
        return int(sample_tokens(
            jnp.asarray(row, jnp.float32)[None], jax.random.PRNGKey(0),
            one(temp, jnp.float32), one(1.0, jnp.float32), one(0, jnp.int32),
            None, one(seed, jnp.int32),
            one(position * (B + 1) + left, jnp.int32))[0])

    want = ref.generate(PARAMS, HF, prompt, 10, temperature=temp,
                        sample=sample)
    runs = [_collect(_submit(core, prompt, 10, temperature=temp, seed=seed))[0]
            for _ in range(2)]
    assert runs[0] == runs[1] == want
    assert want != ref.generate(PARAMS, HF, prompt, 10)  # not the greedy one


def test_eos_inside_a_block_ends_the_request_before_it():
    prompt = _prompt(19, 60)
    free = ref.generate(PARAMS, HF, prompt, 14)
    eos = free[6]  # the third generated block's second token
    cut = free.index(eos)
    core = _core(eos_id=eos, num_slots=2)
    try:
        got, reason, _ = _collect(_submit(core, prompt, 14))
        assert reason == "stop" and got == free[:cut]
        assert got == ref.generate(PARAMS, HF, prompt, 14, eos_id=eos)
        # the device stopped the row too: no pass ran for it after the
        # block with EOS committed (the next burst has nothing to decode)
        time.sleep(0.2)
        recs = [r for r in core.step_stats.snapshot(limit=64)["records"]
                if r["kind"] == "decode"]
        assert sum(r["blocks_committed"] for r in recs) == (
            (cut + len(prompt) % B) // B + 1)
    finally:
        core.stop()


def test_a_prefix_cache_hit_serves_the_same_tokens(core):
    """The donor's pages end on a block boundary (a page is whole blocks):
    a later prompt with the same head prefills only its suffix's whole
    blocks behind them."""
    head = _prompt(32, 70)
    first = head + _prompt(9, 71)
    second = head + _prompt(6, 72)
    _collect(_submit(core, first, 6))
    hits = core.metrics.prefix_hits_total
    got, _, _ = _collect(_submit(core, second, 11))
    assert core.metrics.prefix_hits_total == hits + 1
    assert got == ref.generate(PARAMS, HF, second, 11)


def test_park_and_resume_between_passes_is_token_identical():
    """One slot: a low-priority request parks mid-generation for a
    high-priority arrival (its committed blocks are kept, its open block
    starts over from masks), resumes, and both streams are the
    reference's."""
    core = _core(num_slots=1, decode_burst=2)
    try:
        victim_prompt, other_prompt = _prompt(18, 80), _prompt(9, 81)
        victim = _submit(core, victim_prompt, 30, priority=2)
        deadline = time.monotonic() + 60
        while core.slots[0].generated < 6 and time.monotonic() < deadline:
            time.sleep(0.005)
        other = _submit(core, other_prompt, 7, priority=0)
        got_other, _, _ = _collect(other)
        got_victim, reason, _ = _collect(victim)
        assert core.metrics.preemptions_total >= 1
        assert got_other == ref.generate(PARAMS, HF, other_prompt, 7)
        assert reason == "length"
        assert got_victim == ref.generate(PARAMS, HF, victim_prompt, 30)
    finally:
        core.stop()


def test_a_cancelled_request_frees_its_slot(core):
    request = _submit(core, _prompt(12, 90), 100)
    while request.first_token_at is None:
        time.sleep(0.005)
    request.cancel()
    got, reason, _ = _collect(request)
    assert reason == "cancelled" and 0 < len(got) < 100
    again, _, _ = _collect(_submit(core, _prompt(12, 90), 5))
    assert len(again) == 5 and again[:len(got)] == got[:5]


def _decode_records(core, run):
    """What `run()` returns, and the decode records of an idle engine's
    steps while it ran."""
    time.sleep(0.1)  # the engine idle: every record from here on is run's
    seq = max((r["seq"] for r in
               core.step_stats.snapshot(limit=1)["records"]), default=-1)
    result = run()
    time.sleep(0.1)
    return result, [r for r in core.step_stats.snapshot(limit=256)["records"]
                    if r["kind"] == "decode" and r["seq"] > seq]


def test_the_step_records_the_totals_and_the_timeline_carry_the_counts(core):
    time.sleep(0.1)  # the engine idle: the totals stand still
    before = core.metrics.summary()
    prompt = _prompt(16, 100)  # whole blocks: no given tokens
    sent = []

    def run():
        sent.append(_submit(core, prompt, 8))
        return _collect(sent[0])

    _, recs = _decode_records(core, run)
    request = sent[0]
    assert recs
    for r in recs:
        assert r["block_passes"] == core.decode_burst == 3
        assert r["tokens"] == r["tokens_committed"] == B * r["blocks_committed"]
        assert 0 < r["row_passes"] <= r["block_passes"] * r["active_slots"]
        assert {"experts_touched", "expert_assignments", "expert_load_max",
                "kv_pages_live", "kv_pages_window"} <= set(r)
    assert sum(r["blocks_committed"] for r in recs) == 2
    assert sum(r["positions_unmasked"] for r in recs) == 8
    # a commit rides with the next block's first unmasking: a row-pass for
    # every pass of the reference that held a mask, and one for the
    # request's last block, which commits alone
    passes = []
    ref.generate(PARAMS, HF, prompt, 8, passes=passes)
    assert sum(r["row_passes"] for r in recs) == sum(
        1 for _, before_, _ in passes if before_ > 0) + 1 == 9
    assert sum(r["blocks_fused"] for r in recs) == 2 - 1
    after = core.metrics.summary()
    for name in ("block_passes", "row_passes", "blocks_committed",
                 "tokens_committed", "positions_unmasked", "blocks_fused"):
        assert (after[f"{name}_total"] - before[f"{name}_total"]
                == sum(r[name] for r in recs))
    events = core.flightrec.timeline(request.request_id)["events"]
    commits = [e["attrs"] for e in events if e["event"] == "commit"]
    assert sum(c["blocks"] for c in commits) == 2
    assert sum(c["tokens"] for c in commits) == 8


@pytest.mark.parametrize("steps,row_passes", [(4, 33), (1, 9)])
def test_a_block_costs_its_unmasking_passes_and_the_last_one_a_commit(
        core, steps, row_passes):
    """Eight blocks behind a whole-block prompt, `low_confidence_static`:
    `denoising_steps` calls a block and one more for the request's last
    commit — 33/32 and 9/32 calls a position where a commit of its own a
    block made it 40/32 and 16/32."""
    prompt = _prompt(16, 120 + steps)
    kw = dict(denoising_steps=steps,
              remasking_strategy="low_confidence_static")
    (got, reason, _), recs = _decode_records(
        core, lambda: _collect(_submit(core, prompt, 8 * B, **kw)))
    assert reason == "length"
    assert got == ref.generate(PARAMS, HF, prompt, 8 * B, **kw)
    assert sum(r["tokens_committed"] for r in recs) == 8 * B
    assert sum(r["row_passes"] for r in recs) == row_passes
    assert sum(r["blocks_committed"] for r in recs) == 8
    assert sum(r["blocks_fused"] for r in recs) == 7


def test_rows_commit_in_the_pass_that_others_unmask_in_and_two_stop_there():
    """Bursts of four passes over four rows at once: a row that completes a
    block a pass (`denoising_steps` 1) commits in every pass — four blocks a
    burst and the open one behind them, which from a committed length of 32
    reaches the fourth page of 16 (_block_reach) — while its neighbours
    unmask one and two positions a pass; one row's block holds EOS when it commits, one's
    commit uses up its `max_tokens` (a multiple of the block length, and
    not), and neither opens a block behind it."""
    prompts = [_prompt(n, 130 + i) for i, n in enumerate((20, 18, 21, 12))]
    cases = [(12 * B, dict(denoising_steps=1)), (6 * B - 2, {}),
             (4 * B, dict(denoising_steps=2)), (6 * B, {})]
    free = ref.generate(PARAMS, HF, prompts[3], 6 * B)
    eos = free[2 * B + 1]  # inside the last row's third block
    core = _core(eos_id=eos, decode_burst=4)
    try:
        def run():
            sent = [_submit(core, prompt, n, **kw)
                    for prompt, (n, kw) in zip(prompts, cases)]
            return [_collect(request) for request in sent]

        results, recs = _decode_records(core, run)
        reasons = []
        for prompt, (n, kw), (got, reason, _) in zip(prompts, cases, results):
            assert got == ref.generate(PARAMS, HF, prompt, n, eos_id=eos,
                                       **kw), (len(prompt), n, kw)
            reasons.append(reason)
        assert reasons[3] == "stop" and "length" in reasons
        assert len(results[3][0]) == free.index(eos) < len(free)
        # every commit but a request's last opened the next block
        blocks = sum(r["blocks_committed"] for r in recs)
        assert sum(r["blocks_fused"] for r in recs) == blocks - len(cases)
        # a burst in which the first row committed in all four passes
        assert max(r["blocks_committed"] for r in recs) >= 4
        # no pass ran for a row behind its last commit
        passes = [[] for _ in cases]
        for prompt, (n, kw), held in zip(prompts, cases, passes):
            ref.generate(PARAMS, HF, prompt, n, eos_id=eos, passes=held, **kw)
        assert sum(r["row_passes"] for r in recs) == sum(
            sum(1 for _, before, _ in held if before > 0) + 1
            for held in passes)
    finally:
        core.stop()


@pytest.mark.parametrize("sampling,message", [
    (dict(constraint={"kind": "regex", "pattern": "a+"}), "grammar"),
    (dict(speculative={"enabled": True}), "speculative"),
    (dict(block_length=8), "block_length"),
    (dict(denoising_steps=3), "denoising_steps"),
    (dict(remasking_strategy="random"), "remasking_strategy"),
    (dict(confidence_threshold=2.0), "confidence_threshold"),
])
def test_what_a_block_family_cannot_serve_is_refused_at_submission(
        core, sampling, message):
    with pytest.raises(ValueError, match=message):
        _submit(core, _prompt(10, 1), 4, **sampling)
    # what it can: the model's own block length, named
    assert _submit(core, _prompt(10, 1), 4, block_length=B) is not None


def test_an_autoregressive_family_refuses_the_block_parameters():
    cfg = get_preset("debug-tiny")
    core = EngineCore(cfg, num_slots=1, slot_capacity=64,
                      prefill_buckets=(16,), kv_page_size=16)
    assert core.block == 1
    with pytest.raises(ValueError, match="diffusion over blocks"):
        core.submit(Request(prompt_ids=[1, 2, 3], sampling=SamplingParams(
            denoising_steps=2)))


@pytest.mark.parametrize("kw,error", [
    (dict(quantize="kv"), NotImplementedError),
    (dict(spec_decode=True), NotImplementedError),
    (dict(role="split"), NotImplementedError),
    (dict(quantize="weights"), NotImplementedError),
    (dict(kv_page_size=6), ValueError),
    (dict(prefill_buckets=(18, 32)), ValueError),
])
def test_an_engine_that_would_serve_blocks_wrong_does_not_start(kw, error):
    args = dict(num_slots=2, slot_capacity=128, prefill_buckets=(16, 32),
                kv_page_size=16)
    with pytest.raises(error):
        EngineCore(CFG, PARAMS, **{**args, **kw})


def test_one_frame_carries_a_committed_blocks_tokens(core):
    """Through the service layer: a delta of the stream holds the several
    tokens of one commit, and the usage counts every one."""
    engine = Engine("block-tiny", core, ByteTokenizer(CFG.vocab_size))
    prompt = _prompt(16, 110)

    async def run():
        ids, sizes, usage = [], [], None
        async for delta in engine.stream(prompt, SamplingParams(
                temperature=0.0, max_tokens=12)):
            ids.extend(delta.token_ids or [])
            if delta.token_ids:
                sizes.append(len(delta.token_ids))
            if delta.finish_reason:
                usage = delta.completion_tokens
        return ids, sizes, usage

    ids, sizes, usage = asyncio.run(run())
    assert ids == ref.generate(PARAMS, HF, prompt, 12)
    assert usage == 12 and max(sizes) > 1


def test_the_http_surface_takes_the_parameters_and_names_its_refusals(core):
    """POST /v1/chat/completions: the four request parameters reach the
    scheduler; a stream's content frame may carry several tokens; what a
    block family cannot serve is a 400 that names it."""
    import json

    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app

    engine = Engine("block-tiny", core, ByteTokenizer(CFG.vocab_size))
    body = {"model": "block-tiny", "temperature": 0, "max_tokens": 8,
            "messages": [{"role": "user", "content": "hello there"}]}

    async def run():
        client = TestClient(TestServer(create_engine_app(
            engine, owns_engine=False)))
        await client.start_server()
        try:
            plain = await (await client.post(
                "/v1/chat/completions", json=body)).json()
            assert plain["usage"]["completion_tokens"] == 8
            fewer = await (await client.post(
                "/v1/chat/completions",
                json={**body, "denoising_steps": 1})).json()
            assert fewer["usage"]["completion_tokens"] == 8
            frames = []
            async with client.post("/v1/chat/completions", json={
                    **body, "stream": True,
                    "stream_options": {"include_usage": True}}) as resp:
                assert resp.status == 200
                async for raw in resp.content:
                    if raw.startswith(b"data:") and b"[DONE]" not in raw:
                        frames.append(json.loads(raw[5:]))
            usage = [f["usage"] for f in frames if f.get("usage")]
            assert usage and usage[-1]["completion_tokens"] == 8
            refused = {}
            for name, extra in {
                    "logprobs": {"logprobs": True},
                    "block_length": {"block_length": 8},
                    "denoising_steps": {"denoising_steps": 3},
                    "remasking_strategy": {"remasking_strategy": 5},
                    "confidence_threshold": {"confidence_threshold": "high"},
                    "speculative": {"speculative": {"enabled": True}},
                    "grammar": {"response_format": {"type": "json_object"}},
            }.items():
                resp = await client.post("/v1/chat/completions",
                                         json={**body, **extra})
                refused[name] = (resp.status,
                                 (await resp.json())["error"]["message"])
            return refused
        finally:
            await client.close()

    refused = asyncio.run(run())
    for name, (status, message) in refused.items():
        assert status == 400 and name in message, (name, status, message)
