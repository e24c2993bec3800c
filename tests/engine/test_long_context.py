"""Long-context serving: chunked prefill + prefill/decode interleaving.

VERDICT r1 items 4 and 7: prompts beyond the largest one-shot prefill bucket
must stream through the engine (chunked prefill via prefill_extend_pages), and
decode slots must keep emitting tokens while a long prompt prefills.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.service import Engine
from llmlb_tpu.models import llama
from tests.support import collect, identity_kv_pages, kv_rows


@pytest.fixture(scope="module")
def tiny_cfg():
    return get_preset("debug-tiny")


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return llama.init_params(tiny_cfg, jax.random.PRNGKey(0))


def test_prefill_extend_matches_oneshot(tiny_cfg, tiny_params):
    """Chunked prefill must produce the same cache + final logits as a
    one-shot prefill of the whole prompt."""
    cfg, params = tiny_cfg, tiny_params
    capacity, n = 64, 40
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)

    # one-shot reference: bucket 64
    ck, cv, tables = identity_kv_pages(llama, cfg, 2, capacity)
    row1 = tables[1:2]
    ids = np.zeros((1, 64), np.int32)
    ids[0, :n] = prompt
    ref_logits, ck_ref, cv_ref = llama.prefill_into_pages(
        params, cfg, jnp.asarray(ids), jnp.asarray([n], np.int32),
        row1, ck, cv,
    )

    # chunked: 16-token chunks into row 1
    ck2, cv2, _ = identity_kv_pages(llama, cfg, 2, capacity)
    logits = None
    for start in range(0, n, 16):
        chunk = prompt[start:start + 16]
        ids_c = np.zeros((1, 16), np.int32)
        ids_c[: , :len(chunk)] = chunk
        logits, ck2, cv2 = llama.prefill_extend_pages(
            params, cfg, jnp.asarray(ids_c),
            jnp.asarray([len(chunk)], np.int32),
            jnp.asarray([start], np.int32),
            row1, ck2, cv2,
        )

    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    # caches agree over the valid region of row 1
    np.testing.assert_allclose(
        np.asarray(kv_rows(ck_ref, row1, n)), np.asarray(kv_rows(ck2, row1, n)),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(kv_rows(cv_ref, row1, n)), np.asarray(kv_rows(cv2, row1, n)),
        rtol=2e-4, atol=2e-4,
    )


def test_chunked_greedy_matches_oneshot_decode(tiny_cfg, tiny_params):
    """Greedy continuation after chunked prefill == after one-shot prefill."""
    cfg, params = tiny_cfg, tiny_params
    core_a = EngineCore(cfg, tiny_params, num_slots=2, slot_capacity=96,
                        prefill_buckets=(16, 64))
    core_b = EngineCore(cfg, tiny_params, num_slots=2, slot_capacity=96,
                        prefill_buckets=(16,))  # forces chunking for n=40
    rng = np.random.default_rng(1)
    prompt = list(rng.integers(0, cfg.vocab_size, size=(40,)))
    outs = []
    for core in (core_a, core_b):
        req = Request(prompt_ids=prompt,
                      sampling=SamplingParams(temperature=0.0, max_tokens=8))
        core.submit(req)
        core.start()
        toks, _ = collect(req, timeout=60)
        core.stop()
        outs.append(toks)
    assert outs[0] == outs[1], outs


def test_decode_progresses_during_long_prefill(tiny_cfg, tiny_params):
    """Drive the step loop by hand: while a long prompt's chunks are being
    fed, the already-active slot must emit one token per iteration."""
    cfg = tiny_cfg
    core = EngineCore(cfg, tiny_params, num_slots=2, slot_capacity=256,
                      prefill_buckets=(16, 32))
    short = Request(prompt_ids=[1, 2, 3],
                    sampling=SamplingParams(temperature=0.0, max_tokens=200))
    core.submit(short)
    assert core._try_insert()
    # activated: first token sampled on device, emitted with the next
    # decode fetch (deferred — activation itself costs no host sync)
    assert core.slots[0].first_pending
    assert core._decode_active()
    assert short.first_token_at is not None

    # 130-token prompt: > largest bucket (32) -> chunked (5 chunks)
    long = Request(prompt_ids=list(range(1, 131)),
                   sampling=SamplingParams(temperature=0.0, max_tokens=4))
    core.submit(long)
    assert core._try_insert()  # claims slot, no prefill work yet
    assert core.slots[1].prefilling

    short_tokens_during_prefill = 0
    iterations = 0
    while core.slots[1].prefilling:
        did_prefill = core._advance_prefill()
        assert did_prefill
        if core.slots[1].prefilling:  # not the final chunk yet
            assert long.first_token_at is None
        before = short.events.qsize()
        assert core._decode_active()
        assert short.events.qsize() == before + 1  # decode emitted for short
        short_tokens_during_prefill += 1
        iterations += 1
        assert iterations < 50
    assert iterations == (130 + 31) // 32  # ceil(130/32) = 5 chunks
    assert short_tokens_during_prefill >= 4
    # activated on the final chunk; its first token rode the same loop
    # iteration's decode fetch (deferred emission)
    assert long.first_token_at is not None

    # run the loop to completion for the long request
    core.start()
    collect(long, timeout=60)
    core.stop()


def test_engine_long_prompt_streams_e2e(tiny_cfg):
    """A prompt 4x beyond the largest bucket streams a completion through the
    Engine service layer (VERDICT item 4's done-criterion at test scale)."""
    eng = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=256,
        prefill_buckets=(16, 32), seed=0,
    )
    try:
        async def run():
            ids = list(np.random.default_rng(2).integers(
                1, eng.core.cfg.vocab_size, size=(130,)))
            result = await eng.complete(
                ids, SamplingParams(temperature=0.0, max_tokens=6))
            assert result.prompt_tokens == 130
            assert result.completion_tokens >= 1
            assert result.finish_reason in ("stop", "length")
        asyncio.run(run())
    finally:
        eng.shutdown()


def test_cp_prefill_engine_matches_chunked(tiny_cfg):
    """VERDICT r2 item 5: make_context_parallel_prefill wired into the engine.
    A long prompt served on an sp>1 mesh (ring-attention one-shot prefill +
    cache scatter) must produce the same greedy tokens as the single-device
    chunked-prefill path."""
    from llmlb_tpu.parallel.mesh import MeshConfig

    cfg = tiny_cfg
    rng = np.random.default_rng(3)
    n = 40  # beyond the largest bucket below -> long-prompt path
    prompt = list(rng.integers(1, cfg.vocab_size, size=(n,)))

    def run(mesh_config):
        core = EngineCore(
            cfg, num_slots=2, slot_capacity=128,
            prefill_buckets=(16, 32), seed=0, mesh_config=mesh_config,
        )
        if mesh_config is not None and mesh_config.sp > 1:
            assert core._use_cp_prefill
        core.start()
        try:
            req = Request(
                prompt_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=8),
            )
            core.submit(req)
            return collect(req)[0]
        finally:
            core.stop()

    chunked = run(None)  # default dp x tp mesh: chunked path
    cp = run(MeshConfig(dp=1, tp=2, sp=4))
    assert chunked == cp, (chunked, cp)


def test_prefill_fairness_round_robin(tiny_cfg):
    """Two long prompts prefill concurrently: the second must start emitting
    before the first finishes its whole decode (no head-of-line blocking)."""
    core = EngineCore(
        tiny_cfg, num_slots=2, slot_capacity=128,
        prefill_buckets=(16,), seed=0,
    )
    core.start()
    try:
        rng = np.random.default_rng(4)
        reqs = [
            Request(
                prompt_ids=list(rng.integers(1, tiny_cfg.vocab_size, size=(48,))),
                sampling=SamplingParams(temperature=0.0, max_tokens=4),
            )
            for _ in range(2)
        ]
        for r in reqs:
            core.submit(r)
        # both must reach their first token; fairness means neither waits for
        # the other's FULL prefill+decode to complete first
        import time as _time

        deadline = _time.monotonic() + 120
        while any(r.first_token_at is None for r in reqs):
            assert _time.monotonic() < deadline, "a prefill starved"
            _time.sleep(0.01)
        # drain
        for r in reqs:
            while True:
                kind, _ = r.events.get(timeout=60)
                if kind in ("done", "error"):
                    break
        gap = abs(reqs[0].first_token_at - reqs[1].first_token_at)
        total = max(r.finished_at for r in reqs) - min(r.submitted_at for r in reqs)
        assert gap < max(0.5 * total, 5.0), (gap, total)
    finally:
        core.stop()
