"""Burst decode (k steps per dispatch) must match single-step decode.

The scheduler's decode_burst fuses k decode+sample steps into one jitted
lax.scan with on-device token feedback, syncing the host once per k tokens
instead of per token. These tests pin the
semantics the fusion must preserve: greedy outputs identical to the k=1 path,
EOS/max_tokens finishing mid-burst trimmed, chunked prefill still interleaves.
"""

import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams


def _collect(req: Request, timeout: float = 60.0) -> tuple[list[int], str]:
    tokens: list[int] = []
    while True:
        kind, val = req.events.get(timeout=timeout)
        if kind == "token":
            tokens.append(val)
        elif kind == "done":
            return tokens, val
        elif kind == "error":
            raise RuntimeError(val)


def _run_greedy(core: EngineCore, prompts: list[list[int]],
                max_tokens: int = 12) -> list[tuple[list[int], str]]:
    reqs = [
        Request(prompt_ids=p,
                sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens))
        for p in prompts
    ]
    for r in reqs:
        core.submit(r)
    return [_collect(r) for r in reqs]


@pytest.fixture(scope="module")
def cfg():
    return get_preset("debug-tiny")


def test_burst_matches_single_step_greedy(cfg):
    """Token-for-token equivalence: burst=4 vs burst=1 on the same prompts."""
    prompts = [[5, 9, 2], [7, 7, 7, 7], [3]]
    core1 = EngineCore(cfg, num_slots=4, slot_capacity=64,
                       prefill_buckets=(16, 32), seed=0, decode_burst=1)
    core1.start()
    try:
        base = _run_greedy(core1, prompts)
    finally:
        core1.stop()

    core4 = EngineCore(cfg, num_slots=4, slot_capacity=64,
                       prefill_buckets=(16, 32), seed=0, decode_burst=4)
    core4.start()
    try:
        burst = _run_greedy(core4, prompts)
    finally:
        core4.stop()

    assert burst == base


def test_burst_trims_max_tokens_mid_burst(cfg):
    """max_tokens that is not a multiple of the burst still stops exactly."""
    core = EngineCore(cfg, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0, decode_burst=8)
    core.start()
    try:
        req = Request(prompt_ids=[1, 2, 3],
                      sampling=SamplingParams(temperature=0.0, max_tokens=5))
        core.submit(req)
        tokens, finish = _collect(req)
        # first token comes from prefill; 5 generated total, EOS never hit
        # with random weights on a 64-vocab byte model is unlikely but legal
        assert finish in ("stop", "length")
        assert len(tokens) <= 5
        if finish == "length":
            assert len(tokens) == 5
    finally:
        core.stop()


def test_burst_respects_slot_capacity(cfg):
    """A request whose room runs out mid-burst finishes with 'length' and
    never reports more tokens than the slot can hold."""
    core = EngineCore(cfg, num_slots=2, slot_capacity=24,
                      prefill_buckets=(16,), seed=0, decode_burst=8)
    core.start()
    try:
        prompt = [4] * 10
        req = Request(prompt_ids=prompt,
                      sampling=SamplingParams(temperature=0.0, max_tokens=500))
        core.submit(req)
        tokens, finish = _collect(req)
        assert finish in ("stop", "length")
        # every generated token's KV lands after the prompt's; the sequence
        # must stay within the 24-cell slot row
        assert 10 + len(tokens) <= 24
    finally:
        core.stop()


def test_burst_with_chunked_prefill_interleaves(cfg):
    """A long prompt (chunked prefill) and a short decode share the loop with
    burst decode on: both finish, the short one keeps emitting during the
    long one's prefill."""
    core = EngineCore(cfg, num_slots=2, slot_capacity=128,
                      prefill_buckets=(16, 32), seed=0, decode_burst=4)
    core.start()
    try:
        short = Request(prompt_ids=[8, 8],
                        sampling=SamplingParams(temperature=0.0, max_tokens=20))
        long = Request(prompt_ids=list(range(1, 100)),
                       sampling=SamplingParams(temperature=0.0, max_tokens=4))
        core.submit(short)
        core.submit(long)
        s_tokens, s_finish = _collect(short)
        l_tokens, l_finish = _collect(long)
        assert s_finish in ("stop", "length")
        assert l_finish in ("stop", "length")
    finally:
        core.stop()


def test_burst_cancellation_mid_stream(cfg):
    """Cancel during generation: the slot frees and the request ends with
    'cancelled' even when cancellation lands mid-burst."""
    core = EngineCore(cfg, num_slots=2, slot_capacity=128,
                      prefill_buckets=(16,), seed=0, decode_burst=4)
    core.start()
    try:
        req = Request(prompt_ids=[9, 9, 9],
                      sampling=SamplingParams(temperature=0.0, max_tokens=100))
        core.submit(req)
        # wait for the first token, then cancel
        kind, _ = req.events.get(timeout=60)
        assert kind == "token"
        req.cancel()
        while True:
            kind, val = req.events.get(timeout=60)
            if kind == "done":
                assert val == "cancelled"
                break
        # slot must be reusable afterwards
        nxt = Request(prompt_ids=[2, 2],
                      sampling=SamplingParams(temperature=0.0, max_tokens=3))
        core.submit(nxt)
        _, finish = _collect(nxt)
        assert finish in ("stop", "length")
    finally:
        core.stop()


def test_batched_prefill_matches_sequential(cfg):
    """Same-bucket prompts prefilled together (one padded dispatch) must
    produce the same greedy outputs as one-at-a-time inserts. The padded
    rows repeat the last request, so duplicate scatters are exercised too
    (6 requests -> pow2 pad to 8)."""
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]

    core_seq = EngineCore(cfg, num_slots=8, slot_capacity=64,
                          prefill_buckets=(16,), seed=0, decode_burst=1)
    core_seq.MAX_PREFILL_GROUP = 1  # force one-at-a-time inserts
    core_seq.start()
    try:
        base = _run_greedy(core_seq, prompts, max_tokens=8)
    finally:
        core_seq.stop()

    core_batch = EngineCore(cfg, num_slots=8, slot_capacity=64,
                            prefill_buckets=(16,), seed=0, decode_burst=1)
    core_batch.start()
    try:
        batched = _run_greedy(core_batch, prompts, max_tokens=8)
    finally:
        core_batch.stop()

    assert batched == base


def test_batched_prefill_mixed_buckets_and_long(cfg):
    """A drain that mixes buckets and a chunked long prompt: every request
    finishes and the long one still interleaves."""
    core = EngineCore(cfg, num_slots=4, slot_capacity=128,
                      prefill_buckets=(16, 32), seed=0, decode_burst=4)
    core.start()
    try:
        reqs = [
            Request(prompt_ids=[1] * 4,
                    sampling=SamplingParams(temperature=0.0, max_tokens=6)),
            Request(prompt_ids=[2] * 20,  # second bucket
                    sampling=SamplingParams(temperature=0.0, max_tokens=6)),
            Request(prompt_ids=list(range(1, 60)),  # > 32: chunked
                    sampling=SamplingParams(temperature=0.0, max_tokens=4)),
            Request(prompt_ids=[3] * 5,
                    sampling=SamplingParams(temperature=0.0, max_tokens=6)),
        ]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            tokens, finish = _collect(r)
            assert finish in ("stop", "length")
    finally:
        core.stop()


def test_prefill_dispatch_failure_reaches_batched_requests(cfg):
    """Requests claimed into a prefill batch get terminal events when the
    dispatch raises — slots are assigned before the dispatch so _fail_all
    can see them (a silent event queue hangs the HTTP stream forever)."""
    core = EngineCore(cfg, num_slots=4, slot_capacity=64,
                      prefill_buckets=(16,), seed=0, decode_burst=1)

    def boom(*args, **kwargs):
        raise RuntimeError("injected prefill failure")

    core.family = type("F", (), {
        **{k: staticmethod(getattr(core.family, k))
           for k in dir(core.family) if not k.startswith("__")},
        "prefill_into_pages": staticmethod(boom),
    })()
    core.start()
    try:
        reqs = [
            Request(prompt_ids=[1, 2, 3],
                    sampling=SamplingParams(temperature=0.0, max_tokens=4))
            for _ in range(3)
        ]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            kind, val = r.events.get(timeout=30)
            assert kind == "error", (kind, val)
    finally:
        core.stop()


def test_window_buckets_cross_boundary(cfg):
    """Generation that crosses a context-window bucket boundary (256) must
    be identical to a run with only the full-capacity window available."""
    import dataclasses as _dc

    cfg600 = _dc.replace(cfg, max_position_embeddings=1024)
    prompt = [7] * 250  # window 256 covers prefill; generation crosses it

    core_full = EngineCore(cfg600, num_slots=2, slot_capacity=600,
                           prefill_buckets=(256,), seed=0, decode_burst=4)
    core_full._window_buckets = (600,)  # capacity only: no windowing
    core_full.start()
    try:
        base = _run_greedy(core_full, [prompt], max_tokens=20)
    finally:
        core_full.stop()

    core_win = EngineCore(cfg600, num_slots=2, slot_capacity=600,
                          prefill_buckets=(256,), seed=0, decode_burst=4)
    assert core_win._window_buckets == (256, 512, 600)
    core_win.start()
    try:
        windowed = _run_greedy(core_win, [prompt], max_tokens=20)
    finally:
        core_win.stop()

    assert windowed == base


def test_prewarm_compiles_both_modes(cfg):
    """Prewarm must cover burst AND single-step modes (the legacy k==1 path
    has per-window static recompiles of decode_step_paged); a signature
    drift between decode_step_paged and the prewarm lowering would
    otherwise be swallowed by the best-effort except and only surface as
    production compile stalls. Fused engines dispatch the burst scan even
    at k == 1, so the single-step arm needs fused decode off."""
    import dataclasses as _dc

    from unittest import mock

    from llmlb_tpu.engine import scheduler as sched_mod

    cfg512 = _dc.replace(cfg, max_position_embeddings=1024)
    for burst in (4, 1):
        core = EngineCore(cfg512, num_slots=2, slot_capacity=512,
                          prefill_buckets=(16,), seed=0, decode_burst=burst,
                          fused_decode=burst > 1)
        assert core._window_buckets == (256, 512)
        core._running = True
        try:
            # prewarm swallows failures by design (best-effort in prod);
            # here any swallowed lowering error must fail the test
            with mock.patch.object(sched_mod.log, "exception",
                                   side_effect=AssertionError) as logged:
                core._prewarm_windows()
            assert not logged.called
            if burst > 1:
                assert sorted(core._decode_many) == [256, 512]
        finally:
            core._running = False
