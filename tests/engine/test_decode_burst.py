"""Burst decode (k steps per dispatch) must match single-step decode.

The scheduler's decode_burst fuses k decode+sample steps into one jitted
lax.scan with on-device token feedback, syncing the host once per k tokens
instead of per token. These tests pin the
semantics the fusion must preserve: greedy outputs identical to the k=1 path,
EOS/max_tokens finishing mid-burst trimmed, chunked prefill still interleaves.

And the rule of the way out: whatever ONE fetch brought a row — a burst's k
tokens, the activation's first token with them, a speculative step's accepted
drafts + 1 — leaves the scheduler as ONE content event, so the service writes
one frame a row and fetch.
"""

import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from tests.support import collect as _collect
from tests.support import collect_events, take_tokens


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


@pytest.mark.parametrize("burst", [4, 8])
def test_burst_matches_single_step_greedy(cfg, burst):
    """Token-for-token equivalence: burst=4 and 8 vs burst=1 on the same
    prompts."""
    prompts = [[5, 9, 2], [7, 7, 7, 7], [3]]
    core1 = EngineCore(cfg, num_slots=4, slot_capacity=64,
                       prefill_buckets=(16, 32), seed=0, decode_burst=1)
    core1.start()
    try:
        base = _run_greedy(core1, prompts)
    finally:
        core1.stop()

    core4 = EngineCore(cfg, num_slots=4, slot_capacity=64,
                       prefill_buckets=(16, 32), seed=0, decode_burst=burst)
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
        assert take_tokens(req, 1, timeout=60)
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


# ------------------------------------------- one content event a row and fetch


def _inline(cfg, **kw):
    """A core whose loop the test drives by hand: a decode step's events
    are then read before the next step runs."""
    kw.setdefault("decode_burst", 8)
    return EngineCore(cfg, num_slots=4, slot_capacity=128,
                      prefill_buckets=(16, 32), seed=0, **kw)


def _req(prompt, max_tokens):
    return Request(prompt_ids=list(prompt), sampling=SamplingParams(
        temperature=0.0, max_tokens=max_tokens))


def _step(core, requests):
    """One round of the loop; what it put each request: (tokens, finish
    reason or None, tokens per content event)."""
    core._try_insert()
    core._advance_prefill()
    core._decode_active()
    return [collect_events(r, timeout=None) for r in requests]


def _run_inline(core, requests, limit=200):
    """Rounds until every request ended: per request its tokens, its finish
    reason and the content events of every round that put it any."""
    for r in requests:
        core.submit(r)
    tokens = [[] for _ in requests]
    finish = [None] * len(requests)
    rounds = [[] for _ in requests]
    for _ in range(limit):
        for i, (got, reason, sizes) in enumerate(_step(core, requests)):
            tokens[i] += got
            if sizes:
                rounds[i].append(sizes)
            finish[i] = finish[i] or reason
        if all(finish):
            return tokens, finish, rounds
    raise AssertionError("requests did not finish")


PROMPTS = ([5, 9, 2], [7, 7, 7, 7], [3])


@pytest.fixture(scope="module")
def single_step_tokens(cfg):
    """The greedy tokens of PROMPTS, one token a step."""
    core = _inline(cfg, decode_burst=1)
    tokens, finish, _ = _run_inline(core, [_req(p, 40) for p in PROMPTS])
    assert finish == ["length"] * 3
    return tokens


def test_a_burst_puts_one_event_a_live_row(cfg, single_step_tokens):
    """Three rows that end at different places: every round puts a live row
    ONE content event with that row's tokens in order — the activation's
    first token in the same event as the burst it was fetched with — and a
    row that ends inside a burst (max_tokens) gets the tokens before its
    end."""
    core = _inline(cfg)
    limits = (25, 12, 40)
    tokens, finish, rounds = _run_inline(
        core, [_req(p, n) for p, n in zip(PROMPTS, limits)])
    assert finish == ["length"] * 3
    assert tokens == [t[:n] for t, n in zip(single_step_tokens, limits)]
    assert rounds == [[[9], [8], [8]],
                      [[9], [3]],
                      [[9], [8], [8], [8], [7]]]
    assert all(s.request is None for s in core.slots)


def test_the_single_step_path_puts_an_event_a_fetch(cfg):
    """The same rule where a fetch brings one token: a content event of
    one, and of two where the activation's token rides it."""
    _, _, rounds = _run_inline(_inline(cfg, decode_burst=1),
                               [_req(PROMPTS[0], 5)])
    assert rounds == [[[2], [1], [1], [1]]]


@pytest.mark.parametrize("at", [10, 12, 17])
def test_eos_inside_a_burst_takes_the_tokens_before_it(
        cfg, single_step_tokens, at):
    """The token at index `at` of the greedy stream made the EOS id: the
    request ends with "stop" inside the first burst, inside the second, or
    at the head of the third, with exactly the tokens before its first
    occurrence, EOS itself no content, and the slot serves the next
    request."""
    want = single_step_tokens[0]
    eos = want[at]
    cut = want.index(eos)
    core = _inline(cfg, eos_id=eos)
    tokens, finish, rounds = _run_inline(core, [_req(PROMPTS[0], 40)])
    assert (tokens, finish) == ([want[:cut]], ["stop"])
    sizes = [s for (s,) in rounds[0]]
    assert sizes == [n for n in (min(cut, 9), min(cut - 9, 8)) if n > 0]
    again, _, _ = _run_inline(core, [_req(PROMPTS[0], 40)])
    assert again == tokens


@pytest.mark.parametrize("seen", [0, 3, 7])
def test_a_cancel_inside_a_burst_takes_the_tokens_before_it(
        cfg, single_step_tokens, monkeypatch, seen):
    """A cancel that the emit loop sees after `seen` tokens of the second
    burst: one event with those tokens (none for 0), then `done`, nothing
    after it, and the slot is reusable."""
    core = _inline(cfg)
    request = core.submit(_req(PROMPTS[0], 40))
    first, _, _ = _step(core, [request])[0]
    assert first == single_step_tokens[0][:9]
    asked = iter(range(100))
    monkeypatch.setattr(
        core, "_is_cancelled", lambda r: next(asked) >= seen)
    (got, reason, sizes), = _step(core, [request])
    assert got == single_step_tokens[0][9:9 + seen]
    assert reason == "cancelled" and sizes == ([seen] if seen else [])
    assert request.events.empty() and core.slots[0].request is None
    monkeypatch.undo()
    again, finish, _ = _run_inline(core, [_req(PROMPTS[0], 12)])
    assert (again, finish) == ([single_step_tokens[0][:12]], ["length"])


def test_the_speculative_step_puts_one_event_a_row(cfg):
    """A verify step's accepted drafts + 1 of a row are one fetch: one
    content event, of several tokens where drafts were accepted, and the
    tokens are the non-speculating engine's."""
    prompt = [5, 6, 7, 8, 9] * 5  # repetitive: prompt lookup drafts
    want, _, _ = _run_inline(_inline(cfg, decode_burst=1),
                             [_req(prompt, 48)])
    core = _inline(cfg, decode_burst=1, spec_decode=True)
    tokens, finish, rounds = _run_inline(core, [_req(prompt, 48)])
    assert (tokens, finish) == (want, ["length"])
    assert core.metrics.spec_verify_steps_total > 0
    assert all(len(sizes) == 1 for sizes in rounds[0])  # one event a round
    accepted = core.metrics.spec_accepted_tokens_total
    assert accepted > 0 and max(s for (s,) in rounds[0]) > 1
    # every accepted draft saved its row an event
    assert len(rounds[0]) <= 48 - accepted


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

    core.programs.prefill = boom
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
                assert sorted(core.programs.cache) == [
                    ("decode_many", 4, 256, False),
                    ("decode_many", 4, 512, False)]
        finally:
            core._running = False
