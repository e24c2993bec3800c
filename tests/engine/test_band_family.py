"""models/afmoe.py on the CPU at a small size, seeded float32 weights
(docs/afmoe.md): the family's prefill -> extend chunks (from a page
boundary, from mid-page, a chunk longer than the band) -> 120 decode steps
(the band wraps many times) against the plain reference's one
whole-sequence pass (benchmark/reference/afmoe.py) at EVERY position; each
one-term control of benchmark/check_band.py failing the comparison; the
shares of the experts adding up to the uncut layer; the life of a band — a
slot used again by a shorter request, a row that is not live, rows far
apart in length in one step; what a window layer's decode reads; the
configuration read from its published keys and what it does not compute
refused by name; and the engine's tokens equal to the reference's
`generate`, token for token."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_band
from benchmark.reference import afmoe as reference
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.models import afmoe as family
from llmlb_tpu.models import config_from_hf, family_for
from llmlb_tpu.models.llama import StatePool
from llmlb_tpu.ops.attention import traced_routes
from llmlb_tpu.ops.pallas_attention import decode_work_list
from tests.support import collect_events

CFG = get_preset("debug-trinity-tiny")
S, F = "sliding_attention", "full_attention"
HF = {
    "model_type": "afmoe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 6, "layer_types": [S, S, S, F, S, S],
    "global_attn_every_n_layers": 4, "num_dense_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 16, "band_page_size": 8, "rope_theta": 10000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "num_experts": 4, "num_experts_per_tok": 2, "num_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "mup_enabled": True, "load_balance_coeff": 0.001,
    "use_grouped_mm": True, "tie_word_embeddings": False,
    "max_position_embeddings": 1024,
    "expert_parallel": {"chips": 2, "chip": 1, "experts": 8},
}
W, PAGE, R = 16, 8, 3
N_W, N_G = 5, 1
# prefill past the band, an extend from a page boundary (24), one from
# mid-page (36) and one LONGER than the band (41 .. 71), then decode
CHUNKS = (12, 5, 30)
PREFILL, DECODE = 24, 120


@pytest.fixture(scope="module")
def params():
    return family.init_params(CFG, jax.random.PRNGKey(7))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def _pool(pages, slots=1, cfg=CFG):
    return family.init_kv_pages(cfg, pages + 1, PAGE, num_slots=slots)


def _table(pages, rows=1):
    return jnp.asarray(1 + np.arange(rows * pages, dtype=np.int32)
                       .reshape(rows, pages))


def _serve(params, ids, cfg=CFG, fam=family, decode=DECODE):
    """Logits at every position from PREFILL - 1 on: one prefill, the
    extends of CHUNKS (each padded to one bucket), then decode."""
    total = PREFILL + sum(CHUNKS) + decode
    pages = -(-total // PAGE)
    ck, cv = fam.init_kv_pages(cfg, pages + 1, PAGE)
    table = _table(pages)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :PREFILL] = ids[:PREFILL]
    logits, ck, cv, _ = fam.prefill_into_pages(
        params, cfg, jnp.asarray(padded), jnp.asarray([PREFILL]), table, ck, cv)
    rows, pos = {PREFILL - 1: logits[0]}, PREFILL
    for n in CHUNKS:
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :n] = ids[pos:pos + n]
        logits, ck, cv, _ = fam.prefill_extend_pages(
            params, cfg, jnp.asarray(chunk), jnp.asarray([n]),
            jnp.asarray([pos]), table, ck, cv)
        pos += n
        rows[pos - 1] = logits[0]
    for _ in range(decode):
        logits, ck, cv, _ = fam.decode_step_paged(
            params, cfg, jnp.asarray(ids[pos:pos + 1]), jnp.asarray([pos]),
            ck, cv, table, window=pages * PAGE)
        rows[pos] = logits[0]
        pos += 1
    at = sorted(rows)
    return at, np.stack([np.asarray(rows[p]) for p in at])


def test_the_preset_is_the_published_config_read():
    cfg = config_from_hf(HF, jnp.float32)
    assert cfg == CFG and family_for(cfg) is family
    assert cfg.held_experts == (4, 4) and cfg.router_experts == 8
    assert (cfg.layers_of(S), cfg.layers_of(F), cfg.num_moe_layers) == (
        N_W, N_G, 4)
    assert (cfg.band_pages, cfg.band_cells) == (R, R * PAGE)
    record = family.FAMILY
    assert record.kv_pool_layers(cfg) == N_G
    assert record.kv_token_layer_bytes(cfg) == 2 * 2 * 16 * 4
    assert record.state_slot_bytes(cfg) == N_W * R * PAGE * 2 * 2 * 16 * 4
    assert record.kv_wire_cell(cfg) is None and not record.verifies_drafts


def test_the_published_row_is_read_and_its_band_is_seventeen_pages():
    import json

    with open("benchmark/configs/trinity-mini-l16.json") as f:
        cfg = config_from_hf(json.load(f), jnp.bfloat16)
    assert (cfg.band_pages, cfg.band_cells) == (17, 2176)
    assert family.state_slot_bytes(cfg) == 12 * 2176 * 2048
    assert (cfg.layers_of(S), cfg.layers_of(F)) == (12, 4)
    assert cfg.held_experts == (0, 16) and cfg.router_experts == 128


def test_every_position_matches_the_reference_past_many_band_wraps(params):
    """Prefill (24 > W), an extend from a page boundary, one from mid-page,
    one longer than the band, and 120 decode steps: every logit row within
    1e-4 of the whole-sequence reference (the embedding's factor of 8 is in
    the residual stream)."""
    ids = _ids(PREFILL + sum(CHUNKS) + DECODE + 1, 1)
    at, got = _serve(params, ids)
    want, _ = reference.forward(params, HF, ids[:at[-1] + 1])
    want = np.asarray(want)[at]
    assert len(at) == 1 + len(CHUNKS) + DECODE and at[-1] >= 7 * R * PAGE
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_both_routes_of_the_band_decode_agree_with_the_reference(
        params, route, monkeypatch):
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", route)
    jax.clear_caches()  # the route is read while a program is traced
    try:
        ids = _ids(PREFILL + sum(CHUNKS) + 31, 4)
        at, got = _serve(params, ids, decode=30)
        assert traced_routes()["band_decode"] == {
            "pallas": "pallas:paged_band_decode", "xla": "xla"}[route]
    finally:
        jax.clear_caches()
    want, _ = reference.forward(params, HF, ids[:at[-1] + 1])
    np.testing.assert_allclose(got, np.asarray(want)[at], atol=1e-4)


# (`window_plus_one`, `no_lower_mask`, `no_shared_expert` and the controls of
# the routing go through check_band's own loop and `correctness.check` in
# tests/benchmark/test_band_moe.py)
CONTROLS = ("no_window", "global_rotary", "no_window_rotary", "no_gate",
            "no_attn_out_norm", "no_mlp_out_norm", "no_qk_norm",
            "no_embed_scale", "no_route_scale")


@pytest.mark.parametrize("case", CONTROLS)
def test_a_one_term_control_fails_the_comparison(case, params):
    """Each control this model adds, as benchmark/check_band.py serves it,
    is off the reference by over 1e-2 where the sound program is within
    1e-4."""
    ids = _ids(PREFILL + sum(CHUNKS) + 26, 2)
    served = check_band.variants(family, len(ids))[case]
    at, got = _serve(params, ids, fam=served, decode=25)
    want, _ = reference.forward(params, HF, ids[:at[-1] + 1])
    assert np.abs(got - np.asarray(want)[at]).max() > 1e-2


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips holding 2 of 8 experts each: the sum of every share's
    routed part, with the shared expert (which every chip computes alike)
    counted once, is the uncut reference's mixture layer before its second
    norm; and the program's share is the reference's share."""
    from benchmark.reference import dense
    from llmlb_tpu.ops.norms import rms_norm

    whole = {**HF, "num_experts": 8, "expert_parallel": None}
    cfg = config_from_hf(whole, jnp.float32)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    m = dense.rms_norm(x, params["ln_mlp"][1], 1e-5)
    shared = dense.swiglu(m, params["ws_gate"][1], params["ws_up"][1],
                          params["ws_down"][1])
    lp = {n: params[n][1] for n in ("router", "router_bias", "ws_gate",
                                    "ws_up", "ws_down")}
    got_whole, _ = family._moe_mlp_fn(cfg)(
        {**lp, **{n: params[n] for n in ("we_gate", "we_up", "we_down")},
         "layer": 1}, m[None], None)
    total = jnp.zeros_like(x)
    for chip in range(4):
        share_cfg = config_from_hf(
            {**whole, "num_experts": 2, "expert_parallel": {
                "chips": 4, "chip": chip, "experts": 8}}, jnp.float32)
        held = {n: params[n][:, 2 * chip:2 * chip + 2]
                for n in ("we_gate", "we_up", "we_down")}
        got, _ = family._moe_mlp_fn(share_cfg)({**lp, **held, "layer": 1},
                                               m[None], None)
        total = total + (got[0] - shared)  # the share's routed part
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(got_whole[0]), atol=1e-5)
    # the uncut reference layer is x + norm(that sum)
    names = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
             "ws_gate", "ws_up", "ws_down", "ln_mlp_out")
    full, _ = reference.expert_layer(
        x, 1, *(params[n] for n in names), top_k=2, scale=2.826,
        normalize=True, first=0, eps=1e-5)
    np.testing.assert_allclose(
        np.asarray(x + rms_norm(total + shared, params["ln_mlp_out"][1],
                                1e-5)), np.asarray(full), atol=1e-5)


def test_a_slot_taken_by_a_shorter_request_sees_nothing_of_its_predecessor(params):
    """A prompt of 40 fills slot 0's band; a prompt of 5 prefilled into the
    same slot (cells >= 5 still hold the other's keys) decodes as if the
    band were fresh; a row that is not live beside it writes the trash band
    and leaves slot 1's band as it was. The counters are the cells and the
    pages the step's work-lists named."""
    long_ids, short_ids = _ids(40, 5), _ids(40, 6)
    ck, cv = _pool(16, slots=2)
    tables = _table(8, rows=2)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :40] = long_ids
    for slot in (0, 1):
        _, ck, cv, _ = family.prefill_into_pages(
            params, CFG, jnp.asarray(padded), jnp.asarray([40]),
            tables[slot:slot + 1], ck, cv, slot_ids=jnp.asarray([slot]))
    other_band = np.asarray(ck.state[:, R:2 * R])
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = short_ids[:5]
    logits, ck, cv, _ = family.prefill_into_pages(
        params, CFG, jnp.asarray(padded), jnp.asarray([5]), tables[:1], ck,
        cv, slot_ids=jnp.asarray([0]))
    want, _ = reference.forward(params, HF, short_ids)
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(logits[0]), want[4], atol=1e-4)
    live = jnp.asarray([True, False])
    for pos in range(5, 40):
        logits, ck, cv, counters = family.decode_step_paged(
            params, CFG, jnp.asarray([short_ids[pos], 9]),
            jnp.asarray([pos, 127]), ck, cv, tables, window=64, live=live)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos], atol=1e-4)
        n = pos + 1
        assert int(counters["window_kv_tokens"]) == N_W * min(n, W)
        assert int(counters["global_kv_tokens"]) == N_G * n
        pages = (n - 1) // PAGE - max(n - W, 0) // PAGE + 1
        assert int(counters["window_pages_read"]) == N_W * pages
    np.testing.assert_array_equal(np.asarray(ck.state[:, R:2 * R]), other_band)


@pytest.mark.parametrize("n", [1, 8, 9, 16, 17, 24, 25, 100, 1000])
def test_a_window_layers_decode_reads_at_most_a_window_and_a_page(n):
    """The bound on what a window layer's decode reads, from the work-list
    its call is handed: at most W + PAGE cells a row at any context, and
    ceil(len / PAGE) pages while len <= W."""
    lens = jnp.asarray([n], jnp.int32)
    pages = int(family.band_pages_read(CFG, lens)[0])
    work = decode_work_list(jnp.arange(R, dtype=jnp.int32)[None], lens,
                            page_size=PAGE, kv_from=jnp.maximum(lens - W, 0))
    assert int(work.count) == pages and pages * PAGE <= W + PAGE
    assert pages <= R
    if n <= W:
        assert pages == -(-n // PAGE)


def test_rows_of_40_and_400_tokens_decode_in_one_step(params):
    """Two rows far apart in length, prefilled as a group of unlike lengths
    through chunks, then decoded together: each row's logits are its own
    sequence's."""
    ids = [_ids(44, 7), _ids(404, 8)]
    want = [np.asarray(reference.forward(params, HF, s)[0]) for s in ids]
    pages = 52
    ck, cv = _pool(2 * pages, slots=2)
    tables = _table(pages, rows=2)
    lens = np.asarray([40, 400])
    start = np.zeros(2, np.int32)
    while (start < lens).any():  # chunks of 64, the rows at their own pace
        n = np.minimum(lens - start, 64)
        chunk = np.zeros((2, 64), np.int32)
        for r in range(2):
            chunk[r, :n[r]] = ids[r][start[r]:start[r] + n[r]]
        _, ck, cv, _ = family.prefill_extend_pages(
            params, CFG, jnp.asarray(chunk), jnp.asarray(n),
            jnp.asarray(start), tables, ck, cv, slot_ids=jnp.asarray([0, 1]))
        start = start + n
    for step in range(4):
        pos = lens + step
        logits, ck, cv, _ = family.decode_step_paged(
            params, CFG, jnp.asarray([ids[r][pos[r]] for r in range(2)]),
            jnp.asarray(pos), ck, cv, tables, window=pages * PAGE)
        for r in range(2):
            np.testing.assert_allclose(np.asarray(logits[r]),
                                       want[r][pos[r]], atol=1e-4)


def test_what_the_family_does_not_compute_is_refused_by_name():
    for key, value in (("score_func", "softmax"), ("n_group", 2),
                       ("num_expert_groups", 2), ("attention_bias", True),
                       ("rope_scaling", {"rope_type": "yarn"}),
                       ("global_attn_every_n_layers", 3),
                       ("layer_types", [S, S, S, F, S, "linear_attention"]),
                       ("tie_word_embeddings", True)):
        with pytest.raises(NotImplementedError, match=key):
            config_from_hf({**HF, key: value}, jnp.float32)
    # the embedding's factor rides layer 0's window mixer
    with pytest.raises(NotImplementedError, match="mup_enabled"):
        config_from_hf({**HF, "layer_types": [F, S, S, S, F, S],
                        "global_attn_every_n_layers": None}, jnp.float32)
    # this family's keys stated for a family that computes none of them
    for key, value in (("num_dense_layers", 2), ("mup_enabled", True),
                       ("route_scale", 2.826), ("num_shared_experts", 2)):
        with pytest.raises(ValueError, match=key):
            config_from_hf({"model_type": "llama", "vocab_size": 64,
                            "hidden_size": 32, "intermediate_size": 64,
                            "num_hidden_layers": 1, "num_attention_heads": 2,
                            key: value})
    with pytest.raises(NotImplementedError, match="int8 page pool"):
        family.init_kv_pages(CFG, 4, PAGE, quantized=True)
    pool = family.init_kv_pages(CFG, 4, 4, num_slots=3)
    assert isinstance(pool[0], StatePool)
    assert pool[0].pages.shape == pool[1].pages.shape == (N_G, 4, 4, 2, 16)
    # the band's page is the configuration's, whatever the pool's
    assert pool[0].state.shape == pool[1].state.shape == (
        N_W, (3 + 1) * R, PAGE, 2, 16)


# ---------------------------------------------------------------------------
# The engine: tokens equal the reference's `generate`, token for token
# ---------------------------------------------------------------------------

ARGS = dict(num_slots=2, slot_capacity=512, prefill_buckets=(16, 32),
            kv_page_size=PAGE, decode_burst=4, eos_id=-1)


@pytest.fixture(scope="module")
def served():
    params = family.init_params(CFG, jax.random.PRNGKey(0))
    core = EngineCore(CFG, params, **ARGS)
    core.start()
    yield core, params
    core.stop()


def _submit(core, prompt, max_tokens, **sampling):
    return core.submit(Request(prompt_ids=prompt, sampling=SamplingParams(
        max_tokens=max_tokens, temperature=0.0, **sampling)))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(8, 500, size=n).tolist()


def _assert_generated(params, prompt, tokens, by_generate=False):
    """`tokens` are the reference's greedy tokens after `prompt`: by its
    `generate` (a whole-sequence pass a token), or by ONE pass over prompt
    and tokens, whose largest logit at every position from the prompt's last
    on is the next token — the same statement, by induction over the
    tokens."""
    if by_generate:
        want = reference.generate(params, HF, prompt, len(tokens))
    else:
        logits, _ = reference.forward(
            params, HF, np.asarray(prompt + tokens[:-1], np.int32))
        want = np.argmax(np.asarray(logits)[len(prompt) - 1:], -1).tolist()
    assert tokens == want, (len(prompt), tokens, want)


def test_the_engines_tokens_are_the_references_generate(served):
    """Three requests on two slots, all at once: a prompt of 40 prefills in
    chunks while the other row decodes in bursts of 4 (the burst steps every
    slot: the prefilling slot's band must stay), 40 tokens out wrap the
    band, and the third request — shorter than the window — takes a slot
    whose band another request filled."""
    core, params = served
    prompts = [_prompt(n, 30 + n) for n in (40, 12, 6)]
    requests = [_submit(core, p, n) for p, n in zip(prompts, (40, 36, 12))]
    for prompt, request, n in zip(prompts, requests, (40, 36, 12)):
        tokens, reason, _ = collect_events(request, 600)
        assert reason == "length" and len(tokens) == n
        _assert_generated(params, prompt, tokens, by_generate=n == 12)
    recs = core.step_stats.snapshot(limit=512)["records"]
    decodes = [r for r in recs if r["kind"] == "decode"]
    assert decodes and all(
        0 < r["window_kv_tokens"] <= r["tokens"] * N_W * W
        and r["global_kv_tokens"] >= r["tokens"] * N_G
        and 0 < r["window_pages_read"] <= r["tokens"] * N_W * R
        for r in decodes)
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert prefills and all(r["window_pages_read"] == 0
                            and r["window_kv_tokens"] > 0 for r in prefills)
    m = core.metrics.summary()
    assert m["window_pages_read_total"] >= sum(
        r["window_pages_read"] for r in decodes) > 0
    assert m["window_kv_tokens_total"] > 0 and m["global_kv_tokens_total"] > 0
    assert core.quant_info()["state_bytes"] == 2 * family.state_slot_bytes(CFG)


def test_rows_of_40_and_400_tokens_share_the_engines_steps(served):
    """A prompt of 400 tokens (chunks of 32 through the extend path, its
    band wrapped 16 times before the first token) beside one of 40: once
    both decode, every burst steps a row at a context of 400 and one at 40;
    both streams are the reference's."""
    core, params = served
    prompts = [_prompt(400, 90), _prompt(40, 91)]
    requests = [_submit(core, p, n) for p, n in zip(prompts, (10, 50))]
    for prompt, request, n in zip(prompts, requests, (10, 50)):
        tokens, reason, _ = collect_events(request, 600)
        assert reason == "length" and len(tokens) == n
        _assert_generated(params, prompt, tokens)
    recs = core.step_stats.snapshot(limit=512)["records"]
    both = [r for r in recs if r["kind"] == "decode"
            and r["active_slots"] == 2
            and r["global_kv_tokens"] >= N_G * 400 * (r["tokens"] // 2)]
    assert both, "no burst stepped the long row and the short one together"


def test_park_and_resume_is_token_identical():
    """One slot: a low-priority request parks mid-generation for a
    high-priority arrival and resumes by replaying prompt + tokens through
    prefill and extend (nothing of the band is kept); both streams are the
    reference's."""
    params = family.init_params(CFG, jax.random.PRNGKey(0))
    core = EngineCore(CFG, params, **{**ARGS, "num_slots": 1,
                                      "decode_burst": 2})
    core.start()
    try:
        victim_prompt, other_prompt = _prompt(20, 80), _prompt(9, 81)
        victim = _submit(core, victim_prompt, 30, priority=2)
        deadline = time.monotonic() + 120
        while core.slots[0].generated < 6 and time.monotonic() < deadline:
            time.sleep(0.005)
        other = _submit(core, other_prompt, 7, priority=0)
        got_other, _, _ = collect_events(other, 600)
        got_victim, reason, _ = collect_events(victim, 600)
        assert core.metrics.preemptions_total >= 1
        assert reason == "length" and len(got_victim) == 30
        _assert_generated(params, other_prompt, got_other)
        _assert_generated(params, victim_prompt, got_victim)
    finally:
        core.stop()


@pytest.mark.parametrize("kw,message", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(kv_ship=True), "kv_ship"),
    (dict(role="split"), "--role split"),
    (dict(quantize="kv"), "int8 page pool"),
    (dict(quantize="weights"), "does not serve int8 weights"),
    (dict(lora_dir="/nonexistent"), "no adapter pools"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_an_engine_that_would_serve_a_band_wrong_does_not_start(kw, message):
    params = jax.eval_shape(lambda: family.init_params(
        CFG, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match=message):
        EngineCore(CFG, params, **{**ARGS, **kw})
