"""models/mimo_v2.py on the CPU at a small size, seeded float32 weights
(docs/window-attention.md): the family's prefill -> extend chunks (from a
page boundary, from mid-page, a chunk longer than the ring) -> 300 decode
steps (the ring wraps many times) against the plain reference's one
whole-sequence pass (benchmark/reference/mimo_v2.py) at EVERY position; each
one-term control failing the comparison; the shares of the experts adding up
to the uncut layer; the life of a ring — a slot used again by a shorter
request, a row that is not live, rows far apart in length in one step; the
configuration read from its published keys and what it does not compute
refused by name; and the engine's tokens equal to the reference's
`generate`, token for token."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_window, correctness
from benchmark.reference import mimo_v2 as reference
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.models import config_from_hf, family_for
from llmlb_tpu.models import mimo_v2 as family
from llmlb_tpu.models.llama import StatePool
from tests.support import collect_events

CFG = get_preset("debug-mimo-tiny")
HF = {
    "model_type": "mimo_v2", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
    "swa_head_dim": 24, "swa_v_head_dim": 16, "sliding_window": 16,
    "sliding_window_size": 16, "attention_chunk_size": 16,
    "partial_rotary_factor": 0.334, "rope_theta": 10000000,
    "swa_rope_theta": 10000, "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv", "attention_bias": False,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "n_routed_experts": 4, "n_shared_experts": None, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": None, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "hidden_act": "silu",
    "layernorm_epsilon": 1e-5, "tie_word_embeddings": False,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "max_position_embeddings": 1024,
    "expert_parallel": {"chips": 2, "chip": 1, "experts": 8},
}
W, PAGE = 16, 8
# prefill past the ring, an extend from a page boundary (24), one from
# mid-page (36) and one LONGER than the ring (41 .. 61), then decode
CHUNKS = (12, 5, 20)
PREFILL, DECODE = 24, 300


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
    extends of CHUNKS (each padded to a bucket of its own), then decode."""
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
        chunk = np.zeros((1, 24), np.int32)
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
    assert (cfg.layers_of(family.WINDOW), cfg.layers_of(family.GLOBAL),
            cfg.num_moe_layers) == (5, 2, 6)
    assert cfg.rotary_dim == 8 and cfg.sliding_window == W
    record = family.FAMILY
    assert record.kv_pool_layers(cfg) == 2
    assert record.kv_token_layer_bytes(cfg) == 2 * (24 + 16) * 4
    assert record.state_slot_bytes(cfg) == 5 * W * 4 * (24 + 16) * 4
    assert record.kv_wire_cell(cfg) is None and not record.verifies_drafts


def test_every_position_matches_the_reference_past_two_ring_wraps(params):
    """Prefill (24 > W), an extend from a page boundary, one from mid-page,
    one longer than the ring, and 300 decode steps: every logit row within
    1e-5 of the whole-sequence reference."""
    ids = _ids(PREFILL + sum(CHUNKS) + DECODE + 1, 1)
    at, got = _serve(params, ids)
    want, _ = reference.forward(params, HF, ids[:at[-1] + 1])
    want = np.asarray(want)[at]
    assert len(at) == 1 + len(CHUNKS) + DECODE and at[-1] >= 18 * W
    np.testing.assert_allclose(got, want, atol=1e-5)


CONTROLS = {
    "no_window": dict(sliding_window=512),
    "window_129": dict(sliding_window=W + 1),
    "no_sink": dict(window_sink=False),
    "no_value_scale": dict(value_scale=1.0),
    "full_rotary": dict(partial_rotary_factor=1.0),
    "one_rope_base": dict(window_rope_theta=CFG.rope_theta),
}


@pytest.mark.parametrize("case", sorted(CONTROLS) + ["window_4_kv_heads"])
def test_a_one_term_control_fails_the_comparison(case, params):
    """Each control this model adds, as benchmark/check_window.py serves it,
    is off the reference by over 1e-3 where the sound program is within
    1e-5."""
    ids = _ids(PREFILL + sum(CHUNKS) + 41, 2)
    served = check_window.variants(family, len(ids))[case]
    cfg = CFG
    if case in CONTROLS:  # the pool is the changed configuration's too
        cfg = dataclasses.replace(CFG, **CONTROLS[case])
        served = family
    at, got = _serve(params, ids, cfg=cfg, fam=served, decode=40)
    want, _ = reference.forward(params, HF, ids[:at[-1] + 1])
    assert np.abs(got - np.asarray(want)[at]).max() > 1e-3


def test_check_windows_variants_serve_the_changed_configuration(params):
    """`check_window.OtherConfig` hands pool and serving functions the
    changed configuration: through `correctness.check` the sound family is
    inside every limit and `window_129` outside the logits'."""
    spec = {"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
            "decode_steps": 20, "tolerance": 1e-4, "router_tolerance": 1e-4,
            "flip_margin_multiple": 6.0}
    sound = correctness.check(family, CFG, params, HF, spec, 3, PAGE,
                              reference)
    assert sound["ok"] and sound["grounds"] == [], sound
    assert sound["max_rel_rms_err"] < 1e-5
    off = correctness.check(check_window.variants(family, 77)["window_129"],
                            CFG, params, HF, spec, 3, PAGE, reference)
    assert "logits" in off["grounds"], off


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips holding 2 of 8 experts each: the sum of every share's
    routed part, attention and the dense layer counted once, is the uncut
    reference's mixture layer."""
    whole = {**HF, "n_routed_experts": 8, "expert_parallel": None}
    cfg = config_from_hf(whole, jnp.float32)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    names = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down")
    kw = dict(top_k=2, scale=1.0, normalize=True, eps=1e-5)
    full, scores = reference.expert_layer(
        x, 1, *(params[n] for n in names), first=0, **kw)
    total = jnp.zeros_like(x)
    for chip in range(4):
        held = {n: params[n][:, 2 * chip:2 * chip + 2]
                for n in ("we_gate", "we_up", "we_down")}
        part, s = reference.expert_layer(
            x, 1, params["ln_mlp"], params["router"], params["router_bias"],
            held["we_gate"], held["we_up"], held["we_down"],
            first=2 * chip, **kw)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(scores))
        total = total + (part - x)  # the share's routed part
        # the program's share is the reference's share
        share_cfg = config_from_hf({**whole, "n_routed_experts": 2,
                                    "expert_parallel": {
                                        "chips": 4, "chip": chip,
                                        "experts": 8}}, jnp.float32)
        lp = {**{n: params[n][1] for n in ("ln_mlp", "router", "router_bias")},
              **held, "layer": 1}
        from llmlb_tpu.ops.norms import rms_norm

        h = rms_norm(x, lp["ln_mlp"], 1e-5)[None]
        got, _ = family._moe_mlp_fn(share_cfg)(lp, h, None)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(part - x),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(x + total), np.asarray(full),
                               atol=1e-5)


def test_a_slot_taken_by_a_shorter_request_sees_nothing_of_its_predecessor(params):
    """A prompt of 40 fills slot 0's ring; a prompt of 5 prefilled into the
    same slot (cells >= 5 still hold the other's keys) decodes as if the
    ring were fresh; a row that is not live beside it writes the trash ring
    and leaves slot 1's ring as it was."""
    long_ids, short_ids = _ids(40, 5), _ids(30, 6)
    ck, cv = _pool(16, slots=2)
    tables = _table(8, rows=2)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :40] = long_ids
    _, ck, cv, _ = family.prefill_into_pages(
        params, CFG, jnp.asarray(padded), jnp.asarray([40]), tables[:1], ck,
        cv, slot_ids=jnp.asarray([0]))
    _, ck, cv, _ = family.prefill_into_pages(
        params, CFG, jnp.asarray(padded), jnp.asarray([40]), tables[1:], ck,
        cv, slot_ids=jnp.asarray([1]))
    other_ring = np.asarray(ck.state[:, 1])
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = short_ids[:5]
    logits, ck, cv, _ = family.prefill_into_pages(
        params, CFG, jnp.asarray(padded), jnp.asarray([5]), tables[:1], ck,
        cv, slot_ids=jnp.asarray([0]))
    want, _ = reference.forward(params, HF, short_ids)
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(logits[0]), want[4], atol=1e-5)
    live = jnp.asarray([True, False])
    for pos in range(5, 30):
        logits, ck, cv, counters = family.decode_step_paged(
            params, CFG, jnp.asarray([short_ids[pos], 9]),
            jnp.asarray([pos, 127]), ck, cv, tables, window=64, live=live)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos], atol=1e-5)
        assert int(counters["window_kv_tokens"]) == 5 * min(pos + 1, W)
        assert int(counters["global_kv_tokens"]) == 2 * (pos + 1)
    np.testing.assert_array_equal(np.asarray(ck.state[:, 1]), other_ring)


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
                                       want[r][pos[r]], atol=1e-5)


def test_what_the_family_does_not_compute_is_refused_by_name():
    for key, value in (("add_full_attention_sink_bias", True),
                       ("swa_head_dim", 32), ("n_shared_experts", 1),
                       ("scoring_func", "softmax"), ("n_group", 2),
                       ("rope_scaling", {"rope_type": "yarn"}),
                       ("hybrid_layer_pattern", [0, 1, 1, 2, 1, 0, 1])):
        with pytest.raises(NotImplementedError, match=key):
            config_from_hf({**HF, key: value}, jnp.float32)
    # a window or a partial rotary stated for a family that computes none
    for key, value in (("sliding_window", 128), ("partial_rotary_factor", 0.5)):
        with pytest.raises(ValueError, match=key):
            config_from_hf({"model_type": "llama", "vocab_size": 64,
                            "hidden_size": 32, "intermediate_size": 64,
                            "num_hidden_layers": 1, "num_attention_heads": 2,
                            key: value})
    with pytest.raises(NotImplementedError, match="int8 page pool"):
        family.init_kv_pages(CFG, 4, PAGE, quantized=True)
    pool = family.init_kv_pages(CFG, 4, PAGE, num_slots=3)
    assert isinstance(pool[0], StatePool)
    assert pool[0].pages.shape == (2, 4, PAGE, 2 * 24)
    assert pool[1].pages.shape == (2, 4, PAGE, 2 * 16)
    assert pool[0].state.shape == (5, 3 + 1, W, 4, 24)
    assert pool[1].state.shape == (5, 3 + 1, W, 4, 16)


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


def _assert_generated(params, prompt, tokens):
    want = reference.generate(params, HF, prompt, len(tokens))
    assert tokens == want, (len(prompt), tokens, want)


def test_the_engines_tokens_are_the_references_generate(served):
    """Three requests on two slots, all at once: a prompt of 40 prefills in
    chunks while the other row decodes in bursts of 4 (the burst steps every
    slot: the prefilling slot's ring must stay), 40 tokens out wrap the ring
    twice, and the third request — shorter than the ring — takes a slot
    whose ring another request filled."""
    core, params = served
    prompts = [_prompt(n, 30 + n) for n in (40, 12, 6)]
    requests = [_submit(core, p, n) for p, n in zip(prompts, (40, 36, 12))]
    for prompt, request, n in zip(prompts, requests, (40, 36, 12)):
        tokens, reason, _ = collect_events(request, 600)
        assert reason == "length" and len(tokens) == n
        _assert_generated(params, prompt, tokens)
    recs = core.step_stats.snapshot(limit=512)["records"]
    decodes = [r for r in recs if r["kind"] == "decode"]
    assert decodes and all(
        0 < r["window_kv_tokens"] <= r["tokens"] * 5 * W
        and r["global_kv_tokens"] >= r["tokens"] * 2 for r in decodes)
    m = core.metrics.summary()
    assert m["window_kv_tokens_total"] >= sum(
        r["window_kv_tokens"] for r in decodes) > 0
    assert m["global_kv_tokens_total"] > m["window_kv_tokens_total"] * 0
    assert core.quant_info()["state_bytes"] == 2 * family.state_slot_bytes(CFG)


def test_rows_of_40_and_400_tokens_share_the_engines_steps(served):
    """A prompt of 400 tokens (chunks of 32 through the extend path, its
    ring wrapped 25 times before the first token) beside one of 40: once
    both decode, every burst steps a row at a context of 400 and one at 40;
    both streams are the reference's."""
    core, params = served
    prompts = [_prompt(400, 90), _prompt(40, 91)]
    requests = [_submit(core, p, n) for p, n in zip(prompts, (10, 70))]
    for prompt, request, n in zip(prompts, requests, (10, 70)):
        tokens, reason, _ = collect_events(request, 600)
        assert reason == "length" and len(tokens) == n
        _assert_generated(params, prompt, tokens)
    recs = core.step_stats.snapshot(limit=512)["records"]
    both = [r for r in recs if r["kind"] == "decode"
            and r["active_slots"] == 2
            and r["global_kv_tokens"] >= 2 * 400 * (r["tokens"] // 2)]
    assert both, "no burst stepped the long row and the short one together"


def test_park_and_resume_is_token_identical():
    """One slot: a low-priority request parks mid-generation for a
    high-priority arrival and resumes by replaying prompt + tokens through
    prefill and extend (nothing of the ring is kept); both streams are the
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
def test_an_engine_that_would_serve_a_ring_wrong_does_not_start(kw, message):
    params = jax.eval_shape(lambda: family.init_params(
        CFG, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match=message):
        EngineCore(CFG, params, **{**ARGS, **kw})
