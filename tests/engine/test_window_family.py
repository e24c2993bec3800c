"""models/mimo_v2.py on the CPU at a small size, seeded float32 weights
(docs/window-attention.md). The family's record for the suite
(tests/engine/family_suite.py): prefill past the ring -> an extend from a
page boundary and one from mid-page, each LONGER than the ring -> decode
steps that wrap the ring twice and more, against the plain reference's one
whole-sequence pass (benchmark/reference/mimo_v2.py); each one-term control
of benchmark/check_window.py failing the comparison; the shares of the
experts adding up to the uncut layer; rows far apart in length in one step;
what it does not compute refused by name; and, on one engine, its tokens
equal to the reference's greedy ones, rows of 40 and 400 sharing its steps,
park and resume. Its own: the life of a ring — a slot used again by a
shorter request, a row that is not live."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_window
from benchmark.reference import mimo_v2 as reference
from llmlb_tpu.models import config_from_hf
from llmlb_tpu.models import mimo_v2 as family
from llmlb_tpu.models.llama import StatePool
from llmlb_tpu.ops.norms import rms_norm
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Engine,
    Ring,
    Shares,
    test_a_slot_taken_by_a_shorter_request_sees_nothing_of_its_predecessor,
    test_an_engine_that_would_serve_the_family_wrong_does_not_start,
    test_park_and_resume_is_token_identical,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_rows_of_40_and_400_tokens_decode_in_one_step,
    test_rows_of_40_and_400_tokens_share_the_engines_steps,
    test_the_engines_tokens_are_the_references_greedy_tokens,
    test_the_preset_is_the_published_config_read,
    test_the_shares_add_up_to_the_uncut_layer,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

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
# prefill past the ring (24 > W), an extend from a page boundary (24) and
# one from mid-page (44), each longer than the ring, then 40 decode steps:
# the ring wraps six times, two and a half of them a token at a time
SPEC = {"prefill_tokens": 24, "extend_chunks": 2, "extend_tokens": 20,
        "decode_steps": 40, "tolerance": 1e-4, "router_tolerance": 1e-4,
        "flip_margin_multiple": 6.0}
TOTAL = 24 + 2 * 20 + 40


def _reads(cfg):
    record = family.FAMILY
    return [
        ((cfg.held_experts, cfg.router_experts), ((4, 4), 8)),
        ((cfg.layers_of(family.WINDOW), cfg.layers_of(family.GLOBAL),
          cfg.num_moe_layers), (5, 2, 6)),
        ((cfg.rotary_dim, cfg.sliding_window), (8, W)),
        (record.kv_pool_layers(cfg), 2),
        (record.kv_token_layer_bytes(cfg), 2 * (24 + 16) * 4),
        (record.state_slot_bytes(cfg), 5 * W * 4 * (24 + 16) * 4),
        ((record.kv_wire_cell(cfg), record.verifies_drafts), (None, False))]


def _shares():
    """Four chips holding 2 of 8 experts each: a chip's part is its routed
    experts' alone; attention and the token are counted once."""
    whole = {**HF, "n_routed_experts": 8, "expert_parallel": None}
    params = family.init_params(config_from_hf(whole, jnp.float32),
                                jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    names = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down")
    kw = dict(top_k=2, scale=1.0, normalize=True, eps=1e-5)
    full, scores = reference.expert_layer(
        x, 1, *(params[n] for n in names), first=0, **kw)
    parts = []
    for chip in range(4):
        held = {n: params[n][:, 2 * chip:2 * chip + 2] for n in names[3:]}
        part, s = reference.expert_layer(
            x, 1, *(params[n] for n in names[:3]), *held.values(),
            first=2 * chip, **kw)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(scores))
        share_cfg = config_from_hf(
            {**whole, "n_routed_experts": 2, "expert_parallel": {
                "chips": 4, "chip": chip, "experts": 8}}, jnp.float32)
        lp = {**{n: params[n][1] for n in names[:3]}, **held, "layer": 1}
        h = rms_norm(x, lp["ln_mlp"], 1e-5)[None]
        got, _ = family._moe_mlp_fn(share_cfg)(lp, h, None)
        parts.append((got[0], part - x))
    return Shares(full, parts, lambda total: x + total)


def _records(case, core, recs):
    decodes = [r for r in recs if r["kind"] == "decode"]
    assert decodes and all(
        0 < r["window_kv_tokens"] <= r["tokens"] * 5 * W
        and r["global_kv_tokens"] >= r["tokens"] * 2 for r in decodes)
    m = core.metrics.summary()
    assert m["window_kv_tokens_total"] >= sum(
        r["window_kv_tokens"] for r in decodes) > 0
    assert m["global_kv_tokens_total"] > 0


CASE = Case(
    family=family, preset="debug-mimo-tiny", hf=HF, reference=reference,
    page=PAGE, spec=SPEC, tolerance=1e-5, padded=512, reads=_reads,
    # and short chunks, the second from mid-page (29)
    runs=(("two_ring_wraps", {}, 1),
          ("short_chunks", {"extend_tokens": 5, "decode_steps": 8}, 2)),
    controls={name: (lambda params, name=name: CASE.control(
        params, check_window.variants(family, TOTAL)[name]))
        for name in ("full_rotary", "no_sink", "no_value_scale", "no_window",
                     "one_rope_base", "window_129", "window_4_kv_heads")},
    control_spec={"extend_chunks": 0},
    refused=tuple(({key: value}, key) for key, value in (
        ("add_full_attention_sink_bias", True), ("swa_head_dim", 32),
        ("n_shared_experts", 1), ("scoring_func", "softmax"), ("n_group", 2),
        ("rope_scaling", {"rope_type": "yarn"}),
        ("hybrid_layer_pattern", [0, 1, 1, 2, 1, 0, 1]))),
    shares=_shares,
    ring=Ring(slot=lambda state, slot: state[:, slot], decode_to=30,
              counters=lambda n: {"window_kv_tokens": 5 * min(n, W),
                                  "global_kv_tokens": 2 * n}),
    # three requests on two slots, all at once: a prompt of 40 prefills in
    # chunks while the other row decodes in bursts of 4 (the prefilling
    # slot's ring must stay), 40 tokens out wrap the ring twice, and the
    # third request — shorter than the ring — takes a slot whose ring
    # another request filled
    engine=Engine(
        args=dict(num_slots=2, slot_capacity=512, prefill_buckets=(16, 32),
                  kv_page_size=PAGE, decode_burst=4, eos_id=-1),
        requests=tuple((suite.prompt(n, 30 + n), out)
                       for n, out in ((40, 40), (12, 36), (6, 12))),
        records=_records,
        # the 400's ring wraps 25 times before its first token
        long_beside_short=(10, 70),
        refused_starts=(
            (dict(prefix_cache=True), "the prefix cache"),
            (dict(spec_decode=True), "speculative decoding"),
            (dict(kv_ship=True), "kv_ship"),
            (dict(role="split"), "--role split"),
            (dict(quantize="kv"), "int8 page pool"),
            (dict(quantize="weights"), "does not serve int8 weights"),
            (dict(lora_dir="/nonexistent"), "no adapter pools"))))


def test_another_family_refuses_a_window_and_the_pool_is_pages_and_rings():
    # a window or a partial rotary stated for a family that computes none
    for key, value in (("sliding_window", 128), ("partial_rotary_factor", 0.5)):
        with pytest.raises(ValueError, match=key):
            config_from_hf({"model_type": "llama", "vocab_size": 64,
                            "hidden_size": 32, "intermediate_size": 64,
                            "num_hidden_layers": 1, "num_attention_heads": 2,
                            key: value})
    with pytest.raises(NotImplementedError, match="int8 page pool"):
        family.init_kv_pages(CASE.cfg, 4, PAGE, quantized=True)
    pool = family.init_kv_pages(CASE.cfg, 4, PAGE, num_slots=3)
    assert isinstance(pool[0], StatePool)
    assert pool[0].pages.shape == (2, 4, PAGE, 2 * 24)
    assert pool[1].pages.shape == (2, 4, PAGE, 2 * 16)
    assert pool[0].state.shape == (5, 3 + 1, W, 4, 24)
    assert pool[1].state.shape == (5, 3 + 1, W, 4, 16)
