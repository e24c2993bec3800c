"""models/afmoe.py on the CPU at a small size, seeded float32 weights
(docs/afmoe.md). The family's record for the suite
(tests/engine/family_suite.py): prefill past the band -> an extend from a
page boundary and one from mid-page, each LONGER than the band -> decode
steps that cross two band pages and more, against the plain reference's one
whole-sequence pass (benchmark/reference/afmoe.py); each one-term control of
benchmark/check_band.py failing the comparison; the shares of the experts
adding up to the uncut layer; rows far apart in length in one step; what it
does not compute refused by name; and, on one engine, its tokens equal to
the reference's greedy ones, rows of 40 and 400 sharing its steps, park and
resume. Its own: both routes of the band's decode; the life of a band — a
slot used again by a shorter request, a row that is not live; what a window
layer's decode reads; the published row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_band, correctness
from benchmark.reference import afmoe as reference
from benchmark.reference import dense
from llmlb_tpu.models import afmoe as family
from llmlb_tpu.models import config_from_hf
from llmlb_tpu.models.llama import StatePool
from llmlb_tpu.ops.attention import traced_routes
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.pallas_attention import decode_work_list
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
# prefill past the band's window (24 > W), an extend from a page boundary
# (24) and one from mid-page (54), each longer than the band (R pages), then
# 40 decode steps: the band's pages are written round more than four times
# (the embedding's factor of 8 is in the residual stream: 1e-4)
SPEC = {"prefill_tokens": 24, "extend_chunks": 2, "extend_tokens": 30,
        "decode_steps": 40, "tolerance": 1e-4, "router_tolerance": 1e-4,
        "flip_margin_multiple": 6.0}
TOTAL = 24 + 2 * 30 + 40


def _reads(cfg):
    record = family.FAMILY
    return [
        ((cfg.held_experts, cfg.router_experts), ((4, 4), 8)),
        ((cfg.layers_of(S), cfg.layers_of(F), cfg.num_moe_layers),
         (N_W, N_G, 4)),
        ((cfg.band_pages, cfg.band_cells), (R, R * PAGE)),
        (record.kv_pool_layers(cfg), N_G),
        (record.kv_token_layer_bytes(cfg), 2 * 2 * 16 * 4),
        (record.state_slot_bytes(cfg), N_W * R * PAGE * 2 * 2 * 16 * 4),
        ((record.kv_wire_cell(cfg), record.verifies_drafts), (None, False))]


def _shares():
    """Four chips holding 2 of 8 experts each: a chip's part is its routed
    experts' alone (its layer less the shared expert, which every chip
    computes alike), held against the program's uncut layer; the uncut
    reference's layer is x + norm(the parts and the shared expert)."""
    whole = {**HF, "num_experts": 8, "expert_parallel": None}
    cfg = config_from_hf(whole, jnp.float32)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    m = dense.rms_norm(x, params["ln_mlp"][1], 1e-5)
    shared = dense.swiglu(m, params["ws_gate"][1], params["ws_up"][1],
                          params["ws_down"][1])
    experts = ("we_gate", "we_up", "we_down")
    lp = {n: params[n][1] for n in ("router", "router_bias", "ws_gate",
                                    "ws_up", "ws_down")}
    got_whole, _ = family._moe_mlp_fn(cfg)(
        {**lp, **{n: params[n] for n in experts}, "layer": 1}, m[None], None)
    parts = []
    for chip in range(4):
        share_cfg = config_from_hf(
            {**whole, "num_experts": 2, "expert_parallel": {
                "chips": 4, "chip": chip, "experts": 8}}, jnp.float32)
        held = {n: params[n][:, 2 * chip:2 * chip + 2] for n in experts}
        got, _ = family._moe_mlp_fn(share_cfg)({**lp, **held, "layer": 1},
                                               m[None], None)
        parts.append(got[0] - shared)
    total = sum(parts)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(got_whole[0]), atol=1e-5)
    names = ("ln_mlp", "router", "router_bias", *experts, "ws_gate", "ws_up",
             "ws_down", "ln_mlp_out")
    full, _ = reference.expert_layer(
        x, 1, *(params[n] for n in names), top_k=2, scale=2.826,
        normalize=True, first=0, eps=1e-5)
    return Shares(full, [(part, part) for part in parts], lambda total: (
        x + rms_norm(total + shared, params["ln_mlp_out"][1], 1e-5)))


def _records(case, core, recs):
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


CASE = Case(
    family=family, preset="debug-trinity-tiny", hf=HF, reference=reference,
    page=PAGE, spec=SPEC, tolerance=1e-4, atol=1e-4, padded=512, reads=_reads,
    runs=(("two_band_pages", {}, 1),),
    # (`window_plus_one`, `no_lower_mask`, `no_shared_expert` and the controls
    # of the routing go through check_band's own loop in
    # tests/benchmark/test_band_moe.py)
    controls={name: (lambda params, name=name: CASE.control(
        params, check_band.variants(family, TOTAL)[name]))
        for name in ("no_window", "global_rotary", "no_window_rotary",
                     "no_gate", "no_attn_out_norm", "no_mlp_out_norm",
                     "no_qk_norm", "no_embed_scale", "no_route_scale")},
    control_fails_by=1e-2,
    control_spec={"extend_chunks": 0},
    refused=tuple(({key: value}, key) for key, value in (
        ("score_func", "softmax"), ("n_group", 2), ("num_expert_groups", 2),
        ("attention_bias", True), ("rope_scaling", {"rope_type": "yarn"}),
        ("global_attn_every_n_layers", 3),
        ("layer_types", [S, S, S, F, S, "linear_attention"]),
        ("tie_word_embeddings", True))),
    shares=_shares,
    ring=Ring(slot=lambda state, slot: state[:, slot * R:(slot + 1) * R],
              decode_to=40, counters=lambda n: {
                  "window_kv_tokens": N_W * min(n, W),
                  "global_kv_tokens": N_G * n,
                  "window_pages_read": N_W * (
                      (n - 1) // PAGE - max(n - W, 0) // PAGE + 1)}),
    # three requests on two slots, all at once: a prompt of 40 prefills in
    # chunks while the other row decodes in bursts of 4 (the prefilling
    # slot's band must stay), 40 tokens out wrap the band, and the third
    # request — shorter than the window — takes a slot whose band another
    # request filled
    engine=Engine(
        args=dict(num_slots=2, slot_capacity=512, prefill_buckets=(16, 32),
                  kv_page_size=PAGE, decode_burst=4, eos_id=-1),
        requests=tuple((suite.prompt(n, 30 + n), out)
                       for n, out in ((40, 40), (12, 36), (6, 12))),
        records=_records,
        # the 400's band wraps 16 times before its first token
        long_beside_short=(10, 50),
        refused_starts=(
            (dict(prefix_cache=True), "the prefix cache"),
            (dict(spec_decode=True), "speculative decoding"),
            (dict(kv_ship=True), "kv_ship"),
            (dict(role="split"), "--role split"),
            (dict(quantize="kv"), "int8 page pool"),
            (dict(quantize="weights"), "does not serve int8 weights"),
            (dict(lora_dir="/nonexistent"), "no adapter pools"))))


def test_the_published_row_is_read_and_its_band_is_seventeen_pages():
    import json

    with open("benchmark/configs/trinity-mini-l16.json") as f:
        cfg = config_from_hf(json.load(f), jnp.bfloat16)
    assert (cfg.band_pages, cfg.band_cells) == (17, 2176)
    assert family.state_slot_bytes(cfg) == 12 * 2176 * 2048
    assert (cfg.layers_of(S), cfg.layers_of(F)) == (12, 4)
    assert cfg.held_experts == (0, 16) and cfg.router_experts == 128


@pytest.mark.parametrize("route", ["pallas", "xla"])
def test_both_routes_of_the_band_decode_agree_with_the_reference(
        params, route, monkeypatch):
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", route)
    jax.clear_caches()  # the route is read while a program is traced
    try:
        out = correctness.check(family, CASE.cfg, params, HF,
                                {**SPEC, "decode_steps": 30}, 4, PAGE,
                                reference)
        assert traced_routes()["band_decode"] == {
            "pallas": "pallas:paged_band_decode", "xla": "xla"}[route]
    finally:
        jax.clear_caches()
    assert out["ok"] and out["grounds"] == [], out


@pytest.mark.parametrize("n", [1, 8, 9, 16, 17, 24, 25, 100, 1000])
def test_a_window_layers_decode_reads_at_most_a_window_and_a_page(n):
    """The bound on what a window layer's decode reads, from the work-list
    its call is handed: at most W + PAGE cells a row at any context, and
    ceil(len / PAGE) pages while len <= W."""
    lens = jnp.asarray([n], jnp.int32)
    pages = int(family.band_pages_read(CASE.cfg, lens)[0])
    work = decode_work_list(jnp.arange(R, dtype=jnp.int32)[None], lens,
                            page_size=PAGE, kv_from=jnp.maximum(lens - W, 0))
    assert int(work.count) == pages and pages * PAGE <= W + PAGE
    assert pages <= R
    if n <= W:
        assert pages == -(-n // PAGE)


def test_another_family_refuses_its_keys_and_the_band_keeps_its_own_page():
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
        family.init_kv_pages(CASE.cfg, 4, PAGE, quantized=True)
    pool = family.init_kv_pages(CASE.cfg, 4, 4, num_slots=3)
    assert isinstance(pool[0], StatePool)
    assert pool[0].pages.shape == pool[1].pages.shape == (N_G, 4, 4, 2, 16)
    # the band's page is the configuration's, whatever the pool's
    assert pool[0].state.shape == pool[1].state.shape == (
        N_W, (3 + 1) * R, PAGE, 2, 16)
