"""models/longcat_flash.py on the CPU at a small size, seeded weights
(docs/longcat-flash.md). The family's record for the suite
(tests/engine/family_suite.py): prefill -> two extend chunks -> decode steps
through the pages against the plain reference's one forward pass
(benchmark/reference/longcat_flash.py), by logits, routing followed; the
32-chip question at a small size — the shares' held parts, with the identity
part and everything outside the mixture counted once, add up to the uncut
layer; the shortcut: a program that adds the mixture one sub-layer early is
told apart; the configuration read from its published keys and what it does
not compute refused by name. Its own: a burst of decode steps under a scan;
the zero-compute assignments in ops/moe.py; the catalog row itself where
the catalog is installed."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_shortcut, correctness
from benchmark.reference import longcat_flash as reference
from llmlb_tpu.models import config_from_hf, deepseek_v3, family_for
from llmlb_tpu.models import longcat_flash as family
from llmlb_tpu.ops import moe
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Shares,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_the_preset_is_the_published_config_read,
    test_the_shares_add_up_to_the_uncut_layer,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

HF = {
    "model_type": "longcat_flash", "attention_bias": False, "vocab_size": 512,
    "hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 4, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "attention_method": "MLA",
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "expert_parallel": {"chips": 2, "chip": 1, "experts": 8},
}
PAGE = 16


def _reads(cfg):
    ck, cv = family.init_kv_pages(cfg, 3, PAGE)
    return [
        (isinstance(cfg, deepseek_v3.DeepseekV3Config), True),  # asked first
        ((cfg.held_experts, cfg.router_experts), ((4, 4), 8)),
        ((cfg.router_width, cfg.zero_experts), (12, 4)),
        ((cfg.q_lora_scale, cfg.kv_lora_scale), (2.0, 2.0 ** 0.5)),
        (family.kv_pool_layers(cfg), 4),  # two attention sub-layers a layer
        ((ck.shape, cv.shape), ((4, 3, PAGE, 32), (4, 3, PAGE, 128)))]


def _shares():
    """One layer with all 8 experts, and its cut into the shares of chip 0
    and chip 1 (the 32-chip deployment at a small size). Each chip's layer
    is y_rest + its held experts' part + the identity part: a chip's part
    is what it HOLDS, and the identity part and everything outside the
    mixture are counted once."""
    whole_hf = {**HF, "n_routed_experts": 8, "expert_parallel": None}
    whole = config_from_hf(whole_hf, jnp.float32)
    assert whole.held_experts == (0, 8)
    p = family.init_params(whole, jax.random.PRNGKey(11))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(19, 64)),
                    jnp.float32)
    d, layer = reference.dims(whole_hf), 1
    uncut, scores = reference.double_layer(p, layer, x, d,
                                           reference.rule(whole_hf))
    a = reference.attention_sublayer(
        x, layer, *(p["s0_" + n] for n in reference._ATTN), **d)
    _, h = reference.feed_forward(
        a, layer, *(p["s0_" + n] for n in reference._MLP), eps=d["eps"])
    experts = ("s0_we_gate", "s0_we_up", "s0_we_down")
    parts, counted, rest = [], [], None
    for chip in (0, 1):
        hf = {**HF, "expert_parallel": {"chips": 2, "chip": chip,
                                        "experts": 8}}
        cfg = config_from_hf(hf, jnp.float32)
        share = {**p, **{n: p[n][:, 4 * chip:4 * chip + 4] for n in experts}}
        y, chip_scores = reference.double_layer(share, layer, x, d,
                                                reference.rule(hf))
        np.testing.assert_array_equal(chip_scores, scores)  # one router
        held, identity, _ = reference.mixture_parts(
            h, layer, p["s0_router"][layer], p["s0_router_bias"][layer],
            *(share[n] for n in experts), None, **reference.rule(hf))
        rest = y - held if rest is None else rest  # what both compute alike
        # the program's mixture of this share
        lp = {n[3:]: share[n][layer] for n in ("s0_router", "s0_router_bias")}
        lp.update({n[3:]: share[n] for n in experts}, layer=layer)
        got, routing = family._mixture_fn(cfg)(lp, h[None], None)
        parts.append((got[0] - identity, held))
        counted.append((int(routing.zero), int(jnp.sum(routing.load)),
                        int(routing.elsewhere)))
    # a chip's elsewhere is the other's held; the zero part is everyone's
    (z0, h0, e0), (z1, h1, e1) = counted
    assert z0 == z1 > 0 and (h0, h1) == (e1, e0)
    assert z0 + h0 + e0 == 19 * 3
    return Shares(uncut, parts, lambda total: rest + total, atol=2e-5)


CASE = Case(
    family=family, preset="debug-longcat-tiny", hf=HF, reference=reference,
    page=PAGE, tolerance=1e-4, reads=_reads,
    spec={"prefill_tokens": 24, "extend_chunks": 2, "extend_tokens": 12,
          "decode_steps": 5, "tolerance": 1e-3, "router_tolerance": 1e-4,
          "flip_margin_multiple": 6.0},
    # the mixture added one sub-layer early (so that the second attention
    # and feed-forward see it), the zero-compute experts dropped, the two
    # LoRA scales left out: each is told apart by the logits
    controls={name: (lambda params, name=name: CASE.control(
        params, check_shortcut.variants(family, CASE.cfg)[name]))
        for name in ("shortcut_early", "zero_dropped", "scales_off")},
    control_fails_by=0.05,
    control_spec={"extend_chunks": 0},
    refused=tuple(({key: value}, key) for key, value in (
        ("attention_method", "GQA"), ("zero_expert_type", "copy"),
        ("rope_scaling", {"type": "yarn", "factor": 4}),
        ("attention_bias", True), ("router_bias", True),
        ("hidden_act", "gelu"), ("tie_word_embeddings", True),
        ("q_lora_rank", None), ("n_shared_experts", 1))),
    shares=_shares)


# LongCat-Flash-Omni's language model as the catalog
# (/opt/skills/guides/model-configs/architectures.jsonl) has it, carried here
# so that the test holds where the catalog is not installed.
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}


def test_from_hf_config_reads_the_catalog_row_itself():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = PUBLISHED
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Omni")["config"]
        assert row == PUBLISHED
    cfg = config_from_hf({**row, "model_type": "longcat_flash"})
    assert family_for(cfg) is family
    assert (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.experts_per_token, cfg.vocab_size) == (
        28, 6144, 12288, 2048, 64, 1536, 512, 128, 64, 128, 12, 131072)
    assert (cfg.num_experts, cfg.router_experts, cfg.zero_experts,
            cfg.router_width, cfg.held_experts) == (512, 512, 256, 768,
                                                    (0, 512))
    assert cfg.q_lora_scale == 2.0
    assert cfg.kv_lora_scale == pytest.approx(12 ** 0.5)
    assert (cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.rms_eps,
            cfg.rope_theta, cfg.rope_interleave) == (6.0, False, 1e-5, 1e7,
                                                     True)
    assert family.kv_pool_layers(cfg) == 56


def test_a_share_that_does_not_divide_the_experts_is_refused():
    with pytest.raises(ValueError, match="expert_parallel"):
        config_from_hf({**HF, "expert_parallel": {
            "chips": 3, "chip": 0, "experts": 8}})


def test_another_family_refuses_the_mechanisms_by_name():
    for key in ("zero_expert_num", "q_lora_rank"):
        with pytest.raises(ValueError, match=key):
            config_from_hf({"model_type": "llama", "vocab_size": 512,
                            "hidden_size": 64, "intermediate_size": 96,
                            "num_hidden_layers": 2, "num_attention_heads": 4,
                            key: HF[key]})
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        deepseek_v3.DeepseekV3Config.from_hf_config({"q_lora_rank": 16})


def test_what_the_engine_must_refuse_is_said_by_the_family():
    record = family.FAMILY
    assert not (record.int8_weights or record.int8_kv or record.lora)
    assert family.kv_wire_cell(CASE.cfg) is None
    with pytest.raises(NotImplementedError, match="int8 latent page pool"):
        family.init_kv_pages(CASE.cfg, 3, PAGE, quantized=True)


def test_a_choice_made_without_the_bias_is_refused(params):
    out = correctness.check(
        check_shortcut.variants(family, CASE.cfg)["unbiased_choice"], CASE.cfg,
        params, HF, CASE.spec, 3, PAGE, reference)
    assert "choice_is_own_topk" in out["grounds"]
    assert out["max_rel_rms_err"] < 1e-4  # the reference follows the choice


def test_a_burst_of_decode_steps_is_the_references_greedy_continuation(params):
    """Prefill two rows of unlike lengths, then 6 decode steps under ONE
    scan with the sampled token fed back on the device, as the engine's
    burst runs them: each row's tokens are the argmax of the reference's
    forward over the prompt and the tokens so far."""
    rng = np.random.default_rng(5)
    lens = np.asarray([20, 13], np.int32)
    ids = rng.integers(8, CASE.cfg.vocab_size, (2, 24)).astype(np.int32)
    ck, cv = family.init_kv_pages(CASE.cfg, 9, PAGE)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    logits, ck, cv, _ = family.prefill_into_pages(
        params, CASE.cfg, jnp.asarray(ids), jnp.asarray(lens), tables, ck, cv)
    first = jnp.argmax(logits, -1).astype(jnp.int32)

    @jax.jit
    def burst(last, seq, ck, cv):
        def body(carry, _):
            last, seq, ck, cv = carry
            logits, ck, cv, counters = family.decode_step_paged(
                params, CASE.cfg, last, seq, ck, cv, tables, window=64)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, seq + 1, ck, cv), (nxt, counters)

        return jax.lax.scan(body, (last, seq, ck, cv), None, length=6)[1]

    toks, counters = burst(first, jnp.asarray(lens), ck, cv)
    toks = np.concatenate([np.asarray(first)[None], np.asarray(toks)])  # [7, 2]
    for row in range(2):
        seq = np.concatenate([ids[row, :lens[row]], toks[:-1, row]])
        want, _ = reference.forward(params, HF, seq)
        got = np.argmax(np.asarray(want)[lens[row] - 1:], -1)
        assert got.tolist() == toks[:, row].tolist()
    # every assignment of every step is accounted for: 2 rows x 3 x 2 layers
    total = (counters["zero_assignments"] + counters["expert_assignments"]
             + counters["assignments_elsewhere"])
    assert np.asarray(total).tolist() == [2 * 3 * 2] * 6


def test_a_row_that_is_not_live_is_routed_nowhere(params):
    ck, cv = family.init_kv_pages(CASE.cfg, 9, PAGE)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    args = (params, CASE.cfg, jnp.asarray([9, 11], jnp.int32),
            jnp.asarray([3, 5], jnp.int32), ck, cv, tables)
    *_, counters = family.decode_step_paged(
        *args, window=64, live=jnp.asarray([True, False]))
    assert int(counters["zero_assignments"] + counters["expert_assignments"]
               + counters["assignments_elsewhere"]) == 1 * 3 * 2


# --- ops/moe.py: an expert that is no product --------------------------------

def _routed(x, logits, w, *, real, held=None, valid=None, k=3):
    return moe.moe_routed(
        x, logits, *w, held=held, real=real, token_valid=valid,
        route=lambda r: moe.softmax_bias_routing(
            r, jnp.zeros((r.shape[-1],)), k, scale=6.0))


def test_a_zero_compute_assignment_is_weight_times_the_token():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(7, 16)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
         for s in ((4, 16, 8), (4, 16, 8), (4, 8, 16))]
    # a router that chooses the zero-compute outputs 4, 5, 6 for every token
    logits = jnp.tile(jnp.asarray([0., 0, 0, 0, 5, 4, 3, -9]), (7, 1))
    out, routing = _routed(x, logits, w, real=4)
    weights = 6.0 * jax.nn.softmax(logits, -1)[:, 4:7].sum(-1)
    np.testing.assert_allclose(out, weights[:, None] * x, rtol=1e-6)
    # none counted elsewhere, none in a group
    assert int(routing.zero) == 21 and routing.elsewhere is None
    assert np.asarray(routing.load).tolist() == [0, 0, 0, 0]
    # with a share held the same: a zero-compute expert belongs to no chip
    out2, routing = _routed(x, logits, [v[2:] for v in w], real=4,
                            held=(2, 2))
    np.testing.assert_allclose(out2, out, rtol=1e-6)
    assert (int(routing.zero), int(routing.elsewhere),
            np.asarray(routing.load).tolist()) == (21, 0, [0, 0])


def test_zero_held_and_elsewhere_account_for_every_valid_assignment():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    w = [jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
         for s in ((6, 16, 8), (6, 16, 8), (6, 8, 16))]
    logits = jnp.asarray(rng.normal(size=(11, 9)), jnp.float32)  # 6 + 3 zero
    valid = jnp.asarray([True] * 8 + [False] * 3)
    whole, r = _routed(x, logits, w, real=6, valid=valid)
    assert int(r.zero) + int(jnp.sum(r.load)) == 8 * 3
    assert np.asarray(whole[8:]).tolist() == np.zeros((3, 16)).tolist()
    parts = []
    for first in (0, 3):
        part, rs = _routed(x, logits, [v[first:first + 3] for v in w], real=6,
                           held=(first, 3), valid=valid)
        assert (int(rs.zero) + int(jnp.sum(rs.load)) + int(rs.elsewhere)
                == 8 * 3)
        assert int(rs.zero) == int(r.zero)
        parts.append(part)
    identity = parts[0] + parts[1] - whole  # both computed it: once too many
    weights, chosen, _ = moe.softmax_bias_routing(logits, jnp.zeros((9,)), 3,
                                                  scale=6.0)
    want = jnp.sum(jnp.where(chosen >= 6, weights, 0.0), -1)[:, None] * x
    np.testing.assert_allclose(identity, jnp.where(valid[:, None], want, 0.0),
                               atol=1e-5)


def test_softmax_bias_routing_by_its_rule():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 0.5]])
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0, 0.0])  # lifts output 2 into the 2
    p = jax.nn.softmax(logits, -1)
    weights, chosen, scores = moe.softmax_bias_routing(logits, bias, 2,
                                                       scale=6.0)
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 2]
    np.testing.assert_allclose(scores, p + bias, rtol=1e-6)
    # the UNBIASED scores, not renormalised, x 6
    np.testing.assert_allclose(
        sorted(np.asarray(weights)[0].tolist()),
        sorted((6.0 * p[0, [0, 2]]).tolist()), rtol=1e-6)
    normed, _, _ = moe.softmax_bias_routing(logits, bias, 2, normalize=True)
    assert float(jnp.sum(normed)) == pytest.approx(1.0)


def test_the_router_bias_is_scaled_to_the_scores():
    big = config_from_hf({**PUBLISHED, "model_type": "longcat_flash"})
    assert family.router_bias_sd(big) == pytest.approx(0.1 / 768)
    p = family.init_params(CASE.cfg, jax.random.PRNGKey(0))
    assert p["s0_router_bias"].dtype == jnp.float32
    assert p["s0_router_bias"].shape == (2, 12)
    sd = float(jnp.std(p["s0_router_bias"]))
    assert 0.3 * 0.1 / 12 < sd < 3 * 0.1 / 12


def test_the_step_counters_have_the_shapes_the_family_states(params):
    ck, cv = family.init_kv_pages(CASE.cfg, 5, PAGE)
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    *_, counters = family.prefill_into_pages(
        params, CASE.cfg, jnp.asarray(np.arange(8, 24)[None], jnp.int32),
        jnp.asarray([16]), tables, ck, cv)
    shapes = family.step_counters(CASE.cfg)
    assert {k: v.shape for k, v in counters.items()} == shapes
    assert set(shapes) >= {"zero_assignments", "assignments_elsewhere",
                           "expert_assignments", "experts_touched"}
    assert shapes["expert_load_hist"] == (2, len(family.LOAD_BUCKETS) + 1)
    # the histogram is over the 4 HELD experts of each layer
    assert np.asarray(counters["expert_load_hist"]).sum(-1).tolist() == [4, 4]
    assert dataclasses.replace(CASE.cfg, num_layers=3).num_moe_layers == 3
