"""models/kimi_linear.py on the CPU at a small size, float32, seeded weights
(docs/kimi-linear.md). The family's record for the suite
(tests/engine/family_suite.py): prefill -> an extend from a page boundary and
one from inside a page -> decode steps through the latent pages and the
state against the plain reference's one whole-sequence pass
(benchmark/reference/kimi_linear.py, its state stepped token by token), at
lengths that are no multiple of the rule's chunk too; each one-term control
of benchmark/check_kda.py failing by over 1e-3 (the decay collapsed to its
mean over a head's channels above all); the shares of the chips adding up to
the uncut layer; the life of the state a slot; what the family does not
compute refused by name; the family through the continuous-batching engine.
Its own: the NoPE latent block against plain attention over [k_nope | k_pe],
and the catalog's row read key for key with the shapes and bytes ISSUE 62
counted."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_kda, check_limits, correctness
from benchmark.reference import dense, kimi_linear as reference
from llmlb_tpu.models import FAMILIES, config_from_hf, deepseek_v3, family_for
from llmlb_tpu.models import kimi_linear as family
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Shares,
    State,
    test_a_decode_step_advances_the_live_rows_alone,
    test_a_padded_bucket_leaves_the_state_of_the_true_prompt,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_a_repeated_row_and_a_used_slot_write_the_state_of_their_prompt,
    test_an_engine_that_would_serve_the_family_wrong_does_not_start,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_the_engines_tokens_are_the_references_greedy_tokens,
    test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot,
    test_the_preset_is_the_published_config_read,
    test_the_shares_add_up_to_the_uncut_layer,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

HF = {
    "model_type": "kimi_linear", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 7, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "mla_use_nope": True, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "hidden_act": "silu",
    "linear_attn_config": {
        "full_attn_layers": [4, 7], "kda_layers": [1, 2, 3, 5, 6],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "num_experts": 4,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "model_max_length": 512, "num_nextn_predict_layers": 0,
    "expert_parallel": {"chips": 2, "chip": 1, "experts": 8},
}
PAGE = 16
N_K, N_A = 5, 2
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"
# a prefill of two whole chunks of the rule (16) and two pages, an extend
# from the page boundary (32) and one from inside a page (44), each reading
# its slot's state and carried rows, then decode
SPEC = {"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
        "decode_steps": 5, "tolerance": 5e-5, "router_tolerance": 1e-5,
        "flip_margin_multiple": 8.0}


def _reads(cfg):
    groups = [(g.prefix, g.count, g.pool_layer, g.scope, bool(g.whole))
              for g in family._groups(cfg)]
    return [
        ((cfg.layers_of(family.KDA), cfg.layers_of(family.MLA),
          cfg.num_moe_layers), (N_K, N_A, 6)),
        ((cfg.held_experts, cfg.router_experts, cfg.experts_per_token),
         ((4, 4), 8, 2)),
        ((cfg.mla_nope, cfg.kda_rank, cfg.conv_dim), (True, 16, 192)),
        # K | KK A | KK A: a run a group, its own stacks, its pool's rows
        (groups, [("r0_", 1, 0, "kda_layers", False),
                  ("r1_", 2, 1, "kda_layers", True),
                  ("r2_", 1, 0, "latent_layers", True),
                  ("r3_", 2, 3, "kda_layers", True),
                  ("r4_", 1, 1, "latent_layers", True)])]


def _variant(name, **kw):
    return lambda params: CASE.control(
        params, check_kda.variants(family)[name], **kw)


def _zeroed_chosen_expert(params):
    """check_kda's: the HELD expert of the first mixture layer that the
    compared positions chose most, zeroed in the program's place; the
    reference passes over the true weights."""
    heard = []

    def hearing(params_, hf, ids, **kw):
        heard.append(np.asarray(kw["follow"]))
        return reference.forward(params_, hf, ids, **kw)

    correctness.check(family, CASE.cfg, params, HF, SPEC, 3, PAGE,
                      check_limits.like(reference, hearing))
    at = heard[0][0, check_limits.compared_positions(SPEC)]
    first, held = CASE.cfg.held_experts
    mine = at[(at >= first) & (at < first + held)] - first
    expert = int(np.bincount(mine.ravel()).argmax())
    leaf = check_kda.first_mixture_down(params)
    assert leaf == "r1_we_down"
    return CASE.control(params, given={
        **params, leaf: params[leaf].at[0, expert].set(0.0)})


def _shares():
    """Sixteen chips holding 1 of 16 experts each (the published sixteen
    shares at a small size): a chip's part is its routed expert's alone (its
    layer less the shared expert, which every chip computes alike), held
    against the reference's mixture of that share; the uncut reference's
    layer is x + the parts and the shared expert ONCE."""
    whole = {**HF, "num_experts": 16, "num_experts_per_token": 4,
             "expert_parallel": None}
    cfg = config_from_hf(whole, jnp.float32)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    run, row = "r1_", 1  # the second mixture layer
    m = dense.rms_norm(x, params[run + "ln_mlp"][row], 1e-5)
    lp = {n: params[run + n][row] for n in (
        "router", "router_bias", "ws_gu", "ws_down")}
    shared = dense.swiglu(m, *jnp.split(lp["ws_gu"], 2, axis=-1),
                          lp["ws_down"])
    experts = ("we_gate", "we_up", "we_down")
    names = ("ln_mlp", "router", "router_bias", *experts, "ws_gu", "ws_down")
    rule = dict(top_k=4, scale=2.446, normalize=True, eps=1e-5)
    parts = []
    for chip in range(16):
        share_cfg = config_from_hf(
            {**whole, "num_experts": 1, "expert_parallel": {
                "chips": 16, "chip": chip, "experts": 16}}, jnp.float32)
        held = {n: params[run + n][:, chip:chip + 1] for n in experts}
        got, routing = family._moe_mlp_fn(share_cfg)(
            {**lp, **held, "layer": row}, m[None], None)
        assert int(routing.elsewhere) + int(routing.load.sum()) == 9 * 4
        want, _ = reference.mixture(
            x, row, *({**{n: params[run + n] for n in names}, **held}[n]
                      for n in names), first=chip, **rule)
        parts.append((got[0] - shared, want - x - shared))
    full, _ = reference.mixture(x, row, *(params[run + n] for n in names),
                                first=0, **rule)
    return Shares(full, parts, lambda total: x + total + shared)


def _records(case, core, recs):
    suite.state_records(case, core, recs)
    decodes = [r for r in recs if r["kind"] == "decode"]
    for r in decodes:  # 6 mixtures x 2 a token, split between the chips
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == 6 * 2 * r["tokens"])
        assert r["experts_touched"] <= r["expert_assignments"]
    assert sum(r["expert_assignments"] for r in decodes) > 0
    assert sum(r["assignments_elsewhere"] for r in decodes) > 0
    m = core.metrics.summary()
    assert m["moe_assignments_elsewhere_total"] > 0
    assert m["ssm_state_rows_total"] > 0


def _pool_holds(cfg, ck, cv):
    return [
        # the latents and the shared key's cell of the two latent layers
        (ck.pages.shape, (N_A, 5, PAGE, 32)),
        (cv.pages.shape, (N_A, 5, PAGE, deepseek_v3.ROPE_CELL)),
        # K x (H V) float32 a slot; the slots second to last in the rows
        (ck.state.shape, (N_K, 3, 16, 4 * 16)),
        (cv.state.shape, (N_K, 3, 3, 192)),
        (family.kv_pool_layers(cfg), N_A),
        (family.kv_token_layer_bytes(cfg), (32 + 128) * 4),
        (family.state_slot_bytes(cfg), N_K * (4 * 16 * 16 * 4 + 3 * 192 * 4)),
        (set(family.step_counters(cfg)), {
            "state_rows", "global_kv_tokens", "experts_touched",
            "expert_assignments", "expert_load_max", "expert_load_hist",
            "assignments_elsewhere"})]


_ENGINE = suite.state_engine("int8 latent page pool beside a delta-rule "
                             "state")
CASE = Case(
    family=family, preset="debug-kimi-linear-tiny", hf=HF,
    reference=reference, page=PAGE, spec=SPEC, reads=_reads,
    preset_departs={"chunk_size": 16}, tolerance=5e-5,
    # and a prefill of 37 (two chunks of 16 and 5), extends of 7 from inside
    # a chunk and inside a page; lengths under the convolution's taps
    runs=(("seed3", {}, 3), ("seed4", {}, 4),
          ("no_multiple_of_the_chunk",
           {"prefill_tokens": 37, "extend_tokens": 7}, 9),
          ("length_2", {"prefill_tokens": 2, "extend_tokens": 2}, 6)),
    controls={
        **{name: _variant(name) for name in (
            "decay_channel_mean", "beta_doubled", "keys_rotated",
            "conv_not_carried", "live_mask_off")},
        "unbiased_choice": _variant("unbiased_choice",
                                    ground="flips_at_wide_margin"),
        "zeroed_chosen_expert": _zeroed_chosen_expert},
    refused=(
        ({"mla_use_nope": False}, "mla_use_nope"),
        ({"num_expert_group": 4}, "num_expert_group"),
        ({"topk_group": 2}, "topk_group"),
        ({"moe_router_activation_func": "softmax"},
         "moe_router_activation_func"),
        ({"rope_scaling": {"type": "yarn", "factor": 2.0}}, "rope_scaling"),
        ({"linear_attn_config": {**HF["linear_attn_config"],
                                 "kda_layers": [1, 2, 3, 5]}},
         "linear_attn_config"),
        ({"linear_attn_config": {**HF["linear_attn_config"],
                                 "use_full_rank_gate": True}},
         "linear_attn_config"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings")),
    shares=_shares,
    # rounding, amplified layer by layer (test_linear_family's docstring)
    state=State(slot_axis=(1, 2), atol=2e-4, pool=_pool_holds,
                counters=lambda cfg, rows, cells: {
                    "state_rows": rows, "global_kv_tokens": N_A * cells}),
    engine=_ENGINE._replace(records=_records))


# --- the state a slot ----------------------------------------------------------

def test_a_decode_step_that_is_not_live_before_each_extend_changes_nothing(
        params):
    """check_kda's `interleaved_decode`: what a burst beside a chunked
    prefill does to the prefilling slot, with the mask."""
    plain = correctness.check(family, CASE.cfg, params, HF, SPEC, 3, PAGE,
                              reference)
    stepped = correctness.check(
        check_kda.variants(family)["interleaved_decode"], CASE.cfg, params,
        HF, SPEC, 3, PAGE, reference)
    assert stepped["ok"]
    assert stepped["max_rel_rms_err"] == plain["max_rel_rms_err"]


def test_the_decay_differs_from_channel_to_channel_of_a_head(params):
    """What tells this rule from a decay a head: at the seeded weights a
    head's 16 key channels decay at rates that differ by a tenth and more."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64), jnp.float32)
    lp = {n: params["r1_" + n][0] for n in (
        "w_low", "wf_b", "wg_b", "dt_bias", "a_log")}
    g, beta, gate = family._low_rank(CASE.cfg, lp, x)
    assert g.shape == (1, 24, 4, 16) and beta.shape == (1, 24, 4)
    assert gate.shape == (1, 24, 64)
    a = np.exp(np.asarray(g))
    assert (a > 0).all() and (a <= 1).all()
    assert np.median(a.max(-1) - a.min(-1)) > 0.1
    assert (np.asarray(beta) > 0).all() and (np.asarray(beta) < 1).all()


# --- the latent block without rotary -----------------------------------------

def test_the_nope_latent_block_is_plain_attention_over_nope_and_pe_keys():
    """deepseek_v3's block under `mla_nope`: softmax((q . [k_nope | k_pe]) /
    sqrt(Dn + Dr)) v with nothing rotated, position by position; and the
    same block with the flag off (what deepseek_v3 and longcat_flash run)
    differs from it, and is the program it was (the flag's default)."""
    cfg = CASE.cfg
    params = family.init_params(cfg, jax.random.PRNGKey(5))
    lp = {n: params["r2_" + n][0] for n in family._MLA}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 11, 64), jnp.float32)
    attention = family._attention(cfg)
    lens = jnp.asarray([11], jnp.int32)
    positions = jnp.arange(11, dtype=jnp.int32)[None]
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, 8, 2) / 8))

    def block(c):
        return deepseek_v3._mla_block(
            c, lp, x, positions, inv_freq,
            lambda q, k, v: attention.prefill(q, k, v, lens))

    got, c, k_pe = block(cfg)
    want = reference.latent_mixer(
        x[0], 0, *(params["r2_" + n] for n in family._MLA), heads=4, rank=32,
        nope=16, eps=1e-5)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-5)
    # what the pool keeps: the normed latent and the UNROTATED shared key
    h = dense.rms_norm(x[0], lp["ln_attn"], 1e-5)
    kv = h @ lp["wkv_a"]
    np.testing.assert_allclose(np.asarray(k_pe[0, :, :8]),
                               np.asarray(kv[:, 32:]), atol=1e-6)
    assert (np.asarray(k_pe[..., 8:]) == 0).all()
    assert c.shape == (1, 11, 32)
    rotated, _, _ = block(dataclasses.replace(cfg, mla_nope=False))
    assert np.abs(np.asarray(rotated - got)).max() > 1e-3
    assert deepseek_v3.DeepseekV3Config.mla_nope is False
    assert family.KimiLinearConfig.mla_nope is True


# --- the catalog's row -------------------------------------------------------

@pytest.fixture(scope="module")
def row():
    with open(ROW) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "Kimi-Linear-48B-A3B-Instruct":
                return entry["config"]
    pytest.skip("the catalog has no Kimi-Linear-48B-A3B-Instruct row here")


def test_the_catalog_row_gives_the_shapes_and_bytes_the_issue_counted(row):
    whole = config_from_hf(row)
    assert family_for(whole) is family and whole.dtype == jnp.bfloat16
    assert (whole.num_experts, whole.router_experts, whole.first_expert) == (
        256, 256, 0)
    with open("benchmark/configs/kimi-linear-48b-a3b.json") as f:
        file = json.load(f)
    assert {k: file[k] for k in row if k != "num_experts"} == {
        k: v for k, v in row.items() if k != "num_experts"}
    assert list(file["reduced"]) == ["num_experts"]
    cfg = config_from_hf(file)
    assert dataclasses.replace(whole, num_experts=16) == cfg
    assert cfg.held_experts == (0, 16) and cfg.experts_per_token == 8
    assert (cfg.num_layers, cfg.vocab_size, cfg.hidden_size) == (
        27, 163840, 2304)
    attends = [at for at, m in enumerate(cfg.mixers) if m == family.MLA]
    assert attends == [3, 7, 11, 15, 19, 23, 26]
    assert (cfg.layers_of(family.KDA), cfg.num_moe_layers,
            cfg.first_k_dense) == (20, 26, 1)
    assert [(kind, n) for _, kind, n in family.runs(cfg)] == (
        [("kda_dense", 1), ("kda_moe", 2), ("mla_moe", 1)]
        + [("kda_moe", 3), ("mla_moe", 1)] * 5
        + [("kda_moe", 2), ("mla_moe", 1)])
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel, cfg.conv_dim,
            cfg.kda_rank) == (32, 128, 4, 12288, 128)
    assert cfg.mla_nope and cfg.routed_scaling_factor == 2.446
    assert cfg.rms_eps == 1e-5 and cfg.norm_topk_prob
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert shapes["r3_wqkv"].shape == (3, 2304, 12288)
    # stored side by side, drawn apart: [W_fa | W_ga | W_b], gate | up
    assert shapes["r3_w_low"].shape == (3, 2304, 128 + 128 + 32)
    assert shapes["r3_ws_gu"].shape == (3, 2304, 2 * 1024)
    assert shapes["r3_we_gate"].shape == (3, 16, 2304, 1024)
    assert shapes["r2_router"].shape == (1, 2304, 256)
    assert shapes["r0_wg"].shape == (1, 2304, 9216)
    assert shapes["r1_router_bias"].dtype == jnp.float32
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    kda = (2304 * 12288 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 4096 * 2304 + 12288 * 4 + 4096 + 32 + 128 + 2304)
    latent = (2304 * 6144 + 2304 * 576 + 2 * 32 * 512 * 128 + 4096 * 2304
              + 512 + 2304)
    mixture = (16 + 1) * 3 * 2304 * 1024 + 2304 * 256 + 256 + 2304
    dense_ffn = 3 * 2304 * 9216 + 2304
    assert n == (20 * kda + 7 * latent + 26 * mixture + dense_ffn
                 + 2 * 163840 * 2304 + 2304)
    assert round(kda / 1e6, 2) == 39.52 and round(latent / 1e6, 2) == 29.12
    assert 9.81e9 < 2 * n < 10.01e9  # the issue's 9.91 GB +- 0.1
    assert family.state_slot_bytes(cfg) == 20 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert family.kv_token_layer_bytes(cfg) == (512 + 128) * 2 == 1280
    ck, cv = jax.eval_shape(lambda: family.init_kv_pages(cfg, 544, 128,
                                                         num_slots=32))
    assert ck.pages.shape == (7, 544, 128, 512)
    assert cv.pages.shape == (7, 544, 128, 128)
    assert ck.state.shape == (20, 32, 128, 4096)  # whole lanes
    assert ck.state.dtype == jnp.float32
    assert cv.state.shape == (20, 3, 32, 12288)


def test_every_other_class_refuses_the_catalog_row(row):
    """The row read as another family's `model_type` is refused by the keys
    it states, not served as that model; and read as a type nobody names —
    which carries `num_experts` and falls through to Mixtral's class — it is
    refused by name at once, not built as a dense or a Mixtral model."""
    for module in FAMILIES:
        if module is family:
            continue
        with pytest.raises((ValueError, NotImplementedError, KeyError)):
            config_from_hf({**row,
                            "model_type": module.FAMILY.model_types[0]})
    for hidden in ("linear_attn_config", "mla_use_nope"):
        with pytest.raises(ValueError, match=hidden):
            config_from_hf({k: v for k, v in {
                **row, "model_type": "kimi_next", "kv_lora_rank": None,
                "use_grouped_topk": False, "first_k_dense_replace": 0,
                "num_shared_experts": 0, "moe_renormalize": False,
                "moe_router_activation_func": None,
                "num_experts_per_token": 1,
                "moe_intermediate_size": row["intermediate_size"]}.items()
                if k not in {"linear_attn_config", "mla_use_nope"} - {hidden}})


def test_an_int8_pool_weights_and_adapters_are_refused_by_the_record():
    with pytest.raises(NotImplementedError, match="int8 latent page pool "
                       "beside a delta-rule state"):
        family.init_kv_pages(CASE.cfg, 4, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 weights"):
        family.FAMILY.refuse(int8_weights=True)
    with pytest.raises(NotImplementedError, match="adapter pools"):
        family.FAMILY.refuse(lora=True)
    assert not family.FAMILY.mixed_step and not family.FAMILY.verifies_drafts
