"""models/lfm2_moe.py on the CPU at a small size, float32, seeded weights
(docs/lfm2-moe.md). The family's record for the suite
(tests/engine/family_suite.py): prefill -> an extend from a page boundary and
one from inside a page -> decode steps through the pool and the carried rows
against the plain reference's one whole-sequence pass
(benchmark/reference/lfm2_moe.py) under 1e-5, at lengths of 1, 2 and 3 and
one past a bucket so that the two carried rows cross every boundary; each
one-term control of benchmark/check_conv_moe.py failing by over 1e-3; what
the family does not compute refused by name; the family through the
continuous-batching engine with park and resume. Its own: the life of the
two carried rows a slot, and the catalog's row read key for key with the
shapes and bytes ISSUE 59 counted."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_conv_moe, check_limits, correctness
from benchmark.reference import lfm2_moe as reference
from llmlb_tpu.models import FAMILIES, config_from_hf, family_for
from llmlb_tpu.models import lfm2_moe as family
from llmlb_tpu.models.llama import StatePool
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Engine,
    State,
    test_a_padded_bucket_leaves_the_state_of_the_true_prompt,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_an_engine_that_would_serve_the_family_wrong_does_not_start,
    test_park_and_resume_is_token_identical,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_rows_of_40_and_400_tokens_decode_in_one_step,
    test_rows_of_40_and_400_tokens_share_the_engines_steps,
    test_the_engines_tokens_are_the_references_greedy_tokens,
    test_the_preset_is_the_published_config_read,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

CONV, ATTENTION = family.CONV, family.ATTENTION
HF = {
    "model_type": "lfm2_moe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_hidden_layers": 8, "layer_types": [CONV, CONV, ATTENTION, CONV] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "max_position_embeddings": 512,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
}
PAGE = 16
N_C, N_A = 6, 2
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"
# a prefill of two whole pages, an extend from the page boundary (32) and
# one from inside a page (44), each reading its slot's carried rows, then
# decode
SPEC = {"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
        "decode_steps": 5, "tolerance": 1e-5, "router_tolerance": 1e-5,
        "flip_margin_multiple": 8.0}


def _reads(cfg):
    groups = [(g.prefix, g.start, g.pool_layer, g.scope)
              for g in family._groups(cfg)]
    return [
        ((cfg.layers_of(CONV), cfg.layers_of(ATTENTION), cfg.num_moe_layers),
         (N_C, N_A, 6)),
        ((cfg.head_dim_, cfg.pool_pack, cfg.conv_taps), (16, 2, 3)),
        (groups[:6], [("c_", 0, 0, "short_conv"),
                      ("dense_", 0, None, "dense_feed_forward"),
                      ("c_", 1, 1, "short_conv"),
                      ("dense_", 1, None, "dense_feed_forward"),
                      ("a_", 0, 0, "global_attention"),
                      ("", 0, None, "expert_mixture")]),
        (groups[-2:], [("c_", 5, 5, "short_conv"),
                       ("", 5, None, "expert_mixture")])]


def _variant(name, **kw):
    return lambda params: CASE.control(
        params, check_conv_moe.variants(family)[name], **kw)


def _zeroed_chosen_expert(params):
    """check_conv_moe's: the expert of the first mixture layer that the
    compared positions chose most, zeroed in the program's place; the
    reference passes over the true weights."""
    heard = []

    def hearing(params_, hf, ids, **kw):
        heard.append(np.asarray(kw["follow"]))
        return reference.forward(params_, hf, ids, **kw)

    correctness.check(family, CASE.cfg, params, HF, SPEC, 3, PAGE,
                      check_limits.like(reference, hearing))
    at = heard[0][0, check_limits.compared_positions(SPEC)]
    expert = int(np.bincount(at.ravel()).argmax())
    return CASE.control(params, given={
        **params, "we_down": params["we_down"].at[0, expert].set(0.0)})


def _records(case, core, recs):
    decodes = [r for r in recs if r["kind"] == "decode"]
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert decodes and prefills
    for r in decodes:  # rows x steps of the burst, the live rows alone
        assert r["conv_rows"] == N_C * r["tokens"]
        assert r["global_kv_tokens"] >= r["tokens"] * N_A * 5
        assert 0 < r["experts_touched"] <= r["expert_assignments"]
        assert r["expert_assignments"] == 6 * 2 * r["tokens"]
    for r in prefills:
        assert r["conv_rows"] >= N_C and r["global_kv_tokens"] > 0
        assert r["expert_load_max"] >= 1
    assert any(r["tokens"] == 32 for r in prefills), "no chunk recorded"
    m = core.metrics.summary()
    assert m["conv_rows_total"] >= sum(r["conv_rows"] for r in recs)
    assert m["global_kv_tokens_total"] > 0
    assert m["moe_experts_touched_total"] > 0


CASE = Case(
    family=family, preset="debug-lfm2-moe-tiny", hf=HF, reference=reference,
    page=PAGE, spec=SPEC, padded=512, reads=_reads,
    # lengths at which the two carried rows are the sequence's first rows
    # (1, 2: a row of zeros is still carried; 3: exactly the taps), and a
    # prefill one past a bucket of 32 with extends that end mid-page
    runs=(("seed3", {}, 3), ("seed4", {}, 4),
          ("length_1", {"prefill_tokens": 1, "extend_tokens": 1}, 5),
          ("length_2", {"prefill_tokens": 2, "extend_tokens": 2}, 6),
          ("length_3", {"prefill_tokens": 3, "extend_tokens": 3}, 7),
          ("one_past_a_bucket",
           {"prefill_tokens": 33, "extend_tokens": 17}, 9)),
    controls={
        **{name: _variant(name) for name in (
            "no_b_gate", "silu_behind_conv", "conv_not_carried",
            "live_mask_off", "no_qk_norm", "no_rotary")},
        "unbiased_choice": _variant("unbiased_choice",
                                    ground="flips_at_wide_margin"),
        "zeroed_chosen_expert": _zeroed_chosen_expert},
    refused=(
        ({"conv_bias": True}, "conv_bias"),
        ({"layer_types": [CONV] * 7 + ["sliding_attention"]}, "layer_types"),
        ({"layer_types": [CONV] * 7 + ["mamba"]}, "layer_types"),
        ({"num_hidden_layers": 9}, "layer_types"),
        ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
         "rope_parameters"),
        ({"rope_scaling": {"rope_type": "llama3", "factor": 8.0}},
         "rope_scaling"),
        ({"use_expert_bias": False}, "use_expert_bias"),
        ({"conv_L_cache": 1}, "conv_L_cache")),
    state=State(slot_axis=(1, 1), atol=1e-6, pool=None, counters=None),
    # seven requests on four slots: 70 and 40 tokens prefill in chunks of 32
    # while other rows decode in bursts of 4 (a burst steps every slot: the
    # prefilling slot's carried rows must stay), the short ones are admitted
    # as a group, and the fifth to seventh take a slot another request's
    # rows were left in
    engine=Engine(
        args=dict(num_slots=4, slot_capacity=512, prefill_buckets=(16, 32),
                  kv_page_size=PAGE, decode_burst=4, eos_id=-1),
        requests=tuple((suite.prompt(n, 10 + n), 12)
                       for n in (17, 40, 5, 70, 33, 20, 9)),
        records=_records, long_beside_short=(10, 70),
        refused_starts=(
            (dict(prefix_cache=True), "the prefix cache"),
            (dict(spec_decode=True), "speculative decoding"),
            (dict(kv_ship=True), "kv_ship"),
            (dict(role="split"), "--role split"),
            (dict(quantize="kv"), "int8 page pool beside a convolution"),
            (dict(quantize="weights"), "does not serve int8 weights"),
            (dict(lora_dir="/nonexistent"), "no adapter pools"))))


# --- the two carried rows a slot ---------------------------------------------

def test_a_decode_step_that_is_not_live_before_each_extend_changes_nothing(
        params):
    """check_conv_moe's `interleaved_decode`: what a burst beside a chunked
    prefill does to the prefilling slot, with the mask."""
    plain = correctness.check(family, CASE.cfg, params, HF, SPEC, 3, PAGE,
                              reference)
    stepped = correctness.check(
        check_conv_moe.variants(family)["interleaved_decode"], CASE.cfg,
        params, HF, SPEC, 3, PAGE, reference)
    assert stepped["ok"]
    assert stepped["max_rel_rms_err"] == plain["max_rel_rms_err"]


def test_a_decode_step_moves_the_carried_rows_of_the_live_rows_alone(
        case, params):
    """A step with row 1 not live (a slot mid-way through a chunked prefill,
    or free): its two rows a layer stay bit for bit, row 0's roll on by one
    (the older row is what the newer was), and row 0's logits are what a
    step with every row live gives."""
    a, b = suite.ids(case, 21, 1).tolist(), suite.ids(case, 13, 2).tolist()
    _, ck, cv, _ = suite.prefill_rows(case, params, [a, b], [21, 13], [0, 1],
                                      suite.pool(case, 8, slots=2), 32)
    before = np.asarray(ck.state)
    assert before.shape == (N_C, 2, 2, 64) and np.abs(before).min(
        axis=(2, 3)).max() > 0
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    args = (jnp.asarray([5, 6], jnp.int32), jnp.asarray([21, 13], jnp.int32))

    def step(live):
        k = StatePool(ck.pages + 0, ck.state + 0)
        v = StatePool(cv.pages + 0, cv.state + 0)
        return family.decode_step_paged(
            params, case.cfg, *args, k, v, tables, None, window=32,
            live=None if live is None else jnp.asarray(live))

    logits, k, _v, counters = step([True, False])
    after = np.asarray(k.state)
    assert (after[:, 1] == before[:, 1]).all()
    assert (after[:, 0, 0] == before[:, 0, 1]).all()  # rolled on by one
    assert (after[:, 0, 1] != before[:, 0, 1]).any()
    assert int(counters["conv_rows"]) == N_C * 1
    assert int(counters["global_kv_tokens"]) == N_A * 22
    both, k2, _v2, counters = step(None)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(both[0]),
                               atol=1e-5)
    assert (np.asarray(k2.state)[:, 1, 0] == before[:, 1, 1]).all()
    assert int(counters["conv_rows"]) == N_C * 2
    assert int(counters["global_kv_tokens"]) == N_A * (22 + 14)


def test_a_repeated_row_and_a_used_slot_leave_the_rows_of_their_prompt(
        case, params):
    """A prefill group padded by repeating its last row (both write slot
    1), into a pool whose slots hold another request's rows: what slot 1
    holds afterwards is its prompt's alone (a fresh sequence voids what the
    slot held), and slot 2 is untouched."""
    a, b = suite.ids(case, 21, 1).tolist(), suite.ids(case, 13, 2).tolist()
    _, want, _, _ = suite.prefill_rows(case, params, [b], [13], [0],
                                       suite.pool(case, 8), 16)
    ck, cv = suite.pool(case, 8, slots=3)
    ck = ck._replace(state=ck.state + 3.0)  # what a finished request left
    _, ck, cv, counters = suite.prefill_rows(
        case, params, [a, b, b], [21, 13, 13], [0, 1, 1], (ck, cv), 32)
    np.testing.assert_allclose(np.asarray(ck.state)[:, 1],
                               np.asarray(want.state)[:, 0], atol=1e-5)
    assert (np.asarray(ck.state)[:, 2] == 3.0).all()
    assert int(counters["conv_rows"]) == N_C * 3
    assert int(counters["global_kv_tokens"]) == N_A * 47


def test_the_pool_holds_packed_pages_and_two_rows_a_slot_and_conv_layer(case):
    cfg = case.cfg
    ck, cv = family.init_kv_pages(cfg, 5, PAGE, num_slots=3)
    # two KV heads of 16 side by side in a row of the attention layers' pool
    assert ck.pages.shape == cv.pages.shape == (N_A, 5, PAGE, 1, 32)
    assert ck.state.shape == (N_C, 3, 2, 64) and cv.state.size == 0
    assert family.kv_pool_layers(cfg) == N_A
    assert family.kv_token_layer_bytes(cfg) == 2 * 2 * 16 * 4
    assert family.state_slot_bytes(cfg) == N_C * 2 * 64 * 4
    assert set(family.step_counters(cfg)) == {
        "conv_rows", "global_kv_tokens", "experts_touched",
        "expert_assignments", "expert_load_max", "expert_load_hist"}
    one = family.init_kv_pages(cfg, 5, PAGE)[0]
    assert one.state.shape[1] == 1  # serves a caller with one row
    assert family.kv_wire_cell(cfg) is None
    assert not hasattr(family, "verify_step_paged")
    # the rows are the activations', never float32 beside bf16 weights
    half = family.init_kv_pages(
        dataclasses.replace(cfg, dtype=jnp.bfloat16), 5, PAGE)[0]
    assert half.state.dtype == half.pages.dtype == jnp.bfloat16


# --- the catalog's row -------------------------------------------------------

@pytest.fixture(scope="module")
def row():
    with open(ROW) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "LFM2-24B-A2B":
                return entry["config"]
    pytest.skip("the catalog has no LFM2-24B-A2B row here")


def test_the_catalog_row_gives_the_shapes_and_bytes_the_issue_counted(row):
    cfg = config_from_hf(row)
    assert family_for(cfg) is family and cfg.dtype == jnp.bfloat16
    assert cfg.head_dim_ == 64 and (cfg.num_heads, cfg.num_kv_heads) == (32, 8)
    assert (cfg.layers_of(CONV), cfg.layers_of(ATTENTION)) == (30, 10)
    assert all((kind == ATTENTION) == (at % 4 == 2)
               for at, kind in enumerate(cfg.layer_types))
    assert (cfg.num_dense_layers, cfg.num_moe_layers) == (2, 38)
    assert (cfg.num_experts, cfg.experts_per_token,
            cfg.moe_intermediate_size) == (64, 4, 1536)
    assert cfg.rope_theta == 1e6 and cfg.tie_word_embeddings
    assert cfg.rms_eps == 1e-5 and cfg.conv_taps == 3
    with open("benchmark/configs/lfm2-24b-a2b-l10.json") as f:
        cut = config_from_hf(json.load(f))
    assert cut.layer_types == cfg.layer_types[:10]
    shapes = jax.eval_shape(lambda k: family.init_params(cut, k),
                            jax.random.PRNGKey(0))
    assert "lm_head" not in shapes
    assert shapes["c_conv_in"].shape == (8, 2048, 6144)
    assert shapes["we_gate"].shape == (8, 64, 2048, 1536)
    assert shapes["router_bias"].dtype == jnp.float32
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048 + 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 + 2048
    dense = 3 * 2048 * 11776 + 2048
    mixture = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64 + 2048
    assert n == (8 * conv + 2 * attention + 2 * dense + 8 * mixture
                 + 65536 * 2048 + 2048)
    assert n == 5_267_090_176  # the issue's 5,267 M: 10.53 GB in bf16
    assert family.state_slot_bytes(cut) == 8 * 2 * 2048 * 2
    assert family.kv_token_layer_bytes(cut) == 2 * 8 * 64 * 2
    ck, cv = jax.eval_shape(lambda: family.init_kv_pages(cut, 544, 128,
                                                         num_slots=32))
    # 8 KV heads of 64 as 4 rows of 128 lanes: the same bytes, none padding
    assert cut.pool_pack == 2 and ck.pages.shape == (2, 544, 128, 4, 128)
    assert ck.state.shape == (8, 32, 2, 2048) and cv.state.size == 0


def test_every_other_class_refuses_the_catalog_row(row):
    """The row read as another family's `model_type` is refused by the keys
    it states, not served as that model; and read as a type nobody names —
    which carries `num_experts` and falls through to Mixtral's class — it is
    refused by its `layer_types`, whatever its experts' width (a sibling
    whose experts are as wide as its dense layers, which
    `moe_intermediate_size` alone would let through)."""
    for module in FAMILIES:
        if module is family:
            continue
        with pytest.raises((ValueError, NotImplementedError, KeyError)):
            config_from_hf({**row,
                            "model_type": module.FAMILY.model_types[0]})
    as_wide = {**row, "model_type": "lfm3_moe",
               "moe_intermediate_size": row["intermediate_size"]}
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf(as_wide)


def test_an_int8_pool_weights_and_adapters_are_refused_by_the_record():
    with pytest.raises(NotImplementedError, match="int8 page pool beside a "
                       "convolution's carried rows"):
        family.init_kv_pages(CASE.cfg, 4, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 weights"):
        family.FAMILY.refuse(int8_weights=True)
    with pytest.raises(NotImplementedError, match="adapter pools"):
        family.FAMILY.refuse(lora=True)
