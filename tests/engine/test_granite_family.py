"""models/granite_hybrid.py on the CPU at a small size, float32, seeded
weights (docs/granite-hybrid.md). The family's record for the suite
(tests/engine/family_suite.py): prefill -> two extend chunks (one from a
scan-chunk boundary, one from inside a chunk) -> decode steps through the
pool and the state against the plain reference's one forward pass
(benchmark/reference/granite_hybrid.py, its state stepped token by token)
under 1e-5; the controls of benchmark/check_ssm_dense.py, one term wrong
each, that must FAIL by over 1e-3; the life of the state per slot; what the
family does not compute refused by name; the family through the
continuous-batching engine. Its own: the catalog's row read key for key with
the shapes and bytes ISSUE 55 counted."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_ssm_dense, correctness
from benchmark.reference import granite_hybrid as reference
from llmlb_tpu.models import FAMILIES, config_from_hf, family_for
from llmlb_tpu.models import granite_hybrid as family
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    State,
    test_a_decode_step_advances_the_live_rows_alone,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_a_repeated_row_and_a_used_slot_write_the_state_of_their_prompt,
    test_an_engine_that_would_serve_the_family_wrong_does_not_start,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_the_engines_tokens_are_the_references_greedy_tokens,
    test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot,
    test_the_preset_is_the_published_config_read,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

MAMBA, ATTENTION = family.MAMBA, family.ATTENTION
HF = {
    "model_type": "granitemoehybrid", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "hidden_act": "silu",
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "rope_theta": 10000, "rope_scaling": None,
    "layer_types": [MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA, MAMBA, ATTENTION,
                    MAMBA],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "embedding_multiplier": 12, "attention_multiplier": 0.0625,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "num_local_experts": 0, "num_experts_per_tok": 0,
}
PAGE = 16
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"


def _reads(cfg):
    # runs of like layers: 2 M, A, 3 M, A, 1 M, each a stack of its own and
    # in its pool at its kind's next rows
    groups = [(g.count, g.prefix, g.start, g.pool_layer, g.scope)
              for g in family._groups(cfg)]
    return [
        ((cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)), (6, 2)),
        ((cfg.head_dim_, cfg.d_inner, cfg.conv_dim), (16, 128, 160)),
        (groups, [(2, "r0_", 0, 0, "ssm_layers"),
                  (1, "r1_", 0, 0, "attention_layer"),
                  (3, "r2_", 0, 2, "ssm_layers"),
                  (1, "r3_", 0, 1, "attention_layer"),
                  (1, "r4_", 0, 5, "ssm_layers")])]


def _control(name):
    """benchmark/check_ssm_dense.py's: a variant of the program, or of the
    configuration it is handed."""
    return lambda params: CASE.control(
        params, check_ssm_dense.variants(family).get(name),
        check_ssm_dense.configurations(CASE.cfg).get(name), given=dict(params))


def _pool_holds(cfg, ck, cv):
    return [
        (cfg.pool_pack, 2),  # two KV heads of 16 side by side in a row
        (ck.pages.shape, (2, 5, PAGE, 1, 32)), (cv.pages.shape, ck.pages.shape),
        (ck.state.shape, (6, 3, 8, 16, 16)), (cv.state.shape, (6, 3, 3, 160)),
        (family.kv_pool_layers(cfg), 2),
        (family.kv_token_layer_bytes(cfg), 2 * 2 * 16 * 4),
        (family.state_slot_bytes(cfg), 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)),
        (set(family.step_counters(cfg)), {"state_rows", "global_kv_tokens"})]


CASE = Case(
    family=family, preset="debug-granite-hybrid-tiny", hf=HF,
    reference=reference, page=PAGE,
    # a prefill of two whole scan chunks (16) and two pages, an extend from
    # the chunk boundary (32) and one from inside a chunk (44), then decode
    spec={"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
          "decode_steps": 5, "tolerance": 1e-5},
    # and a prefill of 37 (two chunks of 16 and 5), extends of 7 from inside
    # a chunk and inside a page
    runs=(("seed3", {}, 3), ("seed4", {}, 4), ("seed5", {}, 5),
          ("no_multiple_of_the_chunk",
           {"prefill_tokens": 37, "extend_tokens": 7}, 9)),
    reads=_reads,
    controls={name: _control(name) for name in (
        "residual_one", "attention_by_sqrt", "two_groups", "no_decay",
        "conv_not_carried", "live_mask_off")},
    refused=(
        ({"num_local_experts": 4}, "num_local_experts"),
        ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
        ({"position_embedding_type": "rope"}, "position_embedding_type"),
        ({"mamba_expand": 4}, "mamba_expand"),
        ({"mamba_n_groups": 2}, "mamba_n_groups"),
        ({"mamba_proj_bias": True}, "mamba_proj_bias"),
        ({"mamba_conv_bias": False}, "mamba_conv_bias"),
        ({"attention_bias": True}, "attention_bias"),
        ({"layer_types": [MAMBA] * 7 + ["full_attention"]}, "layer_types"),
        ({"num_hidden_layers": 9}, "layer_types"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"time_step_limit": [0.0, 1.0]}, "time_step_limit")),
    state=State(slot_axis=(1, 1), atol=1e-5, pool=_pool_holds,
                counters=lambda cfg, rows, cells: {
                    "state_rows": rows, "global_kv_tokens": 2 * cells}),
    engine=suite.state_engine("int8 page pool beside a recurrent state"))


def test_a_decode_step_that_is_not_live_before_each_extend_changes_nothing(
        params):
    """check_ssm_dense's `interleaved_decode`: what a burst beside a chunked
    prefill does to the prefilling slot, with the mask."""
    plain = correctness.check(family, CASE.cfg, params, HF, CASE.spec, 3,
                              PAGE, reference)
    stepped = correctness.check(
        check_ssm_dense.variants(family)["interleaved_decode"], CASE.cfg,
        params, HF, CASE.spec, 3, PAGE, reference)
    assert stepped["ok"]
    assert stepped["max_rel_rms_err"] == plain["max_rel_rms_err"]


# --- the catalog's row -------------------------------------------------------

@pytest.fixture(scope="module")
def row():
    with open(ROW) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "granite-4.0-h-micro":
                return entry["config"]
    pytest.skip("the catalog has no granite-4.0-h-micro row here")


def test_the_catalog_row_gives_the_shapes_and_bytes_the_issue_counted(row):
    cfg = config_from_hf(row)
    assert family_for(cfg) is family and cfg.dtype == jnp.bfloat16
    assert cfg.head_dim_ == 64 and (cfg.num_heads, cfg.num_kv_heads) == (32, 8)
    assert (cfg.d_inner, cfg.conv_dim, cfg.ssm_groups) == (4096, 4352, 1)
    assert (cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)) == (36, 4)
    assert [g.count for g in family._groups(cfg)] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert (cfg.embedding_multiplier, cfg.logits_scaling,
            cfg.residual_multiplier, cfg.attention_multiplier) == (
        12.0, 8.0, 0.22, 0.015625)
    assert cfg.tie_word_embeddings and cfg.chunk_size == 256
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert "lm_head" not in shapes
    assert shapes["r2_ssm_in"].shape == (9, 2048, 4096 + 4352 + 64)
    assert shapes["r7_wq"].shape == (1, 2048, 2048)
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    # the issue's layers, the table once (tied) and the final norm
    assert n == 36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2048 + 2048
    assert n == 3_191_396_096  # the issue's 3,191.4 M
    assert family.state_slot_bytes(cfg) == 36 * (64 * 64 * 128 * 4
                                                 + 3 * 4352 * 2)
    assert family.kv_token_layer_bytes(cfg) == 2 * 8 * 64 * 2
    ck, cv = jax.eval_shape(lambda: family.init_kv_pages(cfg, 544, 128,
                                                         num_slots=32))
    # 8 KV heads of 64 as 4 rows of 128 lanes: the same bytes, none padding
    assert cfg.pool_pack == 2 and ck.pages.shape == (4, 544, 128, 4, 128)
    assert ck.state.shape == (36, 32, 64, 64, 128)
    assert cv.state.shape == (36, 32, 3, 4352)


def test_every_other_class_refuses_the_catalog_row(row):
    """The row read as another family's `model_type` is refused by the keys
    it states, not served as that model."""
    for module in FAMILIES:
        if module is family:
            continue
        with pytest.raises((ValueError, NotImplementedError, KeyError)):
            config_from_hf({**row,
                            "model_type": module.FAMILY.model_types[0]})
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf({**row, "model_type": "llama"})


def test_an_int8_pool_weights_and_adapters_are_refused_by_the_record():
    with pytest.raises(NotImplementedError, match="int8 page pool beside a "
                       "recurrent state"):
        family.init_kv_pages(CASE.cfg, 4, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 weights"):
        family.FAMILY.refuse(int8_weights=True)
    with pytest.raises(NotImplementedError, match="adapter pools"):
        family.FAMILY.refuse(lora=True)
