"""models/granite_hybrid.py on the CPU at a small size, float32, seeded
weights (docs/granite-hybrid.md): the family's prefill -> two extend chunks
(one from a scan-chunk boundary, one from inside a chunk) -> decode steps
through the pool and the state against the plain reference's one forward
pass (benchmark/reference/granite_hybrid.py, its state stepped token by
token) under 1e-5; the controls of benchmark/check_ssm_dense.py, one term
wrong each, that must FAIL by over 1e-3; the life of the state per slot; the
catalog's row read key for key with the shapes and bytes ISSUE 55 counted,
and what the family does not compute refused by name; the family through
the continuous-batching engine."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_limits, check_ssm_dense, correctness
from benchmark.reference import granite_hybrid as reference
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.models import FAMILIES, config_from_hf, family_for
from llmlb_tpu.models import granite_hybrid as family
from llmlb_tpu.models.llama import StatePool
from tests.support import collect_events

CFG = get_preset("debug-granite-hybrid-tiny")
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
# a prefill of two whole scan chunks (16) and two pages, an extend from the
# chunk boundary (32) and one from inside a chunk (44), then decode steps
SPEC = {"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
        "decode_steps": 5, "tolerance": 1e-5}
PAGE = 16
CONTROL_FAILS_BY = 1e-3
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def params():
    return family.init_params(CFG, jax.random.PRNGKey(7))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def test_the_preset_is_the_published_config_read():
    cfg = config_from_hf(HF, jnp.float32)
    assert cfg == CFG and family_for(cfg) is family
    assert (cfg.layers_of(MAMBA), cfg.layers_of(ATTENTION)) == (6, 2)
    assert cfg.head_dim_ == 16 and cfg.d_inner == 128 and cfg.conv_dim == 160
    # runs of like layers: 2 M, A, 3 M, A, 1 M, each a stack of its own and
    # in its pool at its kind's next rows
    assert [(g.count, g.prefix, g.start, g.pool_layer, g.scope)
            for g in family._groups(cfg)] == [
        (2, "r0_", 0, 0, "ssm_layers"), (1, "r1_", 0, 0, "attention_layer"),
        (3, "r2_", 0, 2, "ssm_layers"), (1, "r3_", 0, 1, "attention_layer"),
        (1, "r4_", 0, 5, "ssm_layers")]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_prefill_extend_decode_match_the_reference_at_every_position(
        params, seed):
    out = correctness.check(family, CFG, params, HF, SPEC, seed, PAGE,
                            reference)
    assert out["ok"] and out["max_rel_rms_err"] < 1e-5, out
    assert out["positions_compared"] == 1 + 2 + 5


def test_lengths_that_are_no_multiple_of_the_chunk_match_too(params):
    """A prefill of 37 (two chunks of 16 and 5), extends of 7 from inside a
    chunk and inside a page."""
    spec = {**SPEC, "prefill_tokens": 37, "extend_tokens": 7}
    out = correctness.check(family, CFG, params, HF, spec, 9, PAGE, reference)
    assert out["ok"] and out["max_rel_rms_err"] < 1e-5, out


# --- controls: one term wrong, and the comparison must fail ------------------

def _on(true_params, _given, hf, ids, **kw):
    """The reference's pass over the TRUE weights, whatever the program was
    handed."""
    return reference.forward(true_params, hf, ids, **kw)


CONTROLS = ("residual_one", "attention_by_sqrt", "two_groups", "no_decay",
            "conv_not_carried", "live_mask_off")


@pytest.mark.parametrize("control", CONTROLS)
def test_a_program_with_one_term_wrong_fails_the_comparison(control, params):
    served = check_ssm_dense.variants(family).get(control, family)
    cfg = check_ssm_dense.configurations(CFG).get(control, CFG)
    out = correctness.check(
        served, cfg, dict(params), HF, SPEC, 3, PAGE,
        check_limits.like(reference, functools.partial(_on, params)))
    assert not out["ok"] and out["max_rel_rms_err"] > CONTROL_FAILS_BY, out


def test_a_decode_step_that_is_not_live_before_each_extend_changes_nothing(
        params):
    """check_ssm_dense's `interleaved_decode`: what a burst beside a chunked
    prefill does to the prefilling slot, with the mask."""
    plain = correctness.check(family, CFG, params, HF, SPEC, 3, PAGE,
                              reference)
    stepped = correctness.check(
        check_ssm_dense.variants(family)["interleaved_decode"], CFG, params,
        HF, SPEC, 3, PAGE, reference)
    assert stepped["ok"]
    assert stepped["max_rel_rms_err"] == plain["max_rel_rms_err"]


# --- the state's life --------------------------------------------------------

def _pool(slots, pages=9):
    return family.init_kv_pages(CFG, pages, PAGE, num_slots=slots)


def _prefill(params, rows, lens, slots, pool, width):
    ids = np.zeros((len(rows), width), np.int32)
    for i, row in enumerate(rows):
        ids[i, :len(row)] = row
    tables = jnp.asarray([[1 + 2 * s, 2 + 2 * s] for s in slots], jnp.int32)
    return family.prefill_into_pages(
        params, CFG, jnp.asarray(ids), jnp.asarray(lens, jnp.int32), tables,
        *pool, None, slot_ids=jnp.asarray(slots, jnp.int32))


def test_a_repeated_row_and_a_used_slot_write_the_state_of_their_prompt(params):
    """A prefill group padded by repeating its last row (both write slot
    1), into a pool whose slots hold another request's state: what slot 1
    holds afterwards is its prompt's alone, and slot 2 is untouched."""
    a, b = _ids(21, 1).tolist(), _ids(13, 2).tolist()
    _, want_k, want_v, _ = _prefill(params, [b], [13], [0], _pool(1), 16)
    ck, cv = _pool(3)
    ck = ck._replace(state=ck.state + 3.0)  # what a finished request left
    cv = cv._replace(state=cv.state - 2.0)
    _, ck, cv, counters = _prefill(params, [a, b, b], [21, 13, 13], [0, 1, 1],
                                   (ck, cv), 32)
    np.testing.assert_allclose(np.asarray(ck.state[:, 1]),
                               np.asarray(want_k.state[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cv.state[:, 1]),
                               np.asarray(want_v.state[:, 0]), atol=1e-5)
    assert (np.asarray(ck.state[:, 2]) == 3.0).all()
    assert (np.asarray(cv.state[:, 2]) == -2.0).all()
    assert int(counters["scan_tokens"]) == 21 + 13 + 13
    assert int(counters["scan_chunks"]) == 3 * 2
    assert int(counters["state_rows"]) == 3
    assert int(counters["global_kv_tokens"]) == 2 * (21 + 13 + 13)


def test_a_decode_step_advances_the_live_rows_alone(params):
    """A step with row 1 not live (a slot mid-way through a chunked
    prefill, or free): its state and its carried rows stay bit for bit,
    row 0's move, and row 0's logits are what a step with every row live
    gives."""
    a, b = _ids(21, 1).tolist(), _ids(13, 2).tolist()
    _, ck, cv, _ = _prefill(params, [a, b], [21, 13], [0, 1], _pool(2), 32)
    before_k, before_v = np.asarray(ck.state), np.asarray(cv.state)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    args = (jnp.asarray([5, 6], jnp.int32), jnp.asarray([21, 13], jnp.int32))

    def step(live):
        k = StatePool(ck.pages + 0, ck.state + 0)
        v = StatePool(cv.pages + 0, cv.state + 0)
        return family.decode_step_paged(
            params, CFG, *args, k, v, tables, None, window=32,
            live=None if live is None else jnp.asarray(live))

    logits, k, v, counters = step([True, False])
    assert (np.asarray(k.state)[:, 1] == before_k[:, 1]).all()
    assert (np.asarray(v.state)[:, 1] == before_v[:, 1]).all()
    assert (np.asarray(k.state)[:, 0] != before_k[:, 0]).any()
    assert (np.asarray(v.state)[:, 0] != before_v[:, 0]).any()
    assert int(counters["state_rows"]) == 1
    assert int(counters["global_kv_tokens"]) == 2 * 22
    both, k2, _v2, counters = step(None)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(both[0]),
                               atol=1e-5)
    assert (np.asarray(k2.state)[:, 1] != before_k[:, 1]).any()
    assert int(counters["state_rows"]) == 2
    assert int(counters["global_kv_tokens"]) == 2 * (22 + 14)


def test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot():
    ck, cv = family.init_kv_pages(CFG, 5, PAGE, num_slots=3)
    # two KV heads of 16 side by side in a row (`pool_pack`)
    assert CFG.pool_pack == 2
    assert ck.pages.shape == cv.pages.shape == (2, 5, PAGE, 1, 32)
    assert ck.state.shape == (6, 3, 8, 16, 16) and ck.state.dtype == jnp.float32
    assert cv.state.shape == (6, 3, 3, 160)
    assert family.init_kv_pages(CFG, 5, PAGE)[0].state.shape[1] == 1
    assert family.kv_pool_layers(CFG) == 2
    assert family.kv_token_layer_bytes(CFG) == 2 * 2 * 16 * 4
    assert family.state_slot_bytes(CFG) == 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert family.kv_wire_cell(CFG) is None
    assert not hasattr(family, "verify_step_paged")
    assert set(family.step_counters(CFG)) == {"state_rows",
                                              "global_kv_tokens"}


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


@pytest.mark.parametrize("change,named", [
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
    ({"time_step_limit": [0.0, 1.0]}, "time_step_limit"),
])
def test_a_config_it_does_not_compute_is_refused_by_name(change, named):
    with pytest.raises(NotImplementedError, match=named):
        config_from_hf({**HF, **change}, jnp.float32)


def test_an_int8_pool_weights_and_adapters_are_refused_by_the_record():
    with pytest.raises(NotImplementedError, match="int8 page pool beside a "
                       "recurrent state"):
        family.init_kv_pages(CFG, 4, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 weights"):
        family.FAMILY.refuse(int8_weights=True)
    with pytest.raises(NotImplementedError, match="adapter pools"):
        family.FAMILY.refuse(lora=True)


# --- through the continuous-batching engine ----------------------------------

ARGS = dict(num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
            kv_page_size=16, decode_burst=4, eos_id=-1)
MARGIN = 1e-3  # of the reference's top two logits: wider than rounding


def _assert_greedy(params, prompt, tokens):
    """The tokens are the reference's argmax, one forward pass over prompt
    + tokens, wherever its top two logits are not a tie."""
    logits = reference.forward(params, HF, np.asarray(prompt + tokens))
    rows = np.asarray(logits)[len(prompt) - 1:-1]
    top = np.sort(rows, axis=-1)
    wide = top[:, -1] - top[:, -2] > MARGIN
    assert wide.sum() >= len(tokens) - 2
    assert (np.argmax(rows, -1)[wide] == np.asarray(tokens)[wide]).all(), (
        len(prompt), tokens, np.argmax(rows, -1).tolist())


def test_tokens_equal_the_references_argmax_on_every_path_of_the_state(params):
    """Seven requests on four slots, all at once: 70 and 40 tokens prefill
    in chunks of 32 while other rows decode in bursts of 4 (a burst steps
    every slot: the prefilling slot's state must stay), the short ones are
    admitted as a group, and the fifth to seventh take a slot another
    request's state was left in. The step records carry the counters."""
    core = EngineCore(CFG, params, **ARGS)
    core.start()
    try:
        prompts = [np.random.default_rng(10 + n).integers(8, 500, n).tolist()
                   for n in (17, 40, 5, 70, 33, 20, 9)]
        requests = [core.submit(Request(
            prompt_ids=p, sampling=SamplingParams(max_tokens=12,
                                                  temperature=0.0)))
            for p in prompts]
        for prompt, request in zip(prompts, requests):
            tokens, reason, _ = collect_events(request, 300)
            assert reason == "length" and len(tokens) == 12
            _assert_greedy(params, prompt, tokens)
        recs = core.step_stats.snapshot(limit=512)["records"]
        decodes = [r for r in recs if r["kind"] == "decode"]
        prefills = [r for r in recs if r["kind"] == "prefill"]
        assert decodes and prefills
        for r in decodes:  # rows x steps of the burst, the live rows alone
            assert r["state_rows"] == r["tokens"]
            assert r["global_kv_tokens"] >= r["tokens"] * 2 * 5
            assert "scan_tokens" not in r
        for r in prefills:
            assert r["scan_tokens"] == r["tokens"] and r["scan_chunks"] >= 1
        assert any(r["tokens"] == 32 for r in prefills), "no chunk recorded"
        m = core.metrics.summary()
        assert m["ssm_state_rows_total"] >= sum(r["state_rows"] for r in recs)
        assert m["global_kv_tokens_total"] > 0
        assert core.quant_info()["state_bytes"] == 4 * family.state_slot_bytes(
            CFG)
    finally:
        core.stop()


@pytest.mark.parametrize("kw,message", [
    (dict(prefix_cache=True), "the prefix cache"),
    (dict(spec_decode=True), "speculative decoding"),
    (dict(quantize="kv"), "int8 page pool beside a recurrent state"),
    (dict(quantize="weights"), "does not serve int8 weights"),
])
def test_an_engine_that_would_serve_the_state_wrong_does_not_start(
        params, kw, message):
    with pytest.raises(NotImplementedError, match=message):
        EngineCore(CFG, params, **{**ARGS, **kw})
