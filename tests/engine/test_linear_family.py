"""models/olmo_hybrid.py on the CPU at a small size, seeded weights
(docs/linear-attention.md): the family's prefill -> two extend chunks (one
from a page boundary, one from mid-page) -> decode steps through the pool
and the state against the plain reference's one forward pass
(benchmark/reference/olmo_hybrid.py, its state stepped token by token), at
lengths that are no multiple of the rule's chunk too; the life of the state
per slot — a padded bucket, a prefill group with a repeated row, a slot
used again, a decode step beside a row that is not live; the configuration
read from its published keys and what it does not compute refused by name;
five controls, one term wrong each, that must FAIL the comparison; and the
family through the continuous-batching engine.

THE FIGURE. The other families hold 1e-5 here (PERF.md section 2). This one
reads up to 1.5e-5 over the seeds below, and not for the chunk's triangular
solve: with the rule stepped token by token in the program's place the
readings are the same to three digits. A stack of delta-rule layers at
random weights amplifies a perturbation of its input about thirtyfold where
four attention layers give 2.6 (measured on the reference alone), so
float32's rounding arrives larger. The limit here is 5e-5; a control reads
over 1e-3."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_limits, check_linear, correctness
from benchmark.reference import olmo_hybrid as reference
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.models import config_from_hf, family_for
from llmlb_tpu.models import olmo_hybrid as family
from llmlb_tpu.models.llama import StatePool
from llmlb_tpu.ops import delta_rule
from tests.support import collect_events

CFG = get_preset("debug-olmo-hybrid-tiny")
LINEAR, FULL = family.LINEAR, family.FULL
HF = {
    "model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 48,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 3, "num_key_value_heads": 3, "head_dim": 16,
    "hidden_act": "silu", "max_position_embeddings": 512,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL, LINEAR],
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
# a prefill of two whole chunks of the rule (16) and two pages, an extend
# from the page boundary (32) and one from mid-page (44), then decode steps
SPEC = {"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
        "decode_steps": 5, "tolerance": 5e-5}
PAGE = 16
CONTROL_FAILS_BY = 1e-3


@pytest.fixture(scope="module")
def params():
    return family.init_params(CFG, jax.random.PRNGKey(7))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def test_the_preset_is_the_published_config_read():
    cfg = config_from_hf(HF, jnp.float32)
    assert dataclasses.replace(cfg, chunk_size=16) == CFG
    assert cfg.chunk_size == delta_rule.CHUNK == 64 and family_for(cfg) is family
    assert (cfg.layers_of(LINEAR), cfg.layers_of(FULL)) == (4, 1)
    assert cfg.conv_dim == 3 * (8 + 8 + 16) and cfg.head_dim_ == 16
    # three heads are stored as four; 30 as 32; 8 and 4 as they are
    assert cfg.pool_kv_heads == 4
    assert [dataclasses.replace(cfg, num_kv_heads=k).pool_kv_heads
            for k in (1, 2, 4, 5, 8, 9, 30, 32)] == [1, 2, 4, 8, 8, 16, 32, 32]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_prefill_extend_decode_match_the_reference_at_every_position(
        params, seed):
    out = correctness.check(family, CFG, params, HF, SPEC, seed, PAGE,
                            reference)
    assert out["ok"] and out["max_rel_rms_err"] < 5e-5, out
    assert out["positions_compared"] == 1 + 2 + 5


def test_lengths_that_are_no_multiple_of_the_chunk_match_too(params):
    """A prefill of 37 (two chunks of 16 and 5), extends of 7 from inside a
    chunk and inside a page."""
    spec = {**SPEC, "prefill_tokens": 37, "extend_tokens": 7}
    out = correctness.check(family, CFG, params, HF, spec, 9, PAGE, reference)
    assert out["ok"] and out["max_rel_rms_err"] < 5e-5, out


# --- controls: one term wrong, and the comparison must fail ------------------

@contextlib.contextmanager
def _patched(obj, name, value):
    real = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, real)


def _k_not_normalised():
    """`_unit` is asked for q with its scale and for k without."""
    real = family._unit
    return _patched(family, "_unit", lambda x, scale=1.0: (
        real(x, scale) if scale != 1.0 else x.astype(jnp.float32)))


def _norm_on_the_input():
    """The feed-forwards' groups as every other family has them: `ln_mlp`
    norms what the feed-forward takes."""
    real = family._groups
    return _patched(family, "_groups", lambda cfg: [
        g._replace(post_norm=False) for g in real(cfg)])


def _through_its_inputs(params, *, cfg=CFG, **leaves):
    return family, cfg, {**params, **leaves}


CONTROLS = {
    # through what the program is given
    "beta_not_doubled": lambda p: _through_its_inputs(
        p, cfg=dataclasses.replace(CFG, allow_neg_eigval=False)),
    "alpha_dropped": lambda p: _through_its_inputs(
        p, lin_a_log=jnp.full_like(p["lin_a_log"], -1e9)),  # g = 0
    "convolution_shifted_by_one": lambda p: _through_its_inputs(
        p, lin_conv_w=jnp.roll(p["lin_conv_w"], 1, axis=-1)),
    # through the program itself, traced apart under a patch
    "k_not_normalised": lambda p: (
        check_linear.Variant(family, patch=_k_not_normalised), CFG, p),
    "norm_on_the_input_instead_of_the_output": lambda p: (
        check_linear.Variant(family, patch=_norm_on_the_input), CFG, p),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_program_with_one_term_wrong_fails_the_comparison(control, params):
    served, cfg, given = CONTROLS[control](params)
    out = correctness.check(
        served, cfg, given, HF, SPEC, 3, PAGE,
        check_limits.like(reference, functools.partial(_on, params)))
    assert not out["ok"] and out["max_rel_rms_err"] > CONTROL_FAILS_BY, out
    if control == "alpha_dropped":  # and it was a decay worth dropping
        alpha = np.exp(-np.exp(np.asarray(params["lin_a_log"])) * np.log1p(
            np.exp(np.asarray(params["lin_dt_bias"]))))
        assert alpha.min() < 0.9 < alpha.max() <= 1.0


def _on(true_params, _given, hf, ids, **kw):
    """The reference's pass over the TRUE weights, whatever the program was
    handed."""
    return reference.forward(true_params, hf, ids, **kw)


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


def test_a_padded_bucket_leaves_the_state_of_the_true_prompt(params):
    a = _ids(21, 1).tolist()
    exact, ck, cv, _ = _prefill(params, [a], [21], [0], _pool(1), 21)
    padded, pk, pv, _ = _prefill(params, [a], [21], [0], _pool(1), 32)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(exact), atol=1e-4)
    np.testing.assert_allclose(np.asarray(pk.state), np.asarray(ck.state),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(pv.state), np.asarray(cv.state),
                               atol=2e-4)
    assert np.abs(np.asarray(ck.state)).max() > 0


def test_a_repeated_row_and_a_used_slot_write_the_state_of_their_prompt(params):
    """A prefill group padded by repeating its last row (both write slot
    1), into a pool whose slots hold another request's state: what slot 1
    holds afterwards is its prompt's alone (a fresh sequence voids what the
    slot held), and slot 2 is untouched."""
    a, b = _ids(21, 1).tolist(), _ids(13, 2).tolist()
    _, want_k, want_v, _ = _prefill(params, [b], [13], [0], _pool(1), 16)
    ck, cv = _pool(3)
    ck = ck._replace(state=ck.state + 3.0)  # what a finished request left
    cv = cv._replace(state=cv.state - 2.0)
    _, ck, cv, counters = _prefill(params, [a, b, b], [21, 13, 13], [0, 1, 1],
                                   (ck, cv), 32)
    # rounding, amplified layer by layer (the module's docstring): what the
    # slot held before was 3.0 and -2.0
    np.testing.assert_allclose(np.asarray(ck.state[:, 1]),
                               np.asarray(want_k.state[:, 0]), atol=2e-4)
    np.testing.assert_allclose(np.asarray(cv.state[:, :, 1]),
                               np.asarray(want_v.state[:, :, 0]), atol=2e-4)
    assert (np.asarray(ck.state[:, 2]) == 3.0).all()
    assert (np.asarray(cv.state[:, :, 2]) == -2.0).all()
    assert int(counters["scan_tokens"]) == 21 + 13 + 13
    assert int(counters["scan_chunks"]) == 3 * 2
    assert int(counters["state_rows"]) == 3
    assert int(counters["global_kv_tokens"]) == 21 + 13 + 13  # one layer


def test_a_decode_step_advances_the_live_rows_alone(params):
    """Rows of unequal lengths; a step with row 1 not live (a slot that is
    mid-way through a chunked prefill, or free): its state and its carried
    rows stay bit for bit, row 0's move, and row 0's logits are what a step
    with every row live gives."""
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
    assert (np.asarray(v.state)[:, :, 1] == before_v[:, :, 1]).all()
    assert (np.asarray(k.state)[:, 0] != before_k[:, 0]).any()
    assert (np.asarray(v.state)[:, -1, 0] != before_v[:, -1, 0]).any()
    assert int(counters["state_rows"]) == 1
    assert int(counters["global_kv_tokens"]) == 22
    both, k2, _v2, counters = step(None)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(both[0]),
                               atol=1e-4)
    assert (np.asarray(k2.state)[:, 1] != before_k[:, 1]).any()
    assert int(counters["state_rows"]) == 2
    assert int(counters["global_kv_tokens"]) == 22 + 14


def test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot():
    ck, cv = family.init_kv_pages(CFG, 5, PAGE, num_slots=3)
    # three KV heads and a dead fourth; the slots second to last in the rows
    assert ck.pages.shape == cv.pages.shape == (1, 5, PAGE, 4, 16)
    assert ck.state.shape == (4, 3, 8, 3 * 16) and ck.state.dtype == jnp.float32
    assert cv.state.shape == (4, 3, 3, 96)
    assert family.init_kv_pages(CFG, 5, PAGE)[0].state.shape[1] == 1
    assert family.kv_pool_layers(CFG) == 1
    assert family.kv_token_layer_bytes(CFG) == 2 * 4 * 16 * 4
    assert family.state_slot_bytes(CFG) == 4 * (3 * 8 * 16 * 4 + 3 * 96 * 4)
    assert family.kv_wire_cell(CFG) is None
    assert not hasattr(family, "verify_step_paged")
    assert set(family.step_counters(CFG)) == {"state_rows",
                                              "global_kv_tokens"}


def test_the_published_sizes_cost_what_the_issue_counted():
    """The byte arithmetic of benchmark/configs/olmo-hybrid-7b-l16.json off
    the family's own functions, at the published widths."""
    cfg = dataclasses.replace(
        CFG, vocab_size=100352, hidden_size=3840, intermediate_size=11008,
        num_layers=16, num_heads=30, num_kv_heads=30, head_dim=None,
        dtype=jnp.bfloat16, layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 4,
        lin_heads=30, lin_key_dim=96, lin_value_dim=192, chunk_size=64)
    assert cfg.head_dim_ == 128 and cfg.conv_dim == 11520
    assert cfg.pool_kv_heads == 32
    assert family.state_slot_bytes(cfg) == 12 * (30 * 96 * 192 * 4
                                                 + 3 * 11520 * 2)
    assert family.kv_token_layer_bytes(cfg) == 2 * 32 * 128 * 2  # as stored
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert n == 4_100_788_944  # 8.20 GB of bf16
    ck, cv = jax.eval_shape(lambda: family.init_kv_pages(cfg, 400, 128,
                                                         num_slots=32))
    assert ck.state.shape == (12, 32, 96, 5760)  # 45 x 128 lanes, 12 x 8 rows
    assert cv.state.shape == (12, 3, 32, 11520)
    assert ck.pages.shape == (4, 400, 128, 32, 128)


# --- what the family does not compute is refused by name ---------------------

@pytest.mark.parametrize("change,named", [
    ({"layer_types": [LINEAR] * 4 + ["sliding_attention"]}, "layer_types"),
    ({"num_hidden_layers": 6}, "num_hidden_layers"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_parameters"),
    ({"rope_theta": 10000.0}, "rope_theta"),
    ({"linear_num_value_heads": 6}, "linear_num_value_heads"),
    ({"linear_use_gate": False}, "linear_use_gate"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_a_config_it_does_not_compute_is_refused_by_name(change, named):
    with pytest.raises(NotImplementedError, match=named):
        config_from_hf({**HF, **change}, jnp.float32)


def test_an_int8_pool_weights_and_adapters_are_refused_by_the_record():
    with pytest.raises(NotImplementedError, match="int8 page pool beside a "
                       "delta-rule state"):
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
            assert r["global_kv_tokens"] >= r["tokens"] * 5
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
    (dict(quantize="kv"), "int8 page pool beside a delta-rule state"),
    (dict(quantize="weights"), "does not serve int8 weights"),
])
def test_an_engine_that_would_serve_the_state_wrong_does_not_start(
        params, kw, message):
    with pytest.raises(NotImplementedError, match=message):
        EngineCore(CFG, params, **{**ARGS, **kw})
