"""models/nemotron_h.py on the CPU at a small size, seeded weights
(docs/hybrid-state.md): the family's prefill -> two extend chunks (one from
a scan-chunk boundary, one from inside a chunk) -> decode steps through the
pool against the plain reference's one forward pass
(benchmark/reference/nemotron_h.py), routing followed; the shares of the
experts adding up to the uncut layer; the life of the state per slot — a
padded bucket, a prefill group with a repeated row, a slot used again, a
decode step beside a row that is not live; the configuration read from its
published keys and what it does not compute refused by name; and controls
that must FAIL the comparison."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_hybrid, correctness
from benchmark.reference import nemotron_h as reference
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import config_from_hf, family_for
from llmlb_tpu.models import nemotron_h as family
from llmlb_tpu.models.llama import StatePool
from llmlb_tpu.ops import ssm
from llmlb_tpu.ops.norms import rms_norm

CFG = get_preset("debug-nemotron-h-tiny")
HF = {
    "model_type": "nemotron_h", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 32, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "num_hidden_layers": 7,
    "hybrid_override_pattern": "MEM*EME", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 16, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
    "max_position_embeddings": 512,
    "expert_parallel": {"chips": 2, "chip": 1, "experts": 8},
}
# a prefill of two whole scan chunks (16), an extend from the boundary (32)
# and one from inside a chunk (44), then decode steps
SPEC = {"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
        "decode_steps": 5, "tolerance": 1e-3, "router_tolerance": 1e-4,
        "flip_margin_multiple": 6.0}
PAGE = 16


@pytest.fixture(scope="module")
def params():
    return family.init_params(CFG, jax.random.PRNGKey(7))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def test_the_preset_is_the_published_config_read():
    cfg = config_from_hf(HF, jnp.float32)
    assert cfg == CFG and family_for(cfg) is family
    assert cfg.held_experts == (4, 4) and cfg.router_experts == 8
    assert (cfg.layers_of("M"), cfg.layers_of("*"), cfg.layers_of("E")) == (
        3, 1, 3)
    assert cfg.d_inner == 64 and cfg.conv_dim == 64 + 2 * 2 * 16


def test_prefill_extend_decode_match_the_reference_with_routing_followed(params):
    out = correctness.check(family, CFG, params, HF, SPEC, 3, PAGE, reference)
    assert out["ok"] and out["grounds"] == [], out
    assert out["max_rel_rms_err"] < 1e-4 and out["router_rel_rms_err"] < 1e-5
    assert out["dropped_assignments"] == 0 and out["choice_is_own_topk"]
    assert out["positions_compared"] == 1 + 2 + 5


@contextlib.contextmanager
def _rows_not_carried():
    real = ssm.causal_conv
    ssm.causal_conv = lambda x, prev, *r: real(x, prev * 0, *r)
    try:
        yield
    finally:
        ssm.causal_conv = real


@pytest.mark.parametrize("control", [check_hybrid.no_decay, _rows_not_carried],
                         ids=lambda f: f.__name__)
def test_a_program_with_one_term_wrong_fails_the_comparison(control, params):
    """Another config object than any traced before, so that the family's
    jitted functions trace again under the patch."""
    cfg = dataclasses.replace(CFG, max_position_embeddings=511)
    with control():
        out = correctness.check(family, cfg, params, HF, SPEC, 3, PAGE,
                                reference)
    assert not out["ok"] and "logits" in out["grounds"], out


# --- (c) the shares add up ---------------------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The layer with all 8 experts, cut into the shares of chip 0 and chip
    1: each chip's routed part plus the shared expert counted ONCE is the
    reference's uncut layer, and the program's share is the reference's."""
    whole = dataclasses.replace(CFG, first_expert=0, num_experts=8)
    p = family.init_params(whole, jax.random.PRNGKey(11))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(19, 64)), jnp.float32)
    kw = dict(top_k=2, scale=2.5, normalize=True, eps=1e-5)
    moe = [p[n] for n in reference._MOE]
    uncut, scores = reference.expert_layer(x, 1, *moe, first=0, **kw)
    h = rms_norm(x, p["ln_mlp"][1], 1e-5)
    shared = family.relu2(h @ p["ws_up"][1]) @ p["ws_down"][1]
    total = x - shared  # x + both chips' layers - the shared expert once
    elsewhere = []
    for chip in (0, 1):
        first = 4 * chip
        cfg = dataclasses.replace(CFG, first_expert=first)
        lp = {n: p[n][1] for n in ("router", "router_bias", "ws_up",
                                   "ws_down")}
        lp.update(we_up=p["we_up"][:, first:first + 4],
                  we_down=p["we_down"][:, first:first + 4], layer=1)
        out, routing = family._moe_mlp_fn(cfg)(lp, h[None], None)
        total = total + out[0]
        elsewhere.append(int(routing.elsewhere))
        assert int(routing.load.sum()) + elsewhere[-1] == 19 * 2
        assert routing.load.shape == (4,) and routing.scores.shape == (19, 8)
        # the reference, given the same share, computes the same part
        moe_share = [lp["we_up"] if n == "we_up" else lp["we_down"]
                     if n == "we_down" else p[n] for n in reference._MOE]
        ref_share, _ = reference.expert_layer(x, 1, *moe_share, first=first,
                                              **kw)
        np.testing.assert_allclose(np.asarray(x + out[0]),
                                   np.asarray(ref_share), atol=2e-5)
        np.testing.assert_allclose(np.asarray(routing.scores),
                                   np.asarray(scores), atol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=3e-5)
    assert sum(elsewhere) == 19 * 2  # each assignment is one chip's


# --- (d) the state's life ----------------------------------------------------

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
    np.testing.assert_allclose(np.asarray(padded), np.asarray(exact), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pk.state), np.asarray(ck.state),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(pv.state), np.asarray(cv.state),
                               atol=1e-5)
    assert np.abs(np.asarray(ck.state)).max() > 0


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
                               np.asarray(want_k.state[:, 0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(cv.state[:, 1]),
                               np.asarray(want_v.state[:, 0]), atol=1e-5)
    assert (np.asarray(ck.state[:, 2]) == 3.0).all()
    assert (np.asarray(cv.state[:, 2]) == -2.0).all()
    assert int(counters["scan_tokens"]) == 21 + 13 + 13
    assert int(counters["scan_chunks"]) == 3 * 2 and int(counters["state_rows"]) == 3


def test_a_decode_step_advances_the_live_rows_alone(params):
    """Two slots prefilled; a step with row 1 not live (a slot that is
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
    assert (np.asarray(v.state)[:, 1] == before_v[:, 1]).all()
    assert (np.asarray(k.state)[:, 0] != before_k[:, 0]).any()
    assert (np.asarray(v.state)[:, 0, -1] != before_v[:, 0, -1]).any()
    assert int(counters["state_rows"]) == 1
    both, k2, _v2, counters = step(None)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(both[0]),
                               atol=1e-5)
    assert (np.asarray(k2.state)[:, 1] != before_k[:, 1]).any()
    assert int(counters["state_rows"]) == 2
    # the experts are routed nowhere for the row that is not live
    lm, k_tok = CFG.num_moe_layers, CFG.experts_per_token
    one = step([True, False])[3]
    assert int(one["expert_assignments"]) + int(
        one["assignments_elsewhere"]) == lm * k_tok


def test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot():
    ck, cv = family.init_kv_pages(CFG, 5, PAGE, num_slots=3)
    assert ck.pages.shape == cv.pages.shape == (1, 5, PAGE, 2, 16)
    assert ck.state.shape == (3, 3, 8, 8, 16) and ck.state.dtype == jnp.float32
    assert cv.state.shape == (3, 3, 3, 128)
    assert family.init_kv_pages(CFG, 5, PAGE)[0].state.shape[1] == 1
    assert family.kv_pool_layers(CFG) == 1
    assert family.kv_token_layer_bytes(CFG) == 2 * 2 * 16 * 4
    assert family.state_slot_bytes(CFG) == 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert family.kv_wire_cell(CFG) is None
    assert not hasattr(family, "verify_step_paged")


def test_routing_and_counters_leave_logits_and_pool_bit_equal(params):
    a = _ids(21, 1).tolist()
    plain = _prefill(params, [a], [21], [0], _pool(1), 32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    heard = family.prefill_into_pages(
        params, CFG, jnp.asarray([a + [0] * 11], jnp.int32),
        jnp.asarray([21], jnp.int32), tables, *_pool(1), None, routing=True)
    assert (np.asarray(plain[0]) == np.asarray(heard[0])).all()
    assert (np.asarray(plain[1].state) == np.asarray(heard[1].state)).all()
    chosen, scores, kept = heard[3]
    assert chosen.shape == kept.shape == (3, 1, 32, 2) and bool(kept.all())
    assert scores.shape == (3, 1, 32, 8)  # over every expert, held or not
    assert set(plain[3]) == {"experts_touched", "expert_assignments",
                             "expert_load_max", "expert_load_hist",
                             "assignments_elsewhere", "state_rows",
                             "scan_tokens", "scan_chunks"}
    assert set(family.step_counters(CFG)) == set(plain[3]) - {
        "scan_tokens", "scan_chunks"}


# --- (e) what the family does not compute is refused by name -----------------

@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "MEM-EME"),  # a dense feed-forward layer
    ("num_hidden_layers", 8),
    ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"),
    ("n_group", 2),
    ("use_conv_bias", False),
    ("mamba_proj_bias", True),
    ("n_shared_experts", 2),
    ("tie_word_embeddings", True),
    ("time_step_limit", [0.0, 0.5]),
])
def test_what_the_family_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        config_from_hf({**HF, key: value}, jnp.float32)


def test_a_share_that_does_not_divide_the_experts_is_refused():
    with pytest.raises(ValueError, match="expert_parallel"):
        config_from_hf({**HF, "expert_parallel": {"chips": 3, "chip": 0,
                                                   "experts": 8}}, jnp.float32)
    with pytest.raises(ValueError, match="expert_parallel"):
        config_from_hf({**HF, "expert_parallel": {"chips": 2, "chip": 2,
                                                   "experts": 8}}, jnp.float32)
    whole = config_from_hf({**HF, "n_routed_experts": 8,
                            "expert_parallel": None}, jnp.float32)
    assert whole.held_experts == (0, 8)


def test_an_int8_pool_is_refused():
    with pytest.raises(NotImplementedError, match="int8 page pool"):
        family.init_kv_pages(CFG, 5, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 page pool"):
        family.kv_token_layer_bytes(CFG, quantized=True)


@pytest.mark.parametrize("key", ["hybrid_override_pattern", "mamba_num_heads",
                                 "ssm_state_size", "expert_parallel"])
@pytest.mark.parametrize("model_type", ["llama", "mistral", "mixtral",
                                        "deepseek_v3", "sdar_moe",
                                        "some_new_model"])
def test_a_hybrid_config_is_never_read_by_another_class(model_type, key):
    """`models.config_from_hf`: a config that states a state-space layer, a
    layer pattern or a chip's share, under a `model_type` whose class reads
    none of it, would be served as another model. Refused, naming the key."""
    dense = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4}
    with pytest.raises(ValueError, match=key):
        config_from_hf({**dense, "model_type": model_type, key: HF[key]})


def test_the_hybrid_class_reads_the_mixture_keys_the_dense_class_refuses():
    dense = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4}
    for key, value in (("n_routed_experts", 4), ("n_shared_experts", 2),
                       ("moe_intermediate_size", 32)):
        with pytest.raises(ValueError, match=key):
            config_from_hf({**dense, "model_type": "llama", key: value})
    assert config_from_hf(HF, jnp.float32).moe_intermediate_size == 32
