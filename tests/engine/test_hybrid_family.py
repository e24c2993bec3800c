"""models/nemotron_h.py on the CPU at a small size, seeded weights
(docs/hybrid-state.md). The family's record for the suite
(tests/engine/family_suite.py): prefill -> two extend chunks (one from a
scan-chunk boundary, one from inside a chunk) -> decode steps through the
pool against the plain reference's one forward pass
(benchmark/reference/nemotron_h.py), routing followed; the shares of the
experts adding up to the uncut layer; the life of the state per slot; the
configuration read from its published keys and what it does not compute
refused by name; controls that must FAIL the comparison. Its own: a padded
bucket, the routing hook, a share that does not divide, and the keys no
other class reads."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_hybrid
from benchmark.reference import nemotron_h as reference
from llmlb_tpu.models import config_from_hf
from llmlb_tpu.models import nemotron_h as family
from llmlb_tpu.ops import ssm
from llmlb_tpu.ops.norms import rms_norm
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Shares,
    State,
    test_a_decode_step_advances_the_live_rows_alone,
    test_a_padded_bucket_leaves_the_state_of_the_true_prompt,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_a_repeated_row_and_a_used_slot_write_the_state_of_their_prompt,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot,
    test_the_preset_is_the_published_config_read,
    test_the_shares_add_up_to_the_uncut_layer,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

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
PAGE = 16


def _reads(cfg):
    return [
        ((cfg.held_experts, cfg.router_experts), ((4, 4), 8)),
        ((cfg.layers_of("M"), cfg.layers_of("*"), cfg.layers_of("E")),
         (3, 1, 3)),
        ((cfg.d_inner, cfg.conv_dim), (64, 64 + 2 * 2 * 16))]


@contextlib.contextmanager
def _rows_not_carried():
    real = ssm.causal_conv
    ssm.causal_conv = lambda x, prev, *r: real(x, prev * 0, *r)
    try:
        yield
    finally:
        ssm.causal_conv = real


def _traced_under(patch, positions):
    """Another config object than any traced before, so that the family's
    jitted functions trace again under the patch."""
    return lambda params: CASE.control(params, patch=patch, cfg=(
        dataclasses.replace(CASE.cfg, max_position_embeddings=positions)))


def _shares():
    """The layer with all 8 experts, cut into the shares of chip 0 and chip
    1: a chip's routed part is its layer less the token and the shared
    expert, which every chip computes alike."""
    whole = dataclasses.replace(CASE.cfg, first_expert=0, num_experts=8)
    p = family.init_params(whole, jax.random.PRNGKey(11))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(19, 64)), jnp.float32)
    kw = dict(top_k=2, scale=2.5, normalize=True, eps=1e-5)
    uncut, scores = reference.expert_layer(
        x, 1, *(p[n] for n in reference._MOE), first=0, **kw)
    h = rms_norm(x, p["ln_mlp"][1], 1e-5)
    shared = family.relu2(h @ p["ws_up"][1]) @ p["ws_down"][1]
    parts, elsewhere = [], []
    for chip in (0, 1):
        first = 4 * chip
        cfg = dataclasses.replace(CASE.cfg, first_expert=first)
        lp = {n: p[n][1] for n in ("router", "router_bias", "ws_up",
                                   "ws_down")}
        lp.update(we_up=p["we_up"][:, first:first + 4],
                  we_down=p["we_down"][:, first:first + 4], layer=1)
        out, routing = family._moe_mlp_fn(cfg)(lp, h[None], None)
        elsewhere.append(int(routing.elsewhere))
        assert int(routing.load.sum()) + elsewhere[-1] == 19 * 2
        assert routing.load.shape == (4,) and routing.scores.shape == (19, 8)
        np.testing.assert_allclose(np.asarray(routing.scores),
                                   np.asarray(scores), atol=1e-6)
        # the reference, given the same share, computes the same part
        moe_share = [lp[n] if n in ("we_up", "we_down") else p[n]
                     for n in reference._MOE]
        ref_share, _ = reference.expert_layer(x, 1, *moe_share, first=first,
                                              **kw)
        parts.append((out[0] - shared, ref_share - x - shared))
    assert sum(elsewhere) == 19 * 2  # each assignment is one chip's
    return Shares(uncut, parts, lambda total: x + shared + total, atol=2e-5)


def _pool_holds(cfg, ck, cv):
    return [
        (ck.pages.shape, (1, 5, PAGE, 2, 16)), (cv.pages.shape, ck.pages.shape),
        (ck.state.shape, (3, 3, 8, 8, 16)), (cv.state.shape, (3, 3, 3, 128)),
        (family.kv_pool_layers(cfg), 1),
        (family.kv_token_layer_bytes(cfg), 2 * 2 * 16 * 4),
        (family.state_slot_bytes(cfg), 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4))]


CASE = Case(
    family=family, preset="debug-nemotron-h-tiny", hf=HF, reference=reference,
    page=PAGE,
    # a prefill of two whole scan chunks (16), an extend from the boundary
    # (32) and one from inside a chunk (44), then decode steps
    spec={"prefill_tokens": 32, "extend_chunks": 2, "extend_tokens": 12,
          "decode_steps": 5, "tolerance": 1e-3, "router_tolerance": 1e-4,
          "flip_margin_multiple": 6.0},
    tolerance=1e-4, reads=_reads,
    controls={"no_decay": _traced_under(check_hybrid.no_decay, 511),
              "rows_not_carried": _traced_under(_rows_not_carried, 510)},
    refused=tuple(({key: value}, key) for key, value in (
        ("hybrid_override_pattern", "MEM-EME"),  # a dense feed-forward layer
        ("num_hidden_layers", 8),
        ("mlp_hidden_act", "silu"),
        ("mamba_hidden_act", "gelu"),
        ("n_group", 2),
        ("use_conv_bias", False),
        ("mamba_proj_bias", True),
        ("n_shared_experts", 2),
        ("tie_word_embeddings", True),
        ("time_step_limit", [0.0, 0.5]))),
    shares=_shares,
    # the experts are routed nowhere for a row, or a position, that is not
    # live
    state=State(slot_axis=(1, 1), atol=1e-5, pool=_pool_holds,
                counters=lambda cfg, rows, cells: {"state_rows": rows}))


def test_a_row_that_is_not_live_is_routed_nowhere(params):
    a, b = suite.ids(CASE, 21, 1).tolist(), suite.ids(CASE, 13, 2).tolist()
    _, ck, cv, _ = suite.prefill_rows(CASE, params, [a, b], [21, 13], [0, 1],
                                      suite.pool(CASE, 8, slots=2), 32)
    *_, one = family.decode_step_paged(
        params, CASE.cfg, jnp.asarray([5, 6], jnp.int32),
        jnp.asarray([21, 13], jnp.int32), ck, cv,
        jnp.asarray([[1, 2], [3, 4]], jnp.int32), None, window=32,
        live=jnp.asarray([True, False]))
    assert int(one["expert_assignments"]) + int(
        one["assignments_elsewhere"]) == (
        CASE.cfg.num_moe_layers * CASE.cfg.experts_per_token)


def test_routing_and_counters_leave_logits_and_pool_bit_equal(params):
    a = suite.ids(CASE, 21, 1).tolist()
    plain = suite.prefill_rows(CASE, params, [a], [21], [0],
                               suite.pool(CASE, 8), 32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    heard = family.prefill_into_pages(
        params, CASE.cfg, jnp.asarray([a + [0] * 11], jnp.int32),
        jnp.asarray([21], jnp.int32), tables, *suite.pool(CASE, 8), None,
        routing=True)
    assert (np.asarray(plain[0]) == np.asarray(heard[0])).all()
    assert (np.asarray(plain[1].state) == np.asarray(heard[1].state)).all()
    chosen, scores, kept = heard[3]
    assert chosen.shape == kept.shape == (3, 1, 32, 2) and bool(kept.all())
    assert scores.shape == (3, 1, 32, 8)  # over every expert, held or not
    assert set(plain[3]) == {"experts_touched", "expert_assignments",
                             "expert_load_max", "expert_load_hist",
                             "assignments_elsewhere", "state_rows",
                             "scan_tokens", "scan_chunks"}
    assert set(family.step_counters(CASE.cfg)) == set(plain[3]) - {
        "scan_tokens", "scan_chunks"}


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
        family.init_kv_pages(CASE.cfg, 5, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 page pool"):
        family.kv_token_layer_bytes(CASE.cfg, quantized=True)


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
