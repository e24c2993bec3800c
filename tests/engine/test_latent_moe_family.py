"""models/deepseek_v3.py on the CPU at a small size, seeded weights. The
family's record for the suite (tests/engine/family_suite.py): prefill ->
two extend chunks -> decode through a paged latent pool against the plain
reference (benchmark/reference/deepseek_v3.py) with the routing followed,
and controls: a program with one term of the architecture left out must
FAIL the comparison. Its own: the absorbed form against the materialised
one on the same cache; the Pallas latent kernel (interpret mode) against
the XLA fall-back, rows that are not live included; the routing hook and
the counters leaving logits and cache as they were."""

import contextlib
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.reference import deepseek_v3 as reference
from llmlb_tpu.models import deepseek_v3 as family
from llmlb_tpu.ops import attention as attention_ops
from llmlb_tpu.ops import moe as moe_ops
from llmlb_tpu.ops.pallas_attention import paged_latent_decode
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Control,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_the_preset_is_the_published_config_read,
)
from tests.support import identity_kv_pages

HF = {
    "model_type": "deepseek_v3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
    "norm_topk_prob": True, "rope_interleave": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "max_position_embeddings": 512,
}
PAGE = 8
_fresh = itertools.count(1)


def _traced_again(params, patch=None, given=None, ground="logits", **changes):
    """A control on another config object than any traced before: the
    family's jitted functions trace again, through whatever is patched."""
    cfg = dataclasses.replace(
        CASE.cfg, **changes,
        max_position_embeddings=CASE.cfg.max_position_embeddings + next(_fresh))
    return Control(family, cfg, given or params, reference,
                   patch or contextlib.nullcontext, ground)


def _no_latent_norm():
    real = family.rms_norm
    return suite.patched(family, "rms_norm", lambda x, w, eps: (
        x * w if x.shape[-1] == CASE.cfg.kv_lora_rank else real(x, w, eps)))


def _bias_in_the_weights():
    def wrong(logits, bias, k, *, scale=1.0, normalize=True):
        scores = jax.nn.sigmoid(logits) + bias
        picked, idx = jax.lax.top_k(scores, k)  # weighs by score + bias
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        return picked * scale, idx, scores

    return suite.patched(moe_ops, "sigmoid_bias_routing", wrong)


def _choice_by_score_alone():
    real = moe_ops.sigmoid_bias_routing
    return suite.patched(
        moe_ops, "sigmoid_bias_routing",
        lambda logits, bias, k, **kw: real(logits, bias * 0, k, **kw))


def _a_trained_bias(params, patch, **kw):
    """The draw of init_params is small; a bias a trained model might hold,
    with which the sound program still passes."""
    given = {**params, "router_bias": params["router_bias"] * 10}
    sound = _traced_again(given)
    assert correctness.check(family, sound.cfg, given, CASE.hf, CASE.spec, 3,
                             PAGE, reference)["ok"]
    return _traced_again(given, patch, **kw)


CASE = Case(
    family=family, preset="debug-mla-tiny", hf=HF, reference=reference,
    page=PAGE, tolerance=1e-4,
    spec={"prefill_tokens": 24, "extend_chunks": 2, "extend_tokens": 8,
          "decode_steps": 4, "tolerance": 1e-3, "router_tolerance": 1e-4,
          "flip_margin_multiple": 6.0},
    control_spec={"extend_chunks": 0},
    controls={
        "no_kv_a_layernorm": lambda p: _traced_again(p, _no_latent_norm),
        "rope_in_halves_not_pairs": lambda p: _traced_again(
            p, rope_interleave=False),
        "bias_added_to_the_weights": lambda p: _a_trained_bias(
            p, _bias_in_the_weights),
        # its own top-k of its own scores: what fails is what it scored by
        "choice_by_score_without_bias": lambda p: _a_trained_bias(
            p, _choice_by_score_alone, ground="router_rel_rms_err"),
        "scale_left_out": lambda p: _traced_again(
            p, routed_scaling_factor=1.0),
        "unnormalised_weights": lambda p: _traced_again(
            p, norm_topk_prob=False),
        # zeroed on the program's side only: the reference gets the true
        # ones back for its own pass (check_config.py's way)
        "no_shared_experts": lambda p: CASE.control(
            p, given={**p, "ws_down": p["ws_down"] * 0}),
    })


def _serve(params, ids, n_prefill, cap=32, **kw):
    """Prefill `n_prefill` tokens of each row, decode the rest one by one.
    Returns the logits of every call and the two pools."""
    b, t = ids.shape
    ck, cv, tables = identity_kv_pages(family, CASE.cfg, b, cap, page_size=PAGE)
    lens = jnp.full((b,), n_prefill, jnp.int32)
    logits, ck, cv, *extra = family.prefill_into_pages(
        params, CASE.cfg, ids[:, :n_prefill], lens, tables, ck, cv, **kw)
    out, extras = [logits], [extra]
    for pos in range(n_prefill, t):
        logits, ck, cv, *extra = family.decode_step_paged(
            params, CASE.cfg, ids[:, pos], jnp.full((b,), pos, jnp.int32), ck, cv,
            tables, **kw)
        out.append(logits)
        extras.append(extra)
    return out, (ck, cv), extras


def test_absorbed_decode_and_extend_agree_with_materialised_prefill(params):
    """The same positions' logits three ways over one cache layout: a
    prefill of all t tokens (keys and values materialised), a prefill of
    t - 2 then two decode steps (absorbed, paged), and a prefill of t - 2
    then one two-token extend chunk (absorbed over the gathered latent)."""
    b, t = 2, 12
    ids = jax.random.randint(jax.random.PRNGKey(4), (b, t), 0, CASE.cfg.vocab_size)
    whole, (ck_whole, cv_whole), _ = _serve(params, ids, t)
    stepped, (ck_step, cv_step), _ = _serve(params, ids, t - 2)
    np.testing.assert_allclose(np.asarray(stepped[-1]), np.asarray(whole[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ck_step), np.asarray(ck_whole),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cv_step), np.asarray(cv_whole),
                               rtol=1e-4, atol=1e-5)

    ck, cv, tables = identity_kv_pages(family, CASE.cfg, b, 32, page_size=PAGE)
    lens = jnp.full((b,), t - 2, jnp.int32)
    _, ck, cv, _ = family.prefill_into_pages(params, CASE.cfg, ids[:, :t - 2],
                                             lens, tables, ck, cv)
    logits, ck, cv, _ = family.prefill_extend_pages(
        params, CASE.cfg, ids[:, t - 2:], jnp.full((b,), 2, jnp.int32), lens,
        tables, ck, cv)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(whole[0]),
                               rtol=2e-4, atol=2e-4)


def test_the_pool_holds_the_latent_and_one_shared_rope_key(params):
    ck, cv = family.init_kv_pages(CASE.cfg, 5, PAGE)
    assert ck.shape == (CASE.cfg.num_layers, 5, PAGE, CASE.cfg.kv_lora_rank)
    assert cv.shape == (CASE.cfg.num_layers, 5, PAGE, family.ROPE_CELL)
    assert family.kv_token_layer_bytes(CASE.cfg) == (
        CASE.cfg.kv_lora_rank + family.ROPE_CELL) * 4  # float32 here
    _, (_, cv), _ = _serve(params, jnp.ones((1, 6), jnp.int32), 6)
    written = np.asarray(cv[:, 1, :6])
    assert np.abs(written[..., :CASE.cfg.qk_rope_head_dim]).min() > 0
    assert np.abs(written[..., CASE.cfg.qk_rope_head_dim:]).max() == 0  # the tile
    with pytest.raises(NotImplementedError, match="int8 latent"):
        family.init_kv_pages(CASE.cfg, 5, PAGE, quantized=True)


def test_routing_and_counters_leave_logits_and_cache_bit_equal(params):
    b, t = 3, 10
    ids = jax.random.randint(jax.random.PRNGKey(5), (b, t), 0, CASE.cfg.vocab_size)
    plain, pools, counted = _serve(params, ids, t - 2)
    heard, pools_heard, extras = _serve(params, ids, t - 2, routing=True)
    for a, c in zip(plain, heard):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    for a, c in zip(pools, pools_heard):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    lm, k, x = CASE.cfg.num_moe_layers, CASE.cfg.experts_per_token, CASE.cfg.num_experts
    ((chosen, scores, kept),), (counters,) = extras[0], counted[0]  # prefill
    assert chosen.shape == (lm, b, t - 2, k) and scores.shape == (lm, b, t - 2, x)
    assert scores.dtype == jnp.float32 and bool(kept.all())
    # the scores are the quantity whose top-k decides: sigmoid + bias
    top = np.sort(np.argsort(-np.asarray(scores), -1)[..., :k], -1)
    np.testing.assert_array_equal(top, np.sort(np.asarray(chosen), -1))
    assert int(counters["expert_assignments"]) == lm * b * (t - 2) * k
    ((chosen, _, _),), (counters,) = extras[1], counted[1]  # a decode step
    assert chosen.shape == (lm, b, 1, k)
    assert int(counters["expert_assignments"]) == lm * b * k
    touched = sum(len(np.unique(np.asarray(chosen[l]))) for l in range(lm))
    assert int(counters["experts_touched"]) == touched
    hist = np.asarray(counters["expert_load_hist"])
    assert hist.shape == family.step_counters(CASE.cfg)["expert_load_hist"]
    assert hist.sum() == lm * x and hist[:, 0].sum() == lm * x - touched


def test_rows_that_do_not_decode_are_routed_nowhere(params):
    b = 4
    ck, cv, tables = identity_kv_pages(family, CASE.cfg, b, 32, page_size=PAGE)
    live = jnp.asarray([True, False, True, False])
    *_, counters = family.decode_step_paged(
        params, CASE.cfg, jnp.ones((b,), jnp.int32), jnp.full((b,), 3, jnp.int32),
        ck, cv, tables, live=live)
    assert int(counters["expert_assignments"]) == (
        CASE.cfg.num_moe_layers * 2 * CASE.cfg.experts_per_token)


@pytest.mark.parametrize("pages", [None, 2])
def test_pallas_latent_kernel_matches_the_xla_fall_back(pages, monkeypatch):
    """Interpret mode against the einsums over the gathered latent: ragged
    lengths, a row of length 0 (not live: zeros, no page read), a row that
    ends on a page boundary, and a window of fewer pages than the table."""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")
    b, h, c, r, layers, pool, ps, ppn = 5, 4, 32, family.ROPE_CELL, 3, 12, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q_abs = jax.random.normal(ks[0], (b, 1, h, c))
    q_rope = jax.random.normal(ks[1], (b, 1, h, 8))
    c_pages = jax.random.normal(ks[2], (layers, pool, ps, c))
    r_pages = jnp.pad(jax.random.normal(ks[3], (layers, pool, ps, 8)),
                      ((0, 0),) * 3 + ((0, r - 8),))
    tables = jnp.pad(jax.random.permutation(ks[4], jnp.arange(1, pool))[
        :b * 2].reshape(b, 2), ((0, 0), (0, ppn - 2))).astype(jnp.int32)
    lens = jnp.asarray([0, 3, 8, 13, 16], jnp.int32)
    window = None if pages is None else pages * ps
    want = attention_ops.paged_latent_decode(
        q_abs, q_rope, c_pages, r_pages, 1, tables, lens, scale=0.2,
        window=window)
    got = paged_latent_decode(
        q_abs[:, 0], attention_ops._pad_last(q_rope[:, 0], r), c_pages,
        r_pages, 1, tables, lens, scale=0.2, pages=pages or ppn,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want[1:, 0]),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(got[0])).max() == 0.0  # not live: zeros
