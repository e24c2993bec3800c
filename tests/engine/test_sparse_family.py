"""models/dots3_note.py on the CPU at a small size, seeded float32 weights
(docs/sparse-attention.md). The family's record for the suite
(tests/engine/family_suite.py): prefill past the top-k and past the ring ->
an extend from a page boundary and one from inside a page, each LONGER than
the ring -> decode steps, against the plain reference's one whole-sequence
pass (benchmark/reference/dots3_note.py, which selects for itself); each
one-term control of benchmark/check_sparse.py failing the comparison; the
shares of the chips adding up to the uncut layer; a ring used again by a
shorter request; what it does not compute refused by name; and, on one
engine, its tokens equal to the reference's greedy ones. Its own: the
selection while a query sees no more than the top-k cells is the
unrestricted layer BIT FOR BIT; what the three cached tensors and the ring
hold; the selection told to the reference; the catalog's row read key for
key with the shapes and bytes ISSUE 64 counted."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_limits, check_sparse, correctness
from benchmark.reference import dense, dots3_note as reference
from llmlb_tpu.engine import weights
from llmlb_tpu.models import FAMILIES, config_from_hf, deepseek_v3, family_for
from llmlb_tpu.models import dots3_note as family
from tests.engine import family_suite as suite
from tests.engine.family_suite import (  # noqa: F401 — the cases it has
    Case,
    Engine,
    Ring,
    Shares,
    test_a_program_with_one_term_wrong_fails_the_comparison,
    test_a_slot_taken_by_a_shorter_request_sees_nothing_of_its_predecessor,
    test_an_engine_that_would_serve_the_family_wrong_does_not_start,
    test_prefill_extend_decode_match_the_reference_at_every_position,
    test_the_engines_tokens_are_the_references_greedy_tokens,
    test_the_preset_is_the_published_config_read,
    test_the_shares_add_up_to_the_uncut_layer,
    test_what_the_family_does_not_compute_is_refused_by_name,
)

HF = {
    "model_type": "dots3_note", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
    "attention_bias": False, "index_topk": 16, "index_n_heads": 4,
    "index_head_dim": 16,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "sliding_window_size": 5, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 48, "swa_q_lora_rank": 24,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
    "swa_v_head_dim": 16, "swa_rope_theta": 50000, "rope_theta": 80000000,
    "rope_scaling": None, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "tie_word_embeddings": False,
    "max_position_embeddings": 512,
    "expert_parallel": {"chips": 2, "chip": 1, "experts": 8},
}
K, W, PAGE = 16, 5, 8
N_F, N_S = 2, 3
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"
# a prefill past the top-k (24 > 16) and the ring, an extend from a page
# boundary (24) and one from inside a page (44), each four rings long, then
# decode steps that choose 16 of 64 and more cells
SPEC = {"prefill_tokens": 24, "extend_chunks": 2, "extend_tokens": 20,
        "decode_steps": 8, "tolerance": 2e-5, "router_tolerance": 1e-5,
        "flip_margin_multiple": 8.0}
TOTAL = 24 + 2 * 20 + 8


def _reads(cfg):
    record = family.FAMILY
    groups = [(g.prefix, g.count, g.pool_layer, g.scope, g.attends)
              for g in family._groups(cfg)]
    return [
        ((cfg.layers_of(family.FULL), cfg.layers_of(family.SLIDING),
          cfg.num_moe_layers), (N_F, N_S, 4)),
        ((cfg.held_experts, cfg.router_experts, cfg.experts_per_token),
         ((4, 4), 8, 2)),
        ((cfg.index_topk, cfg.index_heads, cfg.index_head_dim), (K, 4, 16)),
        ((cfg.sliding_window, cfg.ring_cells), (W, 128)),
        # F | F | SSS: a run a group, its own stacks, its pool's rows
        (groups, [("r0_", 1, 0, "sparse_layers", True),
                  ("r1_", 1, 1, "sparse_layers", True),
                  ("r2_", 3, 0, "window_layers", False)]),
        # the window's block: the swa sizes where the full layers' were
        ((cfg.window.num_heads, cfg.window.kv_lora_rank,
          cfg.window.qk_nope_head_dim, cfg.window.rope_theta,
          cfg.window.index_topk, cfg.window.attn_gate),
         (2, 48, 24, 5e4, 0, True)),
        ((cfg.q_lora_scale, cfg.kv_lora_scale, cfg.window.kv_lora_scale),
         ((64 / 24) ** 0.5, 2 ** 0.5, (64 / 48) ** 0.5)),
        (record.kv_pool_layers(cfg), N_F),
        # the latent, the rope's cell AND the index key
        (record.kv_token_layer_bytes(cfg), (32 + 128 + 16) * 4),
        (record.state_slot_bytes(cfg), N_S * 128 * (48 + 128) * 4),
        ((record.kv_wire_cell(cfg), record.verifies_drafts,
          record.mixed_step), (None, False, False))]


def _variant(name, **kw):
    return lambda params: CASE.control(
        params, check_sparse.variants(family)[name], **kw)


def _shares():
    """Eight chips holding 1 of 8 experts each (the published eight shares
    at a small size): a chip's part is its routed expert's alone (its layer
    less the shared expert, which every chip computes alike), held against
    the reference's mixture of that share; the uncut reference's layer is
    x + the parts and the shared expert ONCE."""
    whole = {**HF, "n_routed_experts": 8, "num_experts_per_tok": 3,
             "expert_parallel": None}
    cfg = config_from_hf(whole, jnp.float32)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    run, row = "r2_", 1  # a sliding layer's mixture
    m = dense.rms_norm(x, params[run + "ln_mlp"][row], 1e-5)
    lp = {n: params[run + n][row] for n in (
        "router", "router_bias", "ws_gu", "ws_down")}
    shared = dense.swiglu(m, *jnp.split(lp["ws_gu"], 2, axis=-1),
                          lp["ws_down"])
    experts = ("we_gate", "we_up", "we_down")
    names = ("ln_mlp", "router", "router_bias", *experts, "ws_gu", "ws_down")
    rule = dict(top_k=3, scale=1.0, normalize=True, eps=1e-5)
    parts = []
    for chip in range(8):
        share_cfg = config_from_hf(
            {**whole, "n_routed_experts": 1, "expert_parallel": {
                "chips": 8, "chip": chip, "experts": 8}}, jnp.float32)
        held = {n: params[run + n][:, chip:chip + 1] for n in experts}
        got, routing = family._moe_mlp_fn(share_cfg)(
            {**lp, **held, "layer": row}, m[None], None)
        assert int(routing.elsewhere) + int(routing.load.sum()) == 9 * 3
        want, _ = reference.mixture(
            x, row, *({**{n: params[run + n] for n in names}, **held}[n]
                      for n in names), first=chip, shared=False, **rule)
        parts.append((got[0] - shared, want - x))
    full, _ = reference.mixture(x, row, *(params[run + n] for n in names),
                                first=0, **rule)
    return Shares(full, parts, lambda total: x + total + shared)


def _records(case, core, recs):
    decodes = [r for r in recs if r["kind"] == "decode"]
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert decodes and prefills
    for r in decodes:
        assert 0 < r["window_kv_tokens"] <= r["tokens"] * N_S * W
        assert (0 < r["index_selected_cells"] <= r["tokens"] * N_F * K
                and r["index_selected_cells"] <= r["index_scored_cells"])
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == 4 * 2 * r["tokens"])
    # once contexts pass the top-k the indexer scores more than it selects
    assert any(r["index_scored_cells"] > r["index_selected_cells"]
               for r in decodes)
    assert any(r["tokens"] == 32 for r in prefills), "no chunk recorded"
    m = core.metrics.summary()
    assert m["index_scored_cells_total"] >= sum(
        r["index_scored_cells"] for r in recs) > 0
    assert m["index_selected_cells_total"] > 0
    assert m["window_kv_tokens_total"] > 0
    assert m["moe_assignments_elsewhere_total"] > 0
    text = core.metrics.render(queue_depth=0, active_slots=0, num_slots=2)
    for series in ("index_scored_cells_total", "index_selected_cells_total",
                   "window_kv_tokens_total"):  # on /metrics, and moving
        value = next(line.split()[-1] for line in text.splitlines()
                     if line.startswith(f"llmlb_engine_{series} "))
        assert float(value) == m[series] > 0, series


CASE = Case(
    family=family, preset="debug-dots3-note-tiny", hf=HF,
    reference=reference, page=PAGE, spec=SPEC, reads=_reads, tolerance=2e-5,
    # and short chunks (the second from inside a page), lengths under the
    # top-k and under the ring
    runs=(("past_topk_and_ring", {}, 3),
          ("short_chunks", {"extend_tokens": 7, "decode_steps": 6}, 4),
          ("under_topk", {"prefill_tokens": 3, "extend_tokens": 2,
                          "decode_steps": 3}, 6)),
    controls={name: _variant(name) for name in (
        "dense_attention", "no_relu", "topk_half", "window_less_one",
        "no_gate", "no_lora_scales", "index_rope_pairs")} | {
        # the sliding layers are the stack's last and a chunk is four rings
        # long: a chunk's last query sees its own chunk alone, and what the
        # zeroed ring changes is the chunk's first W - 1 tokens' routing
        "ring_zeroed": _variant("ring_zeroed", ground="router_rel_rms_err")},
    refused=tuple(({key: value}, key) for key, value in (
        ("attention_gate_type", "elementwise"),
        ("swa_attention_gate_type", None),
        ("apply_mla_qkv_lora_rescale", False), ("q_lora_rank", None),
        ("index_topk", None), ("scoring_func", "softmax"), ("n_group", 2),
        ("rope_scaling", {"type": "yarn", "factor": 2.0}),
        ("layer_types", ["full_attention"] * 4),
        ("layer_types", ["full_attention"] * 4 + ["chunked_attention"]),
        ("tie_word_embeddings", True))),
    shares=_shares,
    ring=Ring(slot=lambda state, slot: state[:, slot], decode_to=30,
              counters=lambda n: {
                  "window_kv_tokens": N_S * min(n, W),
                  "index_scored_cells": N_F * n,
                  "index_selected_cells": N_F * min(n, K)}),
    # four requests on two slots, all at once: a prompt of 40 prefills in
    # chunks of 32 while the other row decodes in bursts of 4 (the
    # prefilling slot's ring must stay), and the later ones take a slot
    # whose ring and pages another request filled
    engine=Engine(
        args=dict(num_slots=2, slot_capacity=128, prefill_buckets=(16, 32),
                  kv_page_size=PAGE, decode_burst=4, eos_id=-1),
        requests=tuple((suite.prompt(n, 30 + n), out)
                       for n, out in ((40, 24), (12, 30), (6, 12), (70, 8))),
        records=_records,
        refused_starts=(
            (dict(prefix_cache=True), "the prefix cache"),
            (dict(spec_decode=True), "speculative decoding"),
            (dict(kv_ship=True), "kv_ship"),
            (dict(role="split"), "--role split"),
            (dict(quantize="kv"), "int8 latent page pool with index keys"),
            (dict(quantize="weights"), "does not serve int8 weights"),
            (dict(lora_dir="/nonexistent"), "no adapter pools"))))


# --- the selection -------------------------------------------------------------

def test_while_a_query_sees_no_more_than_the_topk_the_layer_is_the_dense_one(
        params):
    """Prefill, extend and decode through the pool at contexts up to the
    top-k: the logits and both pools are BIT FOR BIT those of the same
    weights served with the selection ignored (`index_topk` past every
    context) — the mask is then every causal cell, through the same ops —
    and past the top-k they part."""
    cfg = CASE.cfg
    dense_cfg = dataclasses.replace(cfg, index_topk=1 << 30)
    ids = suite.ids(CASE, 40, 11)

    def served(c, upto):
        ck, cv = family.init_kv_pages(c, 9, PAGE)
        table = suite.table(8)
        out = []
        logits, ck, cv, _ = family.prefill_into_pages(
            params, c, jnp.asarray(ids[None, :6]), jnp.asarray([6]), table,
            ck, cv)
        out.append(logits)
        logits, ck, cv, _ = family.prefill_extend_pages(
            params, c, jnp.asarray(ids[None, 6:12]), jnp.asarray([6]),
            jnp.asarray([6]), table, ck, cv)
        out.append(logits)
        for pos in range(12, upto):
            logits, ck, cv, _ = family.decode_step_paged(
                params, c, jnp.asarray(ids[pos:pos + 1]), jnp.asarray([pos]),
                ck, cv, table, window=64)
            out.append(logits)
        return np.stack([np.asarray(v) for v in out]), ck, cv

    got, ck, cv = served(cfg, K)  # the last query sees cells 0..15: all
    want, dk, dv = served(dense_cfg, K)
    np.testing.assert_array_equal(got, want)
    for a, b in ((ck.pages, dk.pages), (cv.pages, dv.pages),
                 (ck.state, dk.state), (cv.state, dv.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got, _, _ = served(cfg, K + 6)
    want, _, _ = served(dense_cfg, K + 6)
    np.testing.assert_array_equal(got[:len(got) - 6], want[:len(want) - 6])
    assert np.abs(got[-5:] - want[-5:]).max() > 1e-4


def test_a_token_leaves_three_values_in_a_page_and_the_ring_holds_the_last_w(
        params):
    """After a prefill of 21 and an extend of 12 (longer than the ring): the
    latent pool's cells, and in ONE row of the second pool the rope's cell
    (its numbers, then zeros to 128 lanes) and behind it the index key, are
    written for all 33 positions of both full layers; a slot's ring holds
    the last 5 positions' latents in cells p mod 5 and nothing past cell 5;
    the pool's shapes are the record's bytes."""
    cfg = CASE.cfg
    ids = suite.ids(CASE, 33, 12)
    ck, cv = family.init_kv_pages(cfg, 6, PAGE, num_slots=2)
    assert ck.pages.shape == (N_F, 6, PAGE, 32)
    assert cv.pages.shape == (N_F, 6, PAGE, 128 + 16)
    assert ck.state.shape == (N_S, 3, 128, 48)  # slots + the trash ring
    assert cv.state.shape == (N_S, 3, 128, 128)
    table = suite.table(5)
    slot = jnp.asarray([1])
    _, ck, cv, _ = family.prefill_into_pages(
        params, cfg, jnp.asarray(ids[None, :21]), jnp.asarray([21]), table,
        ck, cv, slot_ids=slot)
    _, ck, cv, _ = family.prefill_extend_pages(
        params, cfg, jnp.asarray(ids[None, 21:]), jnp.asarray([12]),
        jnp.asarray([21]), table, ck, cv, slot_ids=slot)
    cells = np.asarray(cv.pages)[:, 1:].reshape(N_F, 5 * PAGE, 144)[:, :33]
    assert (np.abs(cells[..., :8]).max(-1) > 0).all()  # the rope's numbers
    assert (cells[..., 8:128] == 0).all()  # the rest of its tile
    assert (np.abs(cells[..., 128:]).max(-1) > 0).all()  # the index key
    assert (np.abs(np.asarray(ck.pages)[:, 1:].reshape(N_F, 40, 32)[:, :33])
            .max(-1) > 0).all()
    ring = np.asarray(ck.state)[:, 1]
    assert (ring[:, W:] == 0).all() and (np.asarray(ck.state)[:, 0] == 0).all()
    # position p of 28..32 lives in cell p mod 5: what a one-shot prefill of
    # the whole sequence leaves there too
    ok, ov = family.init_kv_pages(cfg, 6, PAGE, num_slots=2)
    padded = np.zeros((1, 40), np.int32)
    padded[0, :33] = ids
    _, ok, ov, _ = family.prefill_into_pages(
        params, cfg, jnp.asarray(padded), jnp.asarray([33]), table, ok, ov,
        slot_ids=slot)
    np.testing.assert_allclose(ring[:, :W], np.asarray(ok.state)[:, 1, :W],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(cv.state)[:, 1, :W],
                               np.asarray(ov.state)[:, 1, :W], atol=1e-5)


def test_the_selection_told_to_the_reference_is_its_own(params):
    """check_sparse's `followed`: the program's index scores are the
    reference's to rounding, every choice is the top-k of the program's own
    scores, and at float32 the two selections agree cell for cell, so the
    followed reading is the unfollowed one."""
    heard = check_sparse.variants(family)["followed"]
    del family.SELECTIONS[:]
    seen: dict = {}

    def told(params_, hf, ids, **kw):
        jax.effects_barrier()
        got, picked = check_sparse.stitched(family.SELECTIONS, N_F, TOTAL)
        out = reference.forward(params_, hf, ids, follow_cells=picked,
                                observe=seen, **kw)
        seen["verdict"] = check_sparse.selection_verdict(
            got, picked, seen["index_scores"], K)
        return out

    followed = correctness.check(heard, CASE.cfg, params, HF, SPEC, 3, PAGE,
                                 check_limits.like(reference, told))
    own = correctness.check(family, CASE.cfg, params, HF, SPEC, 3, PAGE,
                            reference)
    assert followed["ok"] and own["ok"]
    verdict = seen["verdict"]
    assert verdict["choice_is_own_topk"], verdict
    assert verdict["index_rel_rms_err"] < 1e-5, verdict
    assert verdict["disagreeing_cells_max"] == 0, verdict
    assert abs(followed["max_rel_rms_err"] - own["max_rel_rms_err"]) < 1e-6
    # every call of the sequence was heard, a full layer at a time
    assert len(family.SELECTIONS) == N_F * (1 + 2 + 8)
    # and a selection that is NOT the program's is seen by the reference
    wrong = np.tril(np.ones((N_F, TOTAL, TOTAL), bool))
    want, _ = reference.forward(params, HF, suite.ids(CASE, TOTAL, 5))
    other, _ = reference.forward(params, HF, suite.ids(CASE, TOTAL, 5),
                                 follow_cells=wrong)
    assert np.abs(np.asarray(want) - np.asarray(other))[K + 4:].max() > 1e-4


# --- what is refused -------------------------------------------------------------

def test_a_checkpoint_an_int8_pool_weights_and_adapters_are_refused():
    with pytest.raises(NotImplementedError, match="dots3_note checkpoint"):
        weights._param_builders(CASE.cfg)
    with pytest.raises(NotImplementedError, match="int8 latent page pool "
                       "with index keys"):
        family.init_kv_pages(CASE.cfg, 4, PAGE, quantized=True)
    with pytest.raises(NotImplementedError, match="int8 weights"):
        family.FAMILY.refuse(int8_weights=True)
    with pytest.raises(NotImplementedError, match="adapter pools"):
        family.FAMILY.refuse(lora=True)
    assert not hasattr(family, "verify_step_paged")
    # the refusal of a low-rank query names the families that serve one
    with pytest.raises(NotImplementedError, match="dots3_note"):
        deepseek_v3.DeepseekV3Config.from_hf_config({"q_lora_rank": 1536})


# --- the catalog's row -------------------------------------------------------

@pytest.fixture(scope="module")
def row():
    with open(ROW) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "dots3-note-prev":
                return entry["config"]
    pytest.skip("the catalog has no dots3-note-prev row here")


def test_the_catalog_row_gives_the_shapes_and_bytes_the_issue_counted(row):
    whole = config_from_hf(row)
    assert family_for(whole) is family and whole.dtype == jnp.bfloat16
    assert (whole.num_experts, whole.router_experts, whole.first_expert) == (
        256, 256, 0)
    assert whole.layer_types == (("full",) * 2 + (
        ("sliding",) * 3 + ("full",)) * 11)
    assert (whole.layers_of(family.FULL), whole.layers_of(family.SLIDING)
            ) == (13, 33)
    with open("benchmark/configs/dots3-note-prev-l5.json") as f:
        file = json.load(f)
    cut = ("num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size")
    assert {k: file[k] for k in row if k not in cut} == {
        k: v for k, v in row.items() if k not in cut}
    assert list(file["reduced"]) == list(cut)
    assert file["layer_types"] == row["layer_types"][:5]
    cfg = config_from_hf(file)
    assert cfg == dataclasses.replace(
        whole, num_layers=5, layer_types=whole.layer_types[:5],
        num_experts=32, vocab_size=19008)
    assert cfg.held_experts == (0, 32) and cfg.experts_per_token == 8
    assert [(kind, n) for _, kind, n in family.runs(cfg)] == [
        ("full_dense", 1), ("full_moe", 1), ("sliding_moe", 3)]
    assert (cfg.q_lora_scale, cfg.kv_lora_scale) == (5 ** 0.5, 10 ** 0.5)
    assert cfg.window.q_lora_scale == cfg.window.kv_lora_scale == 5 ** 0.5
    assert (cfg.index_topk, cfg.index_heads, cfg.index_head_dim,
            cfg.sliding_window, cfg.ring_cells) == (2048, 64, 128, 513, 640)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.rms_eps) == (8e7, 5e4,
                                                                 1e-5)
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert shapes["r0_wq_b"].shape == (1, 1024, 128 * 192)
    assert shapes["r1_wi_q"].shape == (1, 1024, 64 * 128)
    assert shapes["r1_w_gate"].shape == (1, 5120, 128)
    assert shapes["r2_wkv_a"].shape == (3, 5120, 1024 + 64)
    assert shapes["r2_wk_b"].shape == (3, 64, 1024, 192)
    assert shapes["r2_w_gate"].shape == (3, 5120, 64)
    assert shapes["r2_we_gate"].shape == (3, 32, 5120, 1536)
    assert shapes["r1_router"].shape == (1, 5120, 256)
    assert shapes["r0_wg"].shape == (1, 5120, 13824)
    assert "r2_wi_q" not in shapes
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    full = (5120 * 1024 + 1024 + 1024 * 128 * 192 + 5120 * 576 + 512
            + 2 * 128 * 512 * 128 + 5120 * 128 + 128 * 128 * 5120 + 5120)
    index = 1024 * 64 * 128 + 5120 * 128 + 2 * 128 + 5120 * 64
    sliding = (5120 * 1024 + 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024
               + 64 * 1024 * (192 + 128) + 5120 * 64 + 64 * 128 * 5120 + 5120)
    mixture = (32 + 1) * 3 * 5120 * 1536 + 5120 * 256 + 256 + 5120
    dense_ffn = 3 * 5120 * 13824 + 5120
    assert n == (2 * (full + index) + 3 * sliding + 4 * mixture + dense_ffn
                 + 2 * 19008 * 5120 + 5120)
    assert (round(full / 1e6, 2), round(index / 1e6, 2)) == (134.68, 9.37)
    assert round(sliding / 1e6, 2) == 90.84  # ISSUE 64 rounds to 90.83
    assert 8.07e9 < 2 * n < 8.27e9  # the issue's 8.17 GB +- 0.1
    assert family.kv_token_layer_bytes(cfg) == (512 + 128 + 128) * 2 == 1536
    assert family.state_slot_bytes(cfg) == 3 * 640 * (1024 + 128) * 2
    ck, cv = jax.eval_shape(lambda: family.init_kv_pages(cfg, 2200, 128,
                                                         num_slots=16))
    assert ck.pages.shape == (2, 2200, 128, 512)
    assert cv.pages.shape == (2, 2200, 128, 256)
    assert ck.state.shape == (3, 17, 640, 1024)
    assert cv.state.shape == (3, 17, 640, 128)


def test_every_other_class_refuses_the_catalog_row(row):
    """The row read as another family's `model_type` is refused by the keys
    it states, not served as that model."""
    for module in FAMILIES:
        if module is family:
            continue
        with pytest.raises((ValueError, NotImplementedError, KeyError)):
            config_from_hf({**row,
                            "model_type": module.FAMILY.model_types[0]})
    assert deepseek_v3.DeepseekV3Config.index_topk == 0
    assert deepseek_v3.DeepseekV3Config.attn_gate is False
