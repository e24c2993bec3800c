"""The sampler's top-64 in two stages equals `jax.lax.top_k`, bit for bit.

`ops/sampling._top_k_by_groups` takes each group's maximum, picks the 64
groups with the largest maxima and sorts only their members (PERF.md §6,
PR 35). Every caller wants exactly what the one-stage call returned: the same
values and the same indices under ties, whatever a grammar's mask leaves of a
row. These cases hold it there, on vocabularies that keep the one-stage call
(96, 1,000, 8,191: groups would not halve the elements sorted), on one that
is no multiple of the group width (the padded tail), and on the three the
benchmark serves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops import sampling
from llmlb_tpu.ops.sampling import (TOPK_PREFILTER, _top_k_by_groups,
                                    sample_tokens, selection_plan)

VOCABS = (96, 1_000, 8_191, 32_000, 128_256, 151_936)
ROWS = 5
BLOCKED = -1e30  # llmlb_tpu/structured's bias of a blocked column


def _random(rng, v):
    return rng.normal(0.0, 2.0, size=(ROWS, v)).astype(np.float32)


def _boundary_ties(rng, v):
    """A handful of distinct values: thousands of columns tie at the 64th
    place, and only the lowest indices among them may be returned."""
    return np.round(_random(rng, v) * 1.5).astype(np.float32) / 2


def _few_allowed(rng, v):
    """A mask that leaves fewer than 64 columns: the rest of the 64 are
    blocked columns, tied at -1e30 (a blocked logit's own value is lost in
    float32), lowest indices first."""
    logits = _random(rng, v)
    for row in logits:
        keep = rng.choice(v, size=rng.integers(1, TOPK_PREFILTER), replace=False)
        bias = np.full(v, BLOCKED, np.float32)
        bias[keep] = 0.0
        row += bias
    return logits


def _allowed_outside_the_top(rng, v):
    """The allowed set lies wholly outside the unmasked top 64 (and, for a
    grouped row, mostly in groups whose unmasked maxima are small)."""
    logits = _random(rng, v)
    for row in logits:
        order = np.argsort(-row, kind="stable")
        low = order[max(v // 2, TOPK_PREFILTER):]
        allowed = rng.choice(low, size=min(80, v // 4), replace=False)
        assert not np.isin(allowed, order[:TOPK_PREFILTER]).any()
        bias = np.full(v, BLOCKED, np.float32)
        bias[allowed] = 0.0
        row += bias
    return logits


CASES = {"random": _random, "boundary-ties": _boundary_ties,
         "few-allowed": _few_allowed,
         "allowed-outside-top": _allowed_outside_the_top}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("vocab", VOCABS)
def test_top_k_by_groups_is_lax_top_k(vocab, case):
    rng = np.random.default_rng(vocab + len(case))
    logits = jnp.asarray(CASES[case](rng, vocab))
    k = min(TOPK_PREFILTER, vocab)
    want_values, want_ids = jax.lax.top_k(logits, k)
    values, ids = jax.jit(_top_k_by_groups, static_argnums=1)(logits, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(values), np.asarray(want_values))
    assert ids.dtype == want_ids.dtype and values.dtype == want_values.dtype


@pytest.mark.parametrize("vocab", VOCABS)
def test_selection_plan_follows_the_shape(vocab):
    """Two stages only where they sort fewer elements than the row holds;
    the reading /api/health serves says which."""
    plan = selection_plan(vocab)
    assert plan["vocab"] == vocab
    if plan["group"]:
        groups = -(-vocab // plan["group"])
        assert groups > TOPK_PREFILTER
        assert plan["sorted_per_row"] == groups + TOPK_PREFILTER * plan["group"]
        assert 2 * plan["sorted_per_row"] <= vocab
    else:
        assert plan["sorted_per_row"] == vocab
    assert bool(plan["group"]) == (vocab >= 32_000)


def test_a_row_that_is_no_multiple_of_the_group_pads_its_tail():
    """The padded tail (-inf at the highest indices) never reaches the
    result, even where a row's real -inf columns tie with it."""
    vocab = 3 * TOPK_PREFILTER * sampling._GROUP + 77
    rng = np.random.default_rng(7)
    logits = _random(rng, vocab)
    logits[1, 100:] = -np.inf  # ties with the padding: real columns first
    logits[2, : vocab - 40] = -np.inf  # the last, padded group is picked
    assert selection_plan(vocab)["group"]
    want_values, want_ids = jax.lax.top_k(jnp.asarray(logits), TOPK_PREFILTER)
    values, ids = _top_k_by_groups(jnp.asarray(logits), TOPK_PREFILTER)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(values), np.asarray(want_values))
    assert int(ids.max()) < vocab


def test_sample_tokens_returns_the_parents_ids_at_151936_columns(monkeypatch):
    """A mixed batch under one key — greedy, temperature, top-p, top-k and
    seeded rows, two of them under a grammar's mask — samples the ids that
    the code before PR 35 sampled: that code is `sample_tokens` with the
    helper put back to the one line it replaced."""
    vocab, rows = 151_936, 12
    rng = np.random.default_rng(35)
    logits = jnp.asarray(_boundary_ties(rng, vocab)[:1].repeat(rows, 0)
                         + rng.normal(0, 1, (rows, vocab)).astype(np.float32))
    temps = jnp.asarray([0, 0, 1, 1, .7, .7, 1.3, 1, 1, 0, 1, 2], jnp.float32)
    top_ps = jnp.asarray([1, 1, 1, .9, .5, 1, .95, 1, .8, 1, 1, .3], jnp.float32)
    top_ks = jnp.asarray([0, 5, 0, 0, 0, 10, 40, 100, 1, 0, 0, 0], jnp.int32)
    seeds = jnp.asarray([-1, -1, -1, -1, 11, -1, 12, -1, -1, 13, 14, -1],
                        jnp.int32)
    steps = jnp.arange(rows, dtype=jnp.int32)
    bias = np.zeros((rows, vocab), np.float32)
    for r in (1, 10):  # the allowed set far from the unmasked top
        bias[r] = BLOCKED
        bias[r, rng.choice(vocab, size=30, replace=False)] = 0.0
    args = (logits, jax.random.PRNGKey(2035), temps, top_ps, top_ks,
            jnp.asarray(bias), seeds, steps)
    # a lambda each: two jits of one function would share its traced program
    got = np.asarray(jax.jit(lambda *a: sample_tokens(*a))(*args))
    monkeypatch.setattr(sampling, "_top_k_by_groups", jax.lax.top_k)
    want = np.asarray(jax.jit(lambda *a: sample_tokens(*a))(*args))
    np.testing.assert_array_equal(got, want)
    assert len(set(got.tolist())) > rows // 2  # the rows did sample apart


def test_an_engine_reports_its_selection_plan():
    """/api/health .metrics.sampling is the plan of the engine's own
    vocabulary, set once where the engine is made."""
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore

    cfg = get_preset("debug-tiny")
    core = EngineCore(cfg, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), kv_page_size=8, seed=0,
                      prefix_cache=False)
    reading = core.metrics.summary()["sampling"]
    assert reading == selection_plan(cfg.vocab_size)
    assert reading == {"vocab": cfg.vocab_size, "group": 0,
                       "sorted_per_row": cfg.vocab_size}
