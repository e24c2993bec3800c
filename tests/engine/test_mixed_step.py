"""The mixed step is prefill-then-decode, done in one pass over the weights.

`models/llama._mixed_paged_impl` runs the decode rows' one token each and ONE
arrival's whole prompt through every layer together: one product over the
B + T tokens for the norms, the projections, the feed-forward and the head,
the attention core alone split by token (`docs/scheduling.md` "An arrival
rides a burst"). A family joins by exporting `mixed_step_paged` and setting
`Family.mixed_step` once THIS comparison passes for it: against its own
`prefill_into_pages` followed by `decode_step_paged`, on the CPU in float32,
the rows' logits, the prompt's last-position logits and every pool cell
either path wrote agree under 1e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import FAMILIES, family_for
from tests.support import identity_kv_pages

PS, CAPACITY, ROWS, WIDTH = 8, 64, 4, 16
TOLERANCE = 1e-5
# a debug configuration per family whose record offers the entry point
PRESETS = {"llama": "debug-tiny", "mixtral": "debug-moe-tiny"}


def test_the_families_that_offer_a_mixed_step_are_the_ones_held_here():
    assert sorted(PRESETS) == sorted(
        m.FAMILY.name for m in FAMILIES if m.FAMILY.mixed_step)


def _house(family, cfg, lens, seed: int, **pool):
    """Rows with `lens` tokens in their pages (0: the row holds nothing),
    prefilled one by one; returns (params, the rows' next tokens, pool_k,
    pool_v, tables)."""
    params = family.init_params(cfg, jax.random.PRNGKey(seed))
    ck, cv, tables = identity_kv_pages(family, cfg, ROWS, CAPACITY, PS, **pool)
    rng = np.random.default_rng(seed)
    for row, n in enumerate(lens):
        if n:
            ids = rng.integers(1, cfg.vocab_size, (1, 32)).astype(np.int32)
            _, ck, cv = family.prefill_into_pages(
                params, cfg, jnp.asarray(ids), jnp.asarray([n], jnp.int32),
                tables[row][None], ck, cv)
    last = jnp.asarray(rng.integers(1, cfg.vocab_size, (ROWS,)), jnp.int32)
    return params, last, ck, cv, tables


def _values(pool):
    """A pool's arrays as float32 (an int8 pool's values and its scales)."""
    return [np.asarray(leaf, np.float32) for leaf in jax.tree.leaves(pool)]


# (the rows' lengths with 0 where the arrival goes, the arrival's row, its
# prompt's tokens, the rows that are live)
CASES = {
    # a prompt shorter than the width, inside its first two pages
    "shorter": ((5, 0, 19, 9), 1, 11, (True, True, True, True)),
    # exactly the width: no padding, its last token on a page's last cell
    "whole_width": ((7, 12, 0, 3), 2, WIDTH, (True, True, True, True)),
    # a row that is not live (freed: its table row still set, its length
    # stale) beside the arrival
    "a_row_not_live": ((6, 23, 14, 0), 3, 9, (True, False, True, True)),
    # the arrival's page boundary inside the prompt: 8 cells a page, so a
    # prompt of 13 ends in its second page and the padding spills on
    "page_boundary": ((0, 8, 16, 31), 0, 13, (True, True, True, True)),
}


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16_pool", "int8_pool"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", sorted(PRESETS))
def test_the_mixed_step_is_prefill_then_decode(name, case, quantized):
    cfg = get_preset(PRESETS[name])
    family = family_for(cfg)
    lens, row, n, live = CASES[case]
    seed = sorted(CASES).index(case) + 7
    pool = {"quantized": True} if quantized else {}
    params, last, ck, cv, tables = _house(family, cfg, lens, seed, **pool)
    rng = np.random.default_rng(seed + 100)
    prompt = np.zeros((1, WIDTH), np.int32)
    prompt[0, :n] = rng.integers(1, cfg.vocab_size, (n,))
    prompt_len = jnp.asarray([n], jnp.int32)
    live = np.asarray(live)
    seq_lens = jnp.asarray(lens, jnp.int32)

    # today's two programs: the prompt alone, then the rows' step, with the
    # arrival's row parked where a prefilling row stands
    copy = jax.tree.map(jnp.copy, (ck, cv))
    want_prompt, rk, rv = family.prefill_into_pages(
        params, cfg, jnp.asarray(prompt), prompt_len, tables[row][None], *copy)
    parked = seq_lens.at[row].set(CAPACITY - 1)
    alone = live.copy()
    alone[row] = False
    want_rows, rk, rv = family.decode_step_paged(
        params, cfg, last, parked, rk, rv, tables, window=CAPACITY,
        live=jnp.asarray(alone))

    got, mk, mv = family.mixed_step_paged(
        params, cfg, last, seq_lens, ck, cv, tables, jnp.asarray(prompt),
        prompt_len, jnp.asarray(row, jnp.int32), window=CAPACITY,
        live=jnp.asarray(live))

    got, want_rows = np.asarray(got), np.asarray(want_rows)
    decoding = alone & (np.asarray(lens) > 0)
    assert np.abs(got[decoding] - want_rows[decoding]).max() < TOLERANCE
    assert np.abs(got[row] - np.asarray(want_prompt)[0]).max() < TOLERANCE
    # every cell either path wrote: the prompt's n cells and its padding's,
    # each decoding row's one, the parked rows' last cells, the trash page
    for mixed, ref in zip(_values((mk, mv)), _values((rk, rv))):
        assert mixed.shape == ref.shape
        assert np.abs(mixed - ref).max() < TOLERANCE


def test_a_family_with_a_state_per_slot_has_no_mixed_step_yet():
    """The shared body refuses a group that mixes through a state of its
    own rather than computing something else for it."""
    from llmlb_tpu.models import llama, nemotron_h

    cfg = get_preset("debug-nemotron-h-tiny")
    assert not nemotron_h.FAMILY.mixed_step
    groups = nemotron_h._groups(cfg) if hasattr(nemotron_h, "_groups") else None
    if groups is None:
        pytest.skip("the family builds its groups another way")
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(0))
    ck, cv = nemotron_h.init_kv_pages(cfg, 9, PS, num_slots=ROWS)
    with pytest.raises(NotImplementedError):
        llama._mixed_paged_impl(
            params, cfg, jnp.zeros((ROWS,), jnp.int32),
            jnp.zeros((ROWS,), jnp.int32), ck, cv,
            jnp.zeros((ROWS, 2), jnp.int32), jnp.zeros((1, WIDTH), jnp.int32),
            jnp.asarray([1], jnp.int32), jnp.asarray(0, jnp.int32),
            groups=groups)


def test_the_chip_side_check_runs_and_reads_rounding_in_float32():
    """scripts/mixed_step_check.py stands in for the launcher's `correct`
    (a), which does not reach the mixed program: on the CPU in float32 its
    readings are rounding, seeds past 2**31 included."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parents[2] / "scripts/mixed_step_check.py"
    spec = importlib.util.spec_from_file_location("mixed_step_check", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert len(script.SEEDS) == 14 and min(script.SEEDS) > 2**31
    cfg = get_preset("debug-tiny")
    family = family_for(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    reading = script.check(family, cfg, params, script.SEEDS[0], rows=8,
                           width=32, page_size=16)
    assert reading["rows_decoding"] == 5  # the arrival's and two not live
    assert 16 <= reading["prompt_tokens"] <= 32
    assert max(reading["rows"], reading["prompt"], reading["pool"]) < 1e-5
