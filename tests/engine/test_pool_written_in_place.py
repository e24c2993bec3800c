"""Prefill, extend and verify write the page pool where it lies.

The three programs carry the stacked pool through their layer scan and
scatter into it at (layer, page, offset) (models/llama._scan_groups). A pool
that already holds other rows' KV must come back with every cell outside the
call's own pages bit for bit as it was — in every layer, for a stack of one
group (llama, mixtral; bf16-style and int8 pools) and of two (the latent
mixture: a dense layer, then expert layers, one carry through both scans) —
and the call's logits must not depend on what those other cells hold: with
them poisoned the logits are the same to the last bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import deepseek_v3, llama, mixtral

DIMS = dict(vocab_size=96, hidden_size=64, intermediate_size=80,
            num_layers=3, num_heads=4, num_kv_heads=2, dtype=jnp.float32)
FAMILIES = {
    "llama-f32": (llama, llama.LlamaConfig(**DIMS), False),
    "llama-int8": (llama, llama.LlamaConfig(**DIMS), True),
    "mixtral-f32": (mixtral, mixtral.MixtralConfig(
        **DIMS, num_experts=4, experts_per_token=2), False),
    "two-groups-f32": (deepseek_v3, get_preset("debug-mla-tiny"), False),
}
PAGES, PAGE_SIZE, CHUNK = 12, 8, 8
# two rows; row 1's table ends in the trash page, where its padding lands
TABLES = np.array([[3, 7, 5], [9, 2, 0]], np.int32)
OWN = sorted({0, *TABLES.ravel().tolist()})
OTHERS = [p for p in range(PAGES) if p not in OWN]
PROMPT_LENS = np.array([8, 5], np.int32)
CHUNK_LENS = np.array([8, 3], np.int32)


def _filled(pools, seed, others=None):
    """The pools with seeded content in every cell, as if other rows had
    been served; `others` replaces what pages outside the call's hold."""
    leaves, treedef = jax.tree.flatten(pools)
    rng = np.random.default_rng(seed)
    out = []
    for leaf in leaves:
        if leaf.dtype == jnp.int8:
            x = rng.integers(-100, 100, leaf.shape).astype(np.int8)
        else:
            x = rng.normal(size=leaf.shape).astype(leaf.dtype)
        if others is not None:
            x[:, OTHERS] = 127 if leaf.dtype == jnp.int8 else others
        out.append(jnp.asarray(x))
    return jax.tree.unflatten(treedef, out)


def _bits(tree, pages):
    return [np.asarray(leaf)[:, pages].tobytes()
            for leaf in jax.tree.leaves(tree)]


def _call(family, cfg, entry, params, pools):
    ids = jax.random.randint(jax.random.PRNGKey(11), (2, CHUNK), 0,
                             cfg.vocab_size)
    tables = jnp.asarray(TABLES)
    if entry == "prefill_into_pages":
        out = family.prefill_into_pages(params, cfg, ids,
                                        jnp.asarray(PROMPT_LENS), tables,
                                        *pools)
    else:
        kw = {"window": 2 * PAGE_SIZE} if entry == "verify_step_paged" else {}
        out = getattr(family, entry)(
            params, cfg, ids, jnp.asarray(CHUNK_LENS),
            jnp.asarray(PROMPT_LENS), tables, *pools, **kw)
    return out[0], out[1:3]


@pytest.mark.parametrize("entry", ["prefill_into_pages",
                                   "prefill_extend_pages",
                                   "verify_step_paged"])
@pytest.mark.parametrize("case", sorted(FAMILIES))
def test_other_rows_cells_come_back_bit_identical(case, entry):
    family, cfg, quantized = FAMILIES[case]
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    empty = family.init_kv_pages(cfg, PAGES, PAGE_SIZE, quantized=quantized)

    before = _bits(_filled(empty, 5), OTHERS)  # the call donates its pools
    logits, pools = _call(family, cfg, entry, params, _filled(empty, 5))
    assert _bits(pools, OTHERS) == before
    assert np.isfinite(np.asarray(logits)).all()
    # and every layer of the call's own pages was written: none of them is
    # as it was (the two groups of a mixed stack share one carry)
    own = [p for p in OWN if p]
    for was, now in zip(jax.tree.leaves(_filled(empty, 5)),
                        jax.tree.leaves(pools)):
        changed = np.asarray(was)[:, own] != np.asarray(now)[:, own]
        assert changed.reshape(changed.shape[0], -1).any(axis=1).all()

    poisoned, _ = _call(family, cfg, entry, params,
                        _filled(empty, 5, others=np.nan))
    assert np.asarray(poisoned).tobytes() == np.asarray(logits).tobytes()
