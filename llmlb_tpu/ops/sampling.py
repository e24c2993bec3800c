"""Token sampling on-device: greedy / temperature / top-k / top-p in one jittable op.

All sampling parameters are traced arrays (per-request, shape [B]) so one compiled
decode step serves every request mix — no recompile when a user changes
temperature. Top-p runs inside a static top-K=64 prefilter (p-mass beyond the top
64 logits is negligible at serving temperatures), and the prefilter itself sorts
no vocabulary: one `lax.top_k` over `[B, V]` costs a v5e 0.2-0.26 ns an element,
a fifth of a block pass at 151,936 columns (PERF.md §6, PR 35). So
`_top_k_by_groups` takes each contiguous group's maximum in one pass over the
logits, picks the 64 groups with the largest maxima, and takes the top 64 of
those groups' members: `G + 64·g` elements sorted a row instead of V
(`selection_plan`), with `lax.top_k`'s own values and indices, bit for bit.

Why it is exact. `lax.top_k` orders by (value descending, index ascending).
Let x be one of the true top 64 in a group that was not picked: each of the 64
picked groups then holds its maximum m >= x, and where m == x at a lower index
(groups are contiguous, and the group stage breaks ties by lower index too), so
64 elements come before x — a contradiction. The picked groups are gathered in
ascending order, so candidate order is vocabulary order and the second
`lax.top_k` breaks ties as the one-stage call does.

Two per-request extensions ride the same traced-input discipline (no recompile
per request mix):

- `mask_bias` [B, V]: additive grammar-constraint bias (0 allowed / -1e30
  blocked, llmlb_tpu/structured). Applied to the FULL logits BEFORE the top-k
  prefilter and before the greedy argmax — an allowed set living entirely
  outside the unconstrained top-64 must still be sampleable, so masking after
  the prefilter would leave all-blocked rows.
- `seeds`/`steps` [B]: per-request deterministic sampling. Rows with
  seed >= 0 draw from fold_in(PRNGKey(seed), step) instead of the shared
  batch key, so a seeded request reproduces its token sequence regardless of
  which other requests share the batch. Rows with seed < 0 are bit-identical
  to the shared-key path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TOPK_PREFILTER = 64
# Columns in a group of the two-stage selection: one lane tile, so a group is
# one 512 B row of the gather. Wider groups sort more candidates and narrower
# ones leave half a tile empty (measured on the chip at 64-512, PERF.md §6).
_GROUP = 128


def selection_plan(vocab: int, k: int = TOPK_PREFILTER) -> dict[str, int]:
    """How `sample_tokens` takes its top `k` of a row of `vocab` columns, from
    the static shape alone: `group` columns a group (0: one `lax.top_k` over
    the row) and `sorted_per_row`, the elements the sorts see a row (`vocab`
    where one stage is kept). Two stages only where they sort at most half
    the row: under that the pass for the maxima and the gather buy nothing,
    and a row of fewer than `k` groups has no second stage at all. Served as
    /api/health .metrics.sampling."""
    k = min(k, vocab)
    by_groups = -(-vocab // _GROUP) + k * _GROUP
    if 2 * by_groups > vocab:
        return {"vocab": vocab, "group": 0, "sorted_per_row": vocab}
    return {"vocab": vocab, "group": _GROUP, "sorted_per_row": by_groups}


def _top_k_by_groups(logits: jnp.ndarray, k: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`jax.lax.top_k(logits, k)` — the same values and indices, ties
    included — without sorting the row (module docstring has the proof)."""
    b, v = logits.shape
    g = selection_plan(v, k)["group"]
    if not g:
        return jax.lax.top_k(logits, k)
    groups = -(-v // g)
    if groups * g != v:
        # the padding holds the highest indices, so it loses every tie
        logits = jnp.pad(logits, ((0, 0), (0, groups * g - v)),
                         constant_values=-jnp.inf)
    # rows of one lane tile: the maxima reduce over lanes where the head's
    # matmul left the logits, and the gather below takes whole rows (a
    # [B, G, g] view made XLA lay the logits out twice more, PERF.md §6)
    flat = logits.reshape(b * groups, g)
    _, picked = jax.lax.top_k(flat.max(axis=-1).reshape(b, groups), k)
    picked = jnp.sort(picked, axis=-1)  # candidate order = vocabulary order
    rows = jnp.arange(b, dtype=picked.dtype)[:, None] * groups + picked
    candidates = flat.at[rows.reshape(-1)].get(
        mode="promise_in_bounds", indices_are_sorted=True, unique_indices=True)
    values, at = jax.lax.top_k(candidates.reshape(b, k * g), k)
    ids = jnp.take_along_axis(picked, at // g, axis=1) * g + at % g
    return values, ids


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] float32; 0 => greedy
    top_p: jnp.ndarray,  # [B] float32 in (0, 1]
    top_k: jnp.ndarray,  # [B] int32; 0 => disabled. NOTE: the candidate pool is
    # always capped at TOPK_PREFILTER=64, so top_k values above 64 (and "disabled")
    # clamp to 64 — an intentional serving trade-off, see module docstring.
    mask_bias: jnp.ndarray | None = None,  # [B, V] float32 additive, or None
    seeds: jnp.ndarray | None = None,  # [B] int32; < 0 => shared batch key
    steps: jnp.ndarray | None = None,  # [B] int32 position for the seed fold
) -> jnp.ndarray:
    """Returns sampled token ids [B] int32."""
    if mask_bias is not None:
        # BEFORE argmax and BEFORE the prefilter: greedy and stochastic paths
        # both see only allowed tokens.
        logits = logits + mask_bias
    b, v = logits.shape
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    k = min(TOPK_PREFILTER, v)
    top_logits, top_ids = _top_k_by_groups(logits, k)  # [B, k] sorted desc

    # top-k restriction (within the prefilter window)
    ranks = jnp.arange(k, dtype=jnp.int32)[None, :]
    eff_top_k = jnp.where(top_k <= 0, k, jnp.minimum(top_k, k))[:, None]
    top_logits = jnp.where(ranks < eff_top_k, top_logits, -jnp.inf)

    # temperature
    safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = top_logits / safe_temp

    # top-p (nucleus) over the sorted window
    probs = jax.nn.softmax(scaled, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    # keep tokens whose cumulative mass *before* them is < top_p (always keep rank 0)
    keep = (cumulative - probs) < top_p[:, None]
    scaled = jnp.where(keep, scaled, -jnp.inf)

    sampled_idx = jax.random.categorical(key, scaled, axis=-1)  # [B] in [0, k)
    if seeds is not None:
        step_idx = (steps if steps is not None
                    else jnp.zeros_like(seeds)).astype(jnp.uint32)
        def _row_key(seed, step):
            return jax.random.fold_in(
                jax.random.PRNGKey(jnp.maximum(seed, 0)), step
            )
        row_keys = jax.vmap(_row_key)(seeds, step_idx)
        seeded_idx = jax.vmap(jax.random.categorical)(row_keys, scaled)
        sampled_idx = jnp.where(seeds >= 0, seeded_idx, sampled_idx)
    sampled_ids = jnp.take_along_axis(top_ids, sampled_idx[:, None], axis=-1)[:, 0]

    return jnp.where(temperature <= 0.0, greedy_ids, sampled_ids.astype(jnp.int32))


def token_probability(
    logits: jnp.ndarray,  # [B, V] float32
    ids: jnp.ndarray,  # [B] int32
    temperature: jnp.ndarray,  # [B] float32; 0 => greedy
) -> jnp.ndarray:
    """The probability [B] f32 of `ids` under the softmax of the FULL
    logits at `temperature` (at 1 where it is 0: a greedy pick's confidence
    is its plain probability). What generation by diffusion over blocks
    unmasks by (engine/programs._build_block_many): one max and one sum
    over the vocabulary beside the sampler's own top-k."""
    scaled = logits / jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    picked = jnp.take_along_axis(scaled, ids[:, None], axis=-1)[:, 0]
    return jnp.exp(picked - jax.nn.logsumexp(scaled, axis=-1))
