"""Token sampling on-device: greedy / temperature / top-k / top-p in one jittable op.

All sampling parameters are traced arrays (per-request, shape [B]) so one compiled
decode step serves every request mix — no recompile when a user changes
temperature. Top-p runs inside a static top-K=64 prefilter: a full 128k-vocab sort
per step would thrash HBM bandwidth for no quality gain (p-mass beyond the top 64
logits is negligible at serving temperatures).

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
    top_logits, top_ids = jax.lax.top_k(logits, k)  # [B, k] sorted desc

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
    unmasks by (engine/scheduler._build_block_many): one max and one sum
    over the vocabulary beside the sampler's own top-k."""
    scaled = logits / jnp.where(temperature > 0.0, temperature, 1.0)[:, None]
    picked = jnp.take_along_axis(scaled, ids[:, None], axis=-1)[:, 0]
    return jnp.exp(picked - jax.nn.logsumexp(scaled, axis=-1))
