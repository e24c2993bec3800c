"""Plain reference of the SDAR-MoE block (`model_type` `sdar_moe`,
SDAR-30B-A3B-Chat: a Qwen3-MoE-shaped decoder that generates by diffusion
over blocks), in float32 with `jax.default_matmul_precision("highest")`: no
cache, no kernels, no batching, one whole-sequence pass.

The layer, as published: pre-norm RMSNorm; grouped-query attention with an
RMS norm of each query and key head BEFORE RoPE (one weight vector of
head_dim for all heads), rotate-half RoPE over the whole head at the
absolute position; scores under the BLOCK-CAUSAL mask — position j is
visible to i iff j // B <= i // B, causal across blocks of B, bidirectional
inside one; then a mixture of experts on the post-attention norm: the
router's softmax over all experts in float32, the k largest, their
probabilities renormalised over the k (`norm_topk_prob`), SwiGLU experts of
`moe_intermediate_size`, no shared expert and no dense layer. Logits are
UNSHIFTED: position i's logits predict position i's own token.

`generate` is the published procedure of the SDAR repository's
`generate.py`, as the issue of PR 34 wrote it down, run the slow way: every
pass calls `forward` on the whole sequence (committed tokens, then the open
block with `mask_token_id` where still masked).

Departures from the published model: weights are the program's random bf16
values upcast to float32; the block length, the number of denoising steps,
the remasking strategy, the confidence threshold and the mask id are not in
`config.json` and are read from the configuration file's `assumed` group;
`forward(..., follow=)` mixes the experts the PROGRAM chose (weights from
this reference's own router probabilities), for the reason
benchmark/reference/moe.py gives; the confidence of a greedy pick
(temperature 0) is its probability at temperature 1.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense

F32 = jnp.float32
FOLLOWS = "routing"

GENERATION_DEFAULTS = {"block_length": 4, "denoising_steps": 4,
                       "remasking_strategy": "low_confidence_dynamic",
                       "confidence_threshold": 0.9, "mask_token_id": 151669}


def generation(hf: dict, **given) -> dict:
    """The generation parameters of a configuration: what the caller gives,
    else the file's own key, else its `assumed` group, else the published
    defaults above."""
    assumed = hf.get("assumed") or {}
    return {k: (given[k] if given.get(k) is not None
                else hf.get(k, assumed.get(k, default)))
            for k, default in GENERATION_DEFAULTS.items()}


def block_mask(t: int, block: int):
    """[T, T] bool: query i (rows) sees key j iff j // block <= i // block."""
    pos = jnp.arange(t)
    return (pos[None, :] // block) <= (pos[:, None] // block)


def attention(h, wq, wk, wv, wo, q_norm, k_norm, *, heads, kv_heads,
              head_dim, theta, eps, block):
    t = h.shape[0]
    q = (h @ wq.astype(F32)).reshape(t, heads, head_dim)
    k = (h @ wk.astype(F32)).reshape(t, kv_heads, head_dim)
    v = (h @ wv.astype(F32)).reshape(t, kv_heads, head_dim)
    q = dense.rope(dense.rms_norm(q, q_norm, eps), theta)
    k = dense.rope(dense.rms_norm(k, k_norm, eps), theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
    scores = jnp.where(block_mask(t, block)[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * head_dim) @ wo.astype(F32)


def mixture(h, l, router, we_gate, we_up, we_down, chosen, *, top_k):
    """h [T, E]; `chosen` [T, k] the experts to mix, or None for the k most
    probable. Returns the mixture's output and the router's logits [T, X]."""
    logits = h @ router.astype(F32)  # [T, X]
    probs = jax.nn.softmax(logits, axis=-1)
    if chosen is None:
        chosen = jax.lax.top_k(probs, top_k)[1]
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(router.shape[-1]):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [T]
        out = out + w_e[:, None] * dense.swiglu(
            h, we_gate[l, e], we_up[l, e], we_down[l, e])
    return out, logits


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "eps", "top_k", "block"))
def layer(x, l, wq, wk, wv, wo, q_norm, k_norm, router, we_gate, we_up,
          we_down, ln_attn, ln_mlp, chosen=None, *, heads, kv_heads,
          head_dim, theta, eps, top_k, block):
    with jax.default_matmul_precision("highest"):
        x = x + attention(dense.rms_norm(x, ln_attn[l], eps), wq[l], wk[l],
                          wv[l], wo[l], q_norm[l], k_norm[l], heads=heads,
                          kv_heads=kv_heads, head_dim=head_dim, theta=theta,
                          eps=eps, block=block)
        out, logits = mixture(dense.rms_norm(x, ln_mlp[l], eps), l, router[l],
                              we_gate, we_up, we_down, chosen, top_k=top_k)
        return x + out, logits


def forward(params: dict, hf: dict, ids, follow=None, block_length=None):
    """Logits [T, V] of the token sequence `ids` [T] under the block mask
    (position i's logits are for position i's own token), and the router's
    logits [L, T, X]. `follow` [L, T, k]: the experts to mix in place of
    the router's own top-k."""
    d = dense.dims(hf)
    block = int(generation(hf, block_length=block_length)["block_length"])
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    router_logits = []
    for l in range(hf["num_hidden_layers"]):
        x, logits = layer(
            x, l, params["wq"], params["wk"], params["wv"], params["wo"],
            params["q_norm"], params["k_norm"], params["router"],
            params["we_gate"], params["we_up"], params["we_down"],
            params["ln_attn"], params["ln_mlp"],
            None if follow is None else jnp.asarray(follow[l], jnp.int32),
            **d, top_k=hf["num_experts_per_tok"], block=block)
        router_logits.append(logits)
    return (dense.unembed(x, params["ln_final"], params["lm_head"],
                          eps=d["eps"]),
            jnp.stack(router_logits))


def unmask_choice(conf, masked, *, per_pass: int, dynamic: bool,
                  threshold: float):
    """Which masked positions of one block a pass unmasks. `conf` [B] the
    probability of each position's sampled id, `masked` [B] bool. Static:
    the `per_pass` most probable masked ones. Dynamic: every masked one
    above `threshold`, and where fewer than `per_pass` are, the `per_pass`
    most probable. Equal probabilities go to the earlier position."""
    order = sorted((i for i in range(len(conf)) if masked[i]),
                   key=lambda i: (-conf[i], i))
    chosen = set(order[:per_pass])
    if dynamic:
        chosen |= {i for i in order if conf[i] > threshold}
    return chosen


def generate(params: dict, hf: dict, prompt_ids, n: int, *,
             block_length=None, denoising_steps=None,
             remasking_strategy=None, confidence_threshold=None,
             mask_token_id=None, temperature: float = 0.0, eos_id: int = -1,
             sample=None, passes=None) -> list[int]:
    """`n` tokens after `prompt_ids` (fewer where `eos_id` appears: it ends
    the output and is not part of it). The prompt's whole blocks stand as
    committed; its remainder opens the first block as given tokens. A pass
    gives every position of the open block its logits; a block that entered
    its pass with no mask is committed, else the pass unmasks
    (`unmask_choice`). `sample(logits [V], position, masks_left)` picks an
    id (default: the largest logit); its confidence is its probability at
    `temperature` (at 1 where that is 0). `passes`, a list, is given one
    entry per pass: (block start, masks before, masks after)."""
    g = generation(hf, block_length=block_length,
                   denoising_steps=denoising_steps,
                   remasking_strategy=remasking_strategy,
                   confidence_threshold=confidence_threshold,
                   mask_token_id=mask_token_id)
    b, mask_id = int(g["block_length"]), int(g["mask_token_id"])
    per_pass = b // int(g["denoising_steps"])
    dynamic = g["remasking_strategy"] == "low_confidence_dynamic"
    prompt = [int(t) for t in prompt_ids]
    start = len(prompt) - len(prompt) % b
    committed, given = prompt[:start], prompt[start:]
    out: list[int] = []
    while len(out) < n:
        block = given + [mask_id] * (b - len(given))
        masked = [i >= len(given) for i in range(b)]
        while any(masked):
            logits = np.asarray(
                forward(params, hf, np.asarray(committed + block, np.int32),
                        block_length=b)[0], np.float64)[len(committed):]
            left = sum(masked)
            conf, ids = [0.0] * b, list(block)
            for i in range(b):
                if not masked[i]:
                    continue
                row = logits[i]
                ids[i] = int(np.argmax(row) if sample is None
                             else sample(row, len(committed) + i, left))
                scaled = row / (temperature if temperature > 0 else 1.0)
                scaled = scaled - scaled.max()
                conf[i] = float(np.exp(scaled[ids[i]]) / np.exp(scaled).sum())
            for i in unmask_choice(conf, masked, per_pass=per_pass,
                                   dynamic=dynamic,
                                   threshold=float(g["confidence_threshold"])):
                block[i], masked[i] = ids[i], False
            if passes is not None:
                passes.append((len(committed), left, sum(masked)))
        # the committing pass: the complete block's K and V are the cache's
        if passes is not None:
            passes.append((len(committed), 0, 0))
        committed += block
        for tok in block[len(given):]:
            if tok == eos_id or len(out) >= n:
                return out
            out.append(tok)
        given = []
    return out
