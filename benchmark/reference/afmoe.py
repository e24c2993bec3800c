"""Plain reference of the AFMoE block (`model_type` `afmoe`:
arcee-ai/Trinity-Mini). RMSNorm has a learnt weight and eps `rms_norm_eps`.
Float32, `jax.default_matmul_precision("highest")`, whole-sequence masks: no
band, no pages, no cache, no kernels, no batching; a layer and an expert at
a time.

Embedding: x_0 = E[ids] x sqrt(hidden) (`mup_enabled`). Logits: norm(x_L) W_h,
untied, unscaled. Layer l (a norm on BOTH sides of each sub-layer):
a = norm_1(x); x <- x + norm_2(Attn_l(a)); m = norm_3(x); x <- x +
norm_4(FF_l(m)).

Attn_l: q = a W_q as H heads of D, k = a W_k, v = a W_v as K heads of D,
g = sigmoid(a W_g) [T, H x D]. q <- RMSNorm_q(q), k <- RMSNorm_k(k) per head
over D. If `layer_types[l]` is `sliding_attention`: half-split rotary
(`rope_theta`, all D numbers; pair i is (x[i], x[i + D/2])) on q and k, and
position i sees j iff 0 <= i - j < `sliding_window`. If `full_attention`: NO
rotary, causal over every position. Scores q.k / sqrt(D), softmax, grouped
H / K query heads a KV head, no sink, no bias. Output (softmax v * g) W_o.

FF_l, l < `num_dense_layers`: (silu(m W_1) * m W_3) W_2. Otherwise: s =
sigmoid(m W_r) over the router's experts; the k chosen are the top-k of
s + b (b a trained buffer, choice only; one group: no group step); w =
s[chosen] / (sum + 1e-20) (`route_norm`) x `route_scale`; sum_e w_e
Expert_e(m) + Shared(m), every expert and the shared one a SwiGLU of width
`moe_intermediate_size` (the shared: times `num_shared_experts`).

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
KIND: `g_` global attention, `w_` window attention, `dense_` dense
feed-forward, the mixtures' unprefixed); one expert's weights are upcast at
a time, in a scan over the experts; and THE SHARE (`expert_parallel` in the
configuration file: `chip` of `chips`, `num_experts` experts each of the
router's `experts`): the router scores all the experts and the weights are
those of all k chosen, as published; of the chosen, the experts of this
chip's range are computed and added, the others are the other chips' and
add nothing here — the same share the program holds. With the shares of
every chip summed and attention, the dense layers and the shared expert
counted once, the layer is the published one
(tests/engine/test_band_family.py holds that).

`FOLLOWS = "routing"` (benchmark/reference/moe.py says why): `forward(...,
follow=)` mixes the experts the program chose, with weights from its OWN
float32 scores by the rule above, and returns beside the logits the quantity
whose top-k decides, s + b, for benchmark/correctness.routing_verdict.
`generate` is greedy decoding by whole-sequence passes, for the engine test.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense

F32 = jnp.float32
FOLLOWS = "routing"
WINDOW, GLOBAL = "sliding_attention", "full_attention"

_ATTN = ("ln_attn", "wq", "wk", "wv", "wgate", "q_norm", "k_norm", "wo",
         "ln_attn_out")
_DENSE = ("ln_mlp", "wg", "wu", "wd", "ln_mlp_out")
_MOE = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
        "ws_gate", "ws_up", "ws_down", "ln_mlp_out")


def held_range(hf: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    share = hf.get("expert_parallel") or {}
    return int(share.get("chip", 0)) * hf["num_experts"], hf["num_experts"]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "d", "theta",
                                   "window", "eps"))
def attention_layer(x, l, ln, wq, wk, wv, wgate, q_norm, k_norm, wo, ln_out,
                    *, heads, kv_heads, d, theta, window, eps):
    """`window` None: a global layer, which does not rotate."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        a = dense.rms_norm(x, ln[l], eps)
        q = dense.rms_norm((a @ wq[l].astype(F32)).reshape(t, heads, d),
                           q_norm[l], eps)
        k = dense.rms_norm((a @ wk[l].astype(F32)).reshape(t, kv_heads, d),
                           k_norm[l], eps)
        v = (a @ wv[l].astype(F32)).reshape(t, kv_heads, d)
        gate = jax.nn.sigmoid(a @ wgate[l].astype(F32))
        i = jnp.arange(t)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            q, k = dense.rope(q, theta), dense.rope(k, theta)
            seen &= i - j < window
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, -1) * gate
        return x + dense.rms_norm(out @ wo[l].astype(F32), ln_out[l], eps)


@partial(jax.jit, static_argnames=("eps",))
def dense_layer(x, l, ln, wg, wu, wd, ln_out, *, eps):
    with jax.default_matmul_precision("highest"):
        m = dense.rms_norm(x, ln[l], eps)
        return x + dense.rms_norm(dense.swiglu(m, wg[l], wu[l], wd[l]),
                                  ln_out[l], eps)


@partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "first",
                                   "eps"))
def expert_layer(x, l, ln, router, router_bias, we_gate, we_up, we_down,
                 ws_gate, ws_up, ws_down, ln_out, chosen=None, *, top_k,
                 scale, normalize, first, eps):
    """`we_*` [Lm, held, ...]: the experts [first, first + held) of the
    router's. Returns (x + the layer, s + b [T, X])."""
    with jax.default_matmul_precision("highest"):
        m = dense.rms_norm(x, ln[l], eps)
        s = jax.nn.sigmoid(m @ router[l].astype(F32))
        biased = s + router_bias[l].astype(F32)
        if chosen is None:
            chosen = jax.lax.top_k(biased, top_k)[1]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if normalize:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        weights = picked * scale

        def one_expert(out, e):  # e: the expert's place among the held
            w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
            return out + w_e[:, None] * dense.swiglu(
                m, we_gate[l, e], we_up[l, e], we_down[l, e]), None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                                 jnp.arange(we_up.shape[1]))
        mixed = routed + dense.swiglu(m, ws_gate[l], ws_up[l], ws_down[l])
        return x + dense.rms_norm(mixed, ln_out[l], eps), biased


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm mixture layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    if hf.get("mup_enabled"):
        x = x * math.sqrt(hf["hidden_size"])
    seen = dict.fromkeys(("g_", "w_", "dense_", ""), 0)

    def take(prefix):
        seen[prefix] += 1
        return seen[prefix] - 1

    shape = dict(heads=hf["num_attention_heads"],
                 kv_heads=hf["num_key_value_heads"], d=hf["head_dim"],
                 theta=float(hf.get("rope_theta", 10000.0)), eps=eps)
    scores = []
    for layer, kind in enumerate(hf["layer_types"]):
        if kind not in (WINDOW, GLOBAL):
            raise ValueError(f"no layer kind {kind!r} in this reference")
        prefix = "w_" if kind == WINDOW else "g_"
        x = attention_layer(
            x, take(prefix), *(params[prefix + n] for n in _ATTN),
            window=int(hf["sliding_window"]) if kind == WINDOW else None,
            **shape)
        if layer < hf.get("num_dense_layers", 0):
            x = dense_layer(x, take("dense_"),
                            *(params["dense_" + n] for n in _DENSE), eps=eps)
            continue
        l = take("")
        x, biased = expert_layer(
            x, l, *(params[n] for n in _MOE),
            None if follow is None else jnp.asarray(follow[l], jnp.int32),
            top_k=hf["num_experts_per_tok"],
            scale=float(hf.get("route_scale") or 1.0),
            normalize=bool(hf.get("route_norm", True)),
            first=held_range(hf)[0], eps=eps)
        scores.append(biased)
    return (dense.unembed(x, params["ln_final"], params["lm_head"], eps=eps),
            jnp.stack(scores) if scores else jnp.zeros((0,), F32))


def generate(params: dict, hf: dict, prompt_ids, n: int) -> list[int]:
    """`n` greedy tokens after `prompt_ids`: a whole-sequence pass a token,
    the largest logit of the last position."""
    ids = [int(t) for t in prompt_ids]
    for _ in range(n):
        logits, _ = forward(params, hf, np.asarray(ids, np.int32))
        ids.append(int(np.argmax(np.asarray(logits[-1]))))
    return ids[len(prompt_ids):]
