"""Plain reference of the LFM2-MoE block (`model_type` `lfm2_moe`:
LiquidAI/LFM2-24B-A2B). RMSNorm has a learnt weight and eps `norm_eps`.
Float32, `jax.default_matmul_precision("highest")`, whole-sequence: no cache,
no pages, no carried rows, no kernels, no batching; a layer and an expert at
a time.

x_0 = E[ids]. Layer l: x <- x + Mix_l(norm_op(x)), then x <- x +
FF_l(norm_ffn(x)). Logits: norm_out(x_L) E^T (the head is the table).

Mix_l, `layer_types[l]` == `conv` (the gated short convolution): [B | C | u]
= h W_in (three equal parts in that order, no bias); z = B * u; c_t =
sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j} per channel (depthwise, causal, z
before position 0 is zero, L = `conv_L_cache`, NO bias and NO activation
behind it); y = C * c; Mix = y W_out. Here the convolution is L shifted
adds over the whole sequence.

Mix_l, `full_attention`: q = h W_q as H heads of D, k = h W_k, v = h W_v as
K heads of D; q <- RMSNorm_q(q), k <- RMSNorm_k(k) per head over D; THEN
half-split rotary over all D (`rope_theta`; pair i is (x[i], x[i + D/2]));
scores q.k / sqrt(D), causal softmax, H / K query heads a KV head;
(softmax v) W_o. No bias, no gate, no window, no sink.

FF_l, l < `num_dense_layers`: (silu(m W_1) * m W_3) W_2. Otherwise: s =
sigmoid(m W_r) over the experts; the k chosen are the top-k of s + b (b the
`expert_bias` buffer, float32: the choice only); w = s[chosen] / (sum +
1e-6) (`norm_topk_prob`) x `routed_scaling_factor`; sum_e w_e Expert_e(m),
every expert a SwiGLU of width `moe_intermediate_size`, no shared expert.

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
KIND: `c_` conv mixers, `a_` attention, `dense_` dense feed-forward, the
mixtures' unprefixed; the published `in_proj` is held whole as `conv_in`,
the depthwise `conv.weight` [E, 1, L] as `conv_w` [E, L]); D is the quotient
hidden / heads (the published config has no `head_dim`); one expert's
weights are upcast at a time, in a scan over the experts; the rotary
embedding is unscaled (`rope_type` default) and a file that says otherwise
is refused.

`FOLLOWS = "routing"` (benchmark/reference/moe.py says why): `forward(...,
follow=)` mixes the experts the program chose, with weights from its OWN
float32 scores by the rule above, and returns beside the logits the quantity
whose top-k decides, s + b, for benchmark/correctness.routing_verdict.
`generate` is greedy decoding by whole-sequence passes, for the engine test.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense

F32 = jnp.float32
FOLLOWS = "routing"
CONV, ATTENTION = "conv", "full_attention"
ROUTE_EPS = 1e-6

_CONV = ("ln_conv", "conv_in", "conv_w", "conv_out")
_ATTN = ("ln_attn", "wq", "wk", "wv", "q_norm", "k_norm", "wo")
_DENSE = ("ln_mlp", "wg", "wu", "wd")
_MOE = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down")


@partial(jax.jit, static_argnames=("eps",))
def conv_layer(x, l, ln, w_in, taps, w_out, *, eps):
    with jax.default_matmul_precision("highest"):
        t, e = x.shape
        h = dense.rms_norm(x, ln[l], eps)
        bcu = h @ w_in[l].astype(F32)
        z = bcu[:, :e] * bcu[:, 2 * e:]
        w = taps[l].astype(F32)  # [E, L], w[:, L - 1] on the position itself
        width = w.shape[1]
        back = jnp.concatenate([jnp.zeros((width - 1, e), F32), z])
        c = sum(back[j:j + t] * w[:, j] for j in range(width))
        return x + (bcu[:, e:2 * e] * c) @ w_out[l].astype(F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "d", "theta", "eps"))
def attention_layer(x, l, ln, wq, wk, wv, q_norm, k_norm, wo, *, heads,
                    kv_heads, d, theta, eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln[l], eps)
        q = dense.rms_norm((h @ wq[l].astype(F32)).reshape(t, heads, d),
                           q_norm[l], eps)
        k = dense.rms_norm((h @ wk[l].astype(F32)).reshape(t, kv_heads, d),
                           k_norm[l], eps)
        v = (h @ wv[l].astype(F32)).reshape(t, kv_heads, d)
        q, k = dense.rope(q, theta), dense.rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
        causal = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, -1)
        return x + out @ wo[l].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def dense_layer(x, l, ln, wg, wu, wd, *, eps):
    with jax.default_matmul_precision("highest"):
        m = dense.rms_norm(x, ln[l], eps)
        return x + dense.swiglu(m, wg[l], wu[l], wd[l])


@partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "eps"))
def expert_layer(x, l, ln, router, router_bias, we_gate, we_up, we_down,
                 chosen=None, *, top_k, scale, normalize, eps):
    """Returns (x + the layer, s + b [T, X])."""
    with jax.default_matmul_precision("highest"):
        m = dense.rms_norm(x, ln[l], eps)
        s = jax.nn.sigmoid(m @ router[l].astype(F32))
        biased = s + router_bias[l].astype(F32)
        if chosen is None:
            chosen = jax.lax.top_k(biased, top_k)[1]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if normalize:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                               + ROUTE_EPS)
        weights = picked * scale

        def one_expert(out, e):
            w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            return out + w_e[:, None] * dense.swiglu(
                m, we_gate[l, e], we_up[l, e], we_down[l, e]), None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                                 jnp.arange(we_up.shape[1]))
        return x + routed, biased


@partial(jax.jit, static_argnames=("eps",))
def tied_head(x, ln_final, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return dense.rms_norm(x, ln_final, eps) @ embed.astype(F32).T


def rope_theta(hf: dict) -> float:
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError("this reference rotates by the default rule alone, "
                         f"not {rope['rope_type']!r}")
    return float(rope.get("rope_theta", hf.get("rope_theta", 1000000.0)))


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm mixture layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k."""
    if hf.get("conv_bias"):
        raise ValueError("this reference's convolution has no bias")
    eps = float(hf.get("norm_eps", 1e-5))
    heads = hf["num_attention_heads"]
    shape = dict(heads=heads, kv_heads=hf["num_key_value_heads"],
                 d=hf.get("head_dim") or hf["hidden_size"] // heads,
                 theta=rope_theta(hf), eps=eps)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    seen = dict.fromkeys(("c_", "a_", "dense_", ""), 0)

    def take(prefix):
        seen[prefix] += 1
        return seen[prefix] - 1

    scores = []
    for layer, kind in enumerate(hf["layer_types"]):
        if kind == CONV:
            x = conv_layer(x, take("c_"), *(params["c_" + n] for n in _CONV),
                           eps=eps)
        elif kind == ATTENTION:
            x = attention_layer(x, take("a_"),
                                *(params["a_" + n] for n in _ATTN), **shape)
        else:
            raise ValueError(f"no layer kind {kind!r} in this reference")
        if layer < hf.get("num_dense_layers", 0):
            x = dense_layer(x, take("dense_"),
                            *(params["dense_" + n] for n in _DENSE), eps=eps)
            continue
        l = take("")
        x, biased = expert_layer(
            x, l, *(params[n] for n in _MOE),
            None if follow is None else jnp.asarray(follow[l], jnp.int32),
            top_k=hf["num_experts_per_tok"],
            scale=float(hf.get("routed_scaling_factor", 1.0)),
            normalize=bool(hf.get("norm_topk_prob", True)), eps=eps)
        scores.append(biased)
    return (tied_head(x, params["ln_final"], params["embed"], eps=eps),
            jnp.stack(scores) if scores else jnp.zeros((0,), F32))


def generate(params: dict, hf: dict, prompt_ids, n: int) -> list[int]:
    """`n` greedy tokens after `prompt_ids`: a whole-sequence pass a token,
    the largest logit of the last position."""
    ids = [int(t) for t in prompt_ids]
    for _ in range(n):
        logits, _ = forward(params, hf, np.asarray(ids, np.int32))
        ids.append(int(np.argmax(np.asarray(logits[-1]))))
    return ids[len(prompt_ids):]
