"""Plain reference of the DeepSeek-V3 block (HF `modeling_deepseek_v3`, the
`model_type` of kanana-2-30b-a3b-instruct-2601): pre-norm RMSNorm,
multi-head latent attention in its MATERIALISED form only, a dense SwiGLU in
the first `first_k_dense_replace` layers and after them a sigmoid-routed
mixture with shared experts. Float32, `jax.default_matmul_precision
("highest")`, no cache, no kernels: one whole-sequence causal pass.

Per layer, x <- x + Attn(RMSNorm(x)); x <- x + F(RMSNorm(x)).
Attention (`q_lora_rank` null): q = x W_q -> H heads x [q_nope | q_rope];
x W_kv_a -> [c | k_r]; c <- RMSNorm(c; kv_a_layernorm); RoPE over the
qk_rope_head_dim numbers of k_r (one head, shared by all) and of q_rope (per
head), on PAIRS (2i, 2i+1) (`rope_interleave`), no scaling; c W_kv_b -> per
head [k_nope | v]; score = (q_nope . k_nope + q_rope . k_rope) / sqrt(Dn+Dr),
causal softmax, o = sum p v, out = concat(o) W_o.
Mixture: s = sigmoid(h W_r); chosen = top-k of s + b (`n_group` 1 and
`topk_group` 1: no group restriction); w = s[chosen] / (sum + 1e-20) x
routed_scaling_factor; F(h) = sum_i w_i SwiGLU_{e_i}(h) + SwiGLU_shared(h).

Departures from the published model, all of layout and none of arithmetic:
weights are the program's random bf16 values upcast to float32; `W_kv_b` is
read as the program stores it, split per head into `wk_b` [H, C, Dn] and
`wv_b` [H, C, Dv]; the `num_shared_experts` shared experts are one SwiGLU of
their joint width (as the published checkpoint stores them); the rotated
pairs stay where they are (HF moves them into two halves first, the same
permutation on q and k, which no score sees). One expert's weights are
upcast at a time, in a scan over the experts.

`FOLLOWS = "routing"` (benchmark/reference/moe.py says why): `forward(...,
follow=)` mixes the experts the program chose, with weights from its OWN
float32 scores by the rule above, and returns beside the logits the quantity
whose top-k decides, s + b, for benchmark/correctness.routing_verdict.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32
FOLLOWS = "routing"
DENSE = "dense_"  # the prefix of the leading dense layers' leaves
_ATTN = ("wq", "wkv_a", "ln_kv", "wk_b", "wv_b", "wo", "ln_attn")


def rope_pairs(x, theta):
    """x: [T, H, D]; pair i is (x[2i], x[2i+1]), position = row index."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(h, wq, wkv_a, ln_kv, wk_b, wv_b, wo, *, heads, rank, nope,
              theta, eps):
    t = h.shape[0]
    q = (h @ wq.astype(F32)).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], theta)
    kv = h @ wkv_a.astype(F32)
    c = dense.rms_norm(kv[:, :rank], ln_kv, eps)
    k_rope = rope_pairs(kv[:, None, rank:], theta)[:, 0]  # [T, Dr]
    k_nope = jnp.einsum("tc,hcd->thd", c, wk_b.astype(F32))
    v = jnp.einsum("tc,hcd->thd", c, wv_b.astype(F32))
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
              ) / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, -1) @ wo.astype(F32)


def mixture(h, l, router, bias, we_gate, we_up, we_down, chosen, *, top_k,
            scale, normalize):
    """h [T, E]; router [E, X] and bias [X] of expert layer l; we_* the
    stacked [Lm, X, ...] expert weights, read one expert at a time;
    `chosen` [T, k] the experts to mix, or None for the rule's own. Returns
    the routed experts' output and s + b [T, X]."""
    s = jax.nn.sigmoid(h @ router.astype(F32))
    biased = s + bias.astype(F32)
    if chosen is None:
        chosen = jax.lax.top_k(biased, top_k)[1]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    weights = picked * scale

    def one_expert(out, e):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [T]
        return out + w_e[:, None] * dense.swiglu(
            h, we_gate[l, e], we_up[l, e], we_down[l, e]), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(router.shape[-1]))
    return out, biased


_DIMS = ("heads", "rank", "nope", "theta", "eps")


@partial(jax.jit, static_argnames=_DIMS)
def dense_layer(x, l, wq, wkv_a, ln_kv, wk_b, wv_b, wo, ln_attn, ln_mlp, wg,
                wu, wd, **d):
    with jax.default_matmul_precision("highest"):
        x = x + attention(dense.rms_norm(x, ln_attn[l], d["eps"]), wq[l],
                          wkv_a[l], ln_kv[l], wk_b[l], wv_b[l], wo[l], **d)
        return x + dense.swiglu(dense.rms_norm(x, ln_mlp[l], d["eps"]),
                                wg[l], wu[l], wd[l])


@partial(jax.jit, static_argnames=_DIMS + ("top_k", "scale", "normalize"))
def expert_layer(x, l, wq, wkv_a, ln_kv, wk_b, wv_b, wo, ln_attn, ln_mlp,
                 router, router_bias, we_gate, we_up, we_down, ws_gate, ws_up,
                 ws_down, chosen=None, *, top_k, scale, normalize, **d):
    with jax.default_matmul_precision("highest"):
        x = x + attention(dense.rms_norm(x, ln_attn[l], d["eps"]), wq[l],
                          wkv_a[l], ln_kv[l], wk_b[l], wv_b[l], wo[l], **d)
        h = dense.rms_norm(x, ln_mlp[l], d["eps"])
        routed, biased = mixture(h, l, router[l], router_bias[l], we_gate,
                                 we_up, we_down, chosen, top_k=top_k,
                                 scale=scale, normalize=normalize)
        shared = dense.swiglu(h, ws_gate[l], ws_up[l], ws_down[l])
        return x + routed + shared, biased


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm expert layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k."""
    d = {"heads": hf["num_attention_heads"], "rank": hf["kv_lora_rank"],
         "nope": hf["qk_nope_head_dim"],
         "theta": float(hf.get("rope_theta", 10000.0)),
         "eps": float(hf.get("rms_norm_eps", 1e-6))}
    first = hf.get("first_k_dense_replace", 0)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    for l in range(first):
        x = dense_layer(x, l, *(params[DENSE + n] for n in _ATTN + (
            "ln_mlp", "wg", "wu", "wd")), **d)
    scores = []
    for l in range(hf["num_hidden_layers"] - first):
        x, biased = expert_layer(
            x, l, *(params[n] for n in _ATTN + (
                "ln_mlp", "router", "router_bias", "we_gate", "we_up",
                "we_down", "ws_gate", "ws_up", "ws_down")),
            None if follow is None else jnp.asarray(follow[l], jnp.int32),
            top_k=hf["num_experts_per_tok"],
            scale=float(hf.get("routed_scaling_factor", 1.0)),
            normalize=bool(hf.get("norm_topk_prob", True)), **d)
        scores.append(biased)
    head = (params["embed"].T if hf.get("tie_word_embeddings")
            else params["lm_head"])
    return (dense.unembed(x, params["ln_final"], head, eps=d["eps"]),
            jnp.stack(scores))
