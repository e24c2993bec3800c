"""Plain reference of the LongCat-Flash block (HF `modeling_longcat_flash`,
the language model of LongCat-Flash-Omni: "28 double-layers"). Float32,
`jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching: one whole-sequence causal pass, a sub-layer at a time.

One layer, with x the residual stream, N_i RMS norms, A_0, A_1 latent
attentions, F_0, F_1 dense SwiGLU feed-forwards and M the mixture:

    a = x + A_0(N_0(x))          h = N_1(a)
    b = a + F_0(h)               s = M(h)       # the shortcut: not added here
    c = b + A_1(N_2(b))
    y = c + F_1(N_3(c)) + s      # s joins after the second sub-layer

A(u), H heads: q = W_qb RMSNorm(W_qa u) -> H x [q_nope | q_rope];
[c_kv | k_r] = W_kva u; c = RMSNorm(c_kv); q *= sqrt(hidden / q_lora_rank)
(`mla_scale_q_lora`, both parts); c' = c sqrt(hidden / kv_lora_rank)
(`mla_scale_kv_lora`; k_r is NOT scaled); [k_nope | v]_h = W_kvb,h c'; RoPE on
PAIRS (2i, 2i+1) of q_rope (per head) and k_r (one head, shared), no
scaling; score = (q_nope . k_nope + q_rope . k_r) / sqrt(Dn + Dr), causal
softmax, out = W_o concat_h(sum p v_h).
M(h), router width X = experts + zero-compute experts, k chosen:
p = softmax_X(W_r h) in float32; chosen = top-k of p + b; w_j = scale x
p[chosen_j] (divided by their sum first only under `norm_topk_prob`, false
as published); M(h) = sum_{chosen_j an expert} w_j SwiGLU_j(h)
+ (sum_{chosen_j zero-compute} w_j) h.

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
sub-layer, `s0_` / `s1_`, the mixture's leaves with `s0_`; `W_kvb` split per
head into `wk_b` [H, C, Dn] and `wv_b` [H, C, Dv]); the rotated pairs stay
where they are (HF moves them into two halves first, the same permutation on
q and k, which no score sees); one expert's weights are upcast at a time, in
a scan over the experts; and THE SHARE: this is one chip of a deployment
whose chips share each layer's experts (`expert_parallel` in the
configuration file: `chip` of `chips`, `n_routed_experts` experts each of
the router's `experts`). The router scores all its outputs and the weights
are those of all k chosen, as published; of the chosen, the experts of this
chip's range are computed and added, the zero-compute experts' part is
computed here as on every chip (it needs none), the other experts are the
other chips' and add nothing here — the same share the program holds. With
every chip's held part summed and the zero-compute part counted once, the
layer is the published one (tests/engine/test_shortcut_family.py holds
that). The vocabulary is the configuration's (a slice of the published one:
smaller embedding and head, nothing else).

`FOLLOWS = "routing"` (benchmark/reference/moe.py says why): `forward(...,
follow=)` mixes the outputs the program chose, with weights from its OWN
float32 scores by the rule above, and returns beside the logits the quantity
whose top-k decides, p + b over all X, for
benchmark/correctness.routing_verdict.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense
from benchmark.reference.deepseek_v3 import rope_pairs
from benchmark.reference.nemotron_h import held_range  # noqa: F401 — the same

F32 = jnp.float32
FOLLOWS = "routing"

SUB = ("s0_", "s1_")
_ATTN = ("ln_attn", "wq_a", "ln_q", "wq_b", "wkv_a", "ln_kv", "wk_b", "wv_b",
         "wo")
_MLP = ("ln_mlp", "wg", "wu", "wd")
_MOE = ("router", "router_bias", "we_gate", "we_up", "we_down")


def real_experts(hf: dict) -> int:
    """The router's outputs that are experts; the rest are zero-compute."""
    return int((hf.get("expert_parallel") or {}).get(
        "experts", hf["n_routed_experts"]))


def attention(u, wq_a, ln_q, wq_b, wkv_a, ln_kv, wk_b, wv_b, wo, *, heads,
              rank, nope, theta, eps, q_scale, kv_scale):
    t = u.shape[0]
    q = (dense.rms_norm(u @ wq_a.astype(F32), ln_q, eps)
         @ wq_b.astype(F32)).reshape(t, heads, -1) * q_scale
    q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], theta)
    kv = u @ wkv_a.astype(F32)
    c = dense.rms_norm(kv[:, :rank], ln_kv, eps) * kv_scale
    k_rope = rope_pairs(kv[:, None, rank:], theta)[:, 0]  # [T, Dr], unscaled
    k_nope = jnp.einsum("tc,hcd->thd", c, wk_b.astype(F32))
    v = jnp.einsum("tc,hcd->thd", c, wv_b.astype(F32))
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
              ) / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, -1) @ wo.astype(F32)


def mixture_parts(h, l, router, bias, we_gate, we_up, we_down, chosen, *,
                  top_k, scale, normalize, first, real):
    """h [T, E]; router [E, X] and bias [X] of layer l; we_* the stacked
    [L, held, ...] weights of experts [first, first + held), read one at a
    time; `chosen` [T, k] the router outputs to mix, or None for the rule's
    own. Returns (the held experts' part, the zero-compute experts' part,
    p + b [T, X])."""
    p = jax.nn.softmax(h @ router.astype(F32), axis=-1)
    biased = p + bias.astype(F32)
    if chosen is None:
        chosen = jax.lax.top_k(biased, top_k)[1]
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    weights = picked * scale

    def one_expert(out, e):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return out + w_e[:, None] * dense.swiglu(
            h, we_gate[l, e], we_up[l, e], we_down[l, e]), None

    held, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                           jnp.arange(we_up.shape[1]))
    identity = jnp.sum(jnp.where(chosen >= real, weights, 0.0),
                       axis=-1)[:, None] * h
    return held, identity, biased


_DIMS = ("heads", "rank", "nope", "theta", "eps", "q_scale", "kv_scale")
_RULE = ("top_k", "scale", "normalize", "first", "real")


@partial(jax.jit, static_argnames=_DIMS)
def attention_sublayer(x, l, ln_attn, wq_a, ln_q, wq_b, wkv_a, ln_kv, wk_b,
                       wv_b, wo, **d):
    """x + A(N(x)) with layer l's weights of one sub-layer's stacks."""
    with jax.default_matmul_precision("highest"):
        return x + attention(
            dense.rms_norm(x, ln_attn[l], d["eps"]), wq_a[l], ln_q[l],
            wq_b[l], wkv_a[l], ln_kv[l], wk_b[l], wv_b[l], wo[l], **d)


@partial(jax.jit, static_argnames=("eps",))
def feed_forward(x, l, ln_mlp, wg, wu, wd, *, eps):
    """(F(N(x)), N(x)): the dense feed-forward's output and its input."""
    with jax.default_matmul_precision("highest"):
        h = dense.rms_norm(x, ln_mlp[l], eps)
        return dense.swiglu(h, wg[l], wu[l], wd[l]), h


@partial(jax.jit, static_argnames=_RULE)
def mixture(h, l, router, router_bias, we_gate, we_up, we_down, chosen=None,
            **rule):
    """M(h) of this chip's share, and p + b."""
    with jax.default_matmul_precision("highest"):
        held, identity, biased = mixture_parts(
            h, l, router[l], router_bias[l], we_gate, we_up, we_down, chosen,
            **rule)
        return held + identity, biased


def dims(hf: dict) -> dict:
    hidden = hf["hidden_size"]

    def lora_scale(key, rank):
        return math.sqrt(hidden / rank) if hf.get(key) else 1.0

    return {"heads": hf["num_attention_heads"], "rank": hf["kv_lora_rank"],
            "nope": hf["qk_nope_head_dim"],
            "theta": float(hf.get("rope_theta", 10000.0)),
            "eps": float(hf.get("rms_norm_eps", 1e-5)),
            "q_scale": lora_scale("mla_scale_q_lora", hf["q_lora_rank"]),
            "kv_scale": lora_scale("mla_scale_kv_lora", hf["kv_lora_rank"])}


def rule(hf: dict) -> dict:
    return {"top_k": hf["moe_topk"],
            "scale": float(hf.get("routed_scaling_factor", 1.0)),
            "normalize": bool(hf.get("norm_topk_prob", False)),
            "first": held_range(hf)[0], "real": real_experts(hf)}


def double_layer(params: dict, l: int, x, d: dict, r: dict, chosen=None):
    """One layer of the equations above on x [T, E]: (y, p + b)."""
    def leaves(sub, names):
        return (params[SUB[sub] + n] for n in names)

    a = attention_sublayer(x, l, *leaves(0, _ATTN), **d)
    f0, h = feed_forward(a, l, *leaves(0, _MLP), eps=d["eps"])
    s, biased = mixture(h, l, *leaves(0, _MOE), chosen, **r)
    c = attention_sublayer(a + f0, l, *leaves(1, _ATTN), **d)
    f1, _ = feed_forward(c, l, *leaves(1, _MLP), eps=d["eps"])
    return c + f1 + s, biased


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and p + b [L, T, X] of
    the L layers' routers. `follow` [L, T, k]: the router outputs to mix in
    place of the rule's own top-k."""
    d, r = dims(hf), rule(hf)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    scores = []
    for l in range(hf["num_layers"]):
        x, biased = double_layer(
            params, l, x, d, r,
            None if follow is None else jnp.asarray(follow[l], jnp.int32))
        scores.append(biased)
    return (dense.unembed(x, params["ln_final"], params["lm_head"],
                          eps=d["eps"]), jnp.stack(scores))
