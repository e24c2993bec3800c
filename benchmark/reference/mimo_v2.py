"""Plain reference of the MiMo-V2 language model's block (`model_type`
`mimo_v2`: XiaomiMiMo/MiMo-V2.5): layer l is x <- x + attention_l(RMSNorm(x)),
x <- x + feed_forward_l(RMSNorm(x)), with the attention's KIND by
`hybrid_layer_pattern[l]` (0 global, 1 window) and the feed-forward's by
`moe_layer_freq[l]` (0 a dense SwiGLU, 1 a mixture). Float32,
`jax.default_matmul_precision("highest")`, whole-sequence masks: no ring, no
pages, no cache, no kernels, no batching; a layer and an expert at a time.

Attention, both kinds: q = h W_q -> H heads of D; k = h W_k -> K heads of D;
v = h W_v -> K heads of Dv, v <- `attention_value_scale` x v; rotary on the
FIRST R numbers of each query and key head (R = `partial_rotary_factor` x D
rounded down to even; pair i of them is (x[i], x[i + R/2]); the other D - R
pass unrotated), at base `rope_theta` (global) or `swa_rope_theta` (window);
scores s_ij = q_i . k_j / sqrt(D) for j <= i. GLOBAL: K =
`num_key_value_heads`, the whole context, a plain softmax. WINDOW: K =
`swa_num_key_value_heads`, also i - j < `sliding_window` (a position sees
itself and the W - 1 before it), and with `add_swa_attention_sink_bias` one
learnt scalar a head in the softmax's denominator that takes no value:
p_ij = exp(s_ij - m) / (exp(sink_h - m) + sum_j exp(s_ij - m)). o = (p v) W_o.
Mixture: s = sigmoid(h W_r) in float32; chosen = top-k of s + b (`n_group` =
`topk_group` = 1: no group step); w = s[chosen] / (sum + 1e-20)
(`norm_topk_prob`) x `routed_scaling_factor` (null = 1); expert_i(h) =
(silu(h W_gate,i) * (h W_up,i)) W_down,i; no shared expert.

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
KIND: `g_` global attention, `w_` window attention, `dense_` dense
feed-forward, the mixtures' unprefixed); one expert's weights are upcast at
a time, in a scan over the experts; and THE SHARE (`expert_parallel` in the
configuration file: `chip` of `chips`, `n_routed_experts` experts each of
the router's `experts`): the router scores all the experts and the weights
are those of all k chosen, as published; of the chosen, the experts of this
chip's range are computed and added, the others are the other chips' and
add nothing here — the same share the program holds. With the shares of
every chip summed and attention and the dense layer counted once, the layer
is the published one (tests/engine/test_window_family.py holds that).

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

_ATTN = ("ln_attn", "wq", "wk", "wv", "wo")
_DENSE = ("ln_mlp", "wg", "wu", "wd")
_MOE = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down")


def held_range(hf: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    share = hf.get("expert_parallel") or {}
    return (int(share.get("chip", 0)) * hf["n_routed_experts"],
            hf["n_routed_experts"])


def rotary_numbers(hf: dict) -> int:
    return int(hf["head_dim"] * hf.get("partial_rotary_factor", 1.0)) // 2 * 2


def partial_rope(x, theta: float, r: int):
    """x [T, H, D]: rotate the first `r` numbers of each head at the row's
    position, pair i being (x[i], x[i + r/2]); the rest pass."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]  # [T, r/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        (a * cos - b * sin, b * cos + a * sin, x[..., r:]), axis=-1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "d", "dv", "theta",
                                   "r", "window", "value_scale", "eps"))
def attention_layer(x, l, ln, wq, wk, wv, wo, sink=None, *, heads, kv_heads,
                    d, dv, theta, r, window, value_scale, eps):
    """`window` None: a global layer. `sink` [L, H] or None."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln[l], eps)
        q = partial_rope((h @ wq[l].astype(F32)).reshape(t, heads, d), theta, r)
        k = partial_rope((h @ wk[l].astype(F32)).reshape(t, kv_heads, d),
                         theta, r)
        v = value_scale * (h @ wv[l].astype(F32)).reshape(t, kv_heads, dv)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
        i = jnp.arange(t)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        if sink is not None:  # a column that takes no value
            col = jnp.broadcast_to(sink[l].astype(F32)[:, None, None],
                                   (heads, t, 1))
            probs = jax.nn.softmax(jnp.concatenate([scores, col], -1),
                                   axis=-1)[..., :-1]
        else:
            probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v)
        return x + out.reshape(t, -1) @ wo[l].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def dense_layer(x, l, ln, wg, wu, wd, *, eps):
    with jax.default_matmul_precision("highest"):
        h = dense.rms_norm(x, ln[l], eps)
        return x + dense.swiglu(h, wg[l], wu[l], wd[l])


@partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "first",
                                   "eps"))
def expert_layer(x, l, ln, router, router_bias, we_gate, we_up, we_down,
                 chosen=None, *, top_k, scale, normalize, first, eps):
    """`we_*` [Lm, held, ...]: the experts [first, first + held) of the
    router's. Returns (x + the layer, s + b [T, X])."""
    with jax.default_matmul_precision("highest"):
        h = dense.rms_norm(x, ln[l], eps)
        s = jax.nn.sigmoid(h @ router[l].astype(F32))
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
                h, we_gate[l, e], we_up[l, e], we_down[l, e]), None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                                 jnp.arange(we_up.shape[1]))
        return x + routed, biased


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm mixture layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k."""
    eps = float(hf.get("layernorm_epsilon", hf.get("rms_norm_eps", 1e-5)))
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    seen = dict.fromkeys(("g_", "w_", "dense_", ""), 0)

    def take(prefix):
        seen[prefix] += 1
        return seen[prefix] - 1

    shared = dict(heads=hf["num_attention_heads"], d=hf["head_dim"],
                  dv=hf["v_head_dim"], r=rotary_numbers(hf),
                  value_scale=float(hf.get("attention_value_scale") or 1.0),
                  eps=eps)
    scores = []
    for kind, routed in zip(hf["hybrid_layer_pattern"], hf["moe_layer_freq"]):
        if kind == 1:
            sink = (params["w_sink"]
                    if hf.get("add_swa_attention_sink_bias") else None)
            x = attention_layer(
                x, take("w_"), *(params["w_" + n] for n in _ATTN), sink,
                kv_heads=hf["swa_num_key_value_heads"],
                theta=float(hf["swa_rope_theta"]),
                window=int(hf["sliding_window"]), **shared)
        elif kind == 0:
            x = attention_layer(
                x, take("g_"), *(params["g_" + n] for n in _ATTN),
                kv_heads=hf["num_key_value_heads"],
                theta=float(hf["rope_theta"]), window=None, **shared)
        else:
            raise ValueError(f"no layer kind {kind!r} in this reference")
        if routed:
            l = take("")
            x, biased = expert_layer(
                x, l, *(params[n] for n in _MOE),
                None if follow is None else jnp.asarray(follow[l], jnp.int32),
                top_k=hf["num_experts_per_tok"],
                scale=float(hf.get("routed_scaling_factor") or 1.0),
                normalize=bool(hf.get("norm_topk_prob", True)),
                first=held_range(hf)[0], eps=eps)
            scores.append(biased)
        else:
            x = dense_layer(x, take("dense_"),
                            *(params["dense_" + n] for n in _DENSE), eps=eps)
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
