"""Plain reference of the Nemotron-H block (HF `modeling_nemotron_h`, the
`model_type` of NVIDIA-Nemotron-3-Nano-30B-A3B): every layer is
x <- x + mixer(RMSNorm(x)) with ONE mixer, by the character of
`hybrid_override_pattern` — `M` a Mamba-2 state-space mixer, `*` grouped-query
attention, `E` a sigmoid-routed mixture of relu^2 experts with a shared
expert. Float32, `jax.default_matmul_precision("highest")`, no cache, no
kernels, no batching, no chunks: one whole-sequence pass, layer by layer.

`M` (H heads of P channels, G groups, state N, kernel K): [z | xBC | dt] =
h W_in, widths H P | H P + 2 G N | H; xBC <- silu(conv(xBC) + b), a causal
depthwise convolution over time (K - 1 zero rows in front); [x | B | C] =
xBC; dt <- softplus(dt + dt_bias), A = -exp(A_log). Per head h of group
g = h // (H / G), TOKEN BY TOKEN (`jax.lax.scan` over the positions):
S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t + D_h x_t, S_0 = 0.
Then y <- y silu(z), an RMS norm over each group's H P / G channels times a
weight, and W_out.
`*`: q, k, v = h W_q, h W_k, h W_v; causal softmax(q k^T / sqrt(d)) v, W_o;
NO rotary embedding (the family's modelling code applies none: `rope_theta`
and `partial_rotary_factor` are read by no layer).
`E`: s = sigmoid(h W_r); chosen = top-k of s + b (`n_group` 1, `topk_group`
1: no group restriction); w = s[chosen] / (sum + 1e-20) x
routed_scaling_factor; expert_i(h) = relu(h W_up,i)^2 W_down,i; out = sum_i w_i
expert_i(h) + relu(h W_s,up)^2 W_s,down.

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
KIND of layer, in the kind's own order); one expert's weights are upcast at a
time, in a scan over the experts; and THE SHARE: this is one chip of a
deployment whose chips share each expert layer (`expert_parallel` in the
configuration file: `chip` of `chips`, `n_routed_experts` experts each of the
router's `experts`). The router scores all the experts and the weights are
those of all k chosen, as published; of the chosen, the experts of this
chip's range are computed and added, the others are the other chips' and add
nothing here — the same share the program holds. With the shares of every
chip summed and the shared expert counted once, the layer is the published
one (tests/engine/test_hybrid_family.py holds that).

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

_SSM = ("ln_ssm", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
        "ssm_a_log", "ssm_d", "ln_gate", "ssm_out")
_ATTN = ("ln_attn", "wq", "wk", "wv", "wo")
_MOE = ("ln_mlp", "router", "router_bias", "we_up", "we_down", "ws_up",
        "ws_down")


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def held_range(hf: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    share = hf.get("expert_parallel") or {}
    return (int(share.get("chip", 0)) * hf["n_routed_experts"],
            hf["n_routed_experts"])


@partial(jax.jit, static_argnames=("heads", "p", "groups", "n", "eps"))
def ssm_layer(x, l, ln, w_in, conv_w, conv_b, dt_bias, a_log, d, ln_gate,
              w_out, *, heads, p, groups, n, eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        di, gn = heads * p, groups * n
        proj = dense.rms_norm(x, ln[l], eps) @ w_in[l].astype(F32)
        z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * gn], proj[:, 2 * di + 2 * gn:]
        w = conv_w[l].astype(F32)  # [C, K], the last tap on the current row
        k = w.shape[-1]
        rows = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
        xbc = jax.nn.silu(sum(rows[j:j + t] * w[:, j] for j in range(k))
                          + conv_b[l].astype(F32))
        xs = xbc[:, :di].reshape(t, heads, p)
        per_group = heads // groups
        b = jnp.repeat(xbc[:, di:di + gn].reshape(t, groups, n), per_group, 1)
        c = jnp.repeat(xbc[:, di + gn:].reshape(t, groups, n), per_group, 1)
        dt = jax.nn.softplus(dt + dt_bias[l].astype(F32))  # [T, H]
        a = -jnp.exp(a_log[l].astype(F32))  # [H]

        def token(s, inp):
            x_t, dt_t, b_t, c_t = inp  # [H, P], [H], [H, N], [H, N]
            s = (jnp.exp(dt_t * a)[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return s, jnp.sum(s * c_t[:, None, :], axis=-1)

        _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), F32),
                            (xs, dt, b, c))
        y = y + d[l].astype(F32)[:, None] * xs
        y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(t, di) * ln_gate[l].astype(F32)
        return x + y @ w_out[l].astype(F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps"))
def attention_layer(x, l, ln, wq, wk, wv, wo, *, heads, kv_heads, head_dim,
                    eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln[l], eps)
        q = (h @ wq[l].astype(F32)).reshape(t, heads, head_dim)
        k = (h @ wk[l].astype(F32)).reshape(t, kv_heads, head_dim)
        v = (h @ wv[l].astype(F32)).reshape(t, kv_heads, head_dim)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                           -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        return x + out.reshape(t, -1) @ wo[l].astype(F32)


@partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "first",
                                   "eps"))
def expert_layer(x, l, ln, router, router_bias, we_up, we_down, ws_up,
                 ws_down, chosen=None, *, top_k, scale, normalize, first,
                 eps):
    """`we_*` [Lm, held, F, M]: the experts [first, first + held) of the
    router's, both matrices with the expert's width first. Returns (x + the layer, s + b [T, X])."""
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
            return out + w_e[:, None] * (
                relu2(h @ we_up[l, e].astype(F32).T)  # stored [F, M]
                @ we_down[l, e].astype(F32)), None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                                 jnp.arange(we_up.shape[1]))
        shared = relu2(h @ ws_up[l].astype(F32)) @ ws_down[l].astype(F32)
        return x + routed + shared, biased


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm expert layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k."""
    eps = float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    seen = dict.fromkeys("M*E", 0)
    scores = []
    for kind in hf["hybrid_override_pattern"]:
        l = seen[kind]
        seen[kind] += 1
        if kind == "M":
            x = ssm_layer(x, l, *(params[n] for n in _SSM),
                          heads=hf["mamba_num_heads"], p=hf["mamba_head_dim"],
                          groups=hf["n_groups"], n=hf["ssm_state_size"],
                          eps=eps)
        elif kind == "*":
            x = attention_layer(
                x, l, *(params[n] for n in _ATTN),
                heads=hf["num_attention_heads"],
                kv_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim") or hf["attention_head_dim"],
                eps=eps)
        elif kind == "E":
            x, biased = expert_layer(
                x, l, *(params[n] for n in _MOE),
                None if follow is None else jnp.asarray(follow[l], jnp.int32),
                top_k=hf["num_experts_per_tok"],
                scale=float(hf.get("routed_scaling_factor", 1.0)),
                normalize=bool(hf.get("norm_topk_prob", True)),
                first=held_range(hf)[0], eps=eps)
            scores.append(biased)
        else:
            raise ValueError(f"no layer kind {kind!r} in this reference")
    return (dense.unembed(x, params["ln_final"], params["lm_head"], eps=eps),
            jnp.stack(scores))
