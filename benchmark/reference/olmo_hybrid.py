"""Plain reference of the Olmo-Hybrid block (`model_type` `olmo_hybrid`:
Olmo-Hybrid-7B): by `layer_types`, a gated delta-rule mixer
(`linear_attention`) or full attention, then a SwiGLU feed-forward, each
sub-layer's OUTPUT normed — h = x + RMSNorm(mix(x)), y = h +
RMSNorm(W_down(silu(W_gate h) * W_up h)) — a final RMS norm, an untied head.
Float32, `jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching, no chunks: one whole-sequence pass, layer by layer.

`linear_attention` (H heads, keys of K, values of V, kernel W): [q | k | v] =
x W_qkv (widths H K | H K | H V), z = x W_z, [a | b] = x W_ab; q, k, v each
through a causal depthwise convolution over time (W - 1 zero rows in front, no
bias) and SiLU; per head q <- q / sqrt(|q|^2 + 1e-6) K^-1/2, k <- k /
sqrt(|k|^2 + 1e-6); beta = 2 sigmoid(b) (1 sigmoid(b) without
`linear_allow_neg_eigval`); alpha = exp(-exp(A_log) softplus(a + dt_bias)).
Per head, TOKEN BY TOKEN (`jax.lax.scan` over the positions), S_0 = 0:

    S <- alpha_t S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

then per head RMSNorm_w(o_t) over the V channels (one weight of V for all
heads) times silu(z_t), and W_o.
`full_attention`: q, k, v = x W_q, x W_k, x W_v; an RMS norm with weight over
the WHOLE width of q and of k, then the split into heads; causal
softmax(q k^T / sqrt(d)) v, W_o; NO rotary embedding (`rope_theta` null) and
no grouping beyond `num_key_value_heads`.

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
KIND of mixer in the kind's own order — `lin_*`, `wq`.. — and one stack of
feed-forwards over all layers; the three projections in front of the
convolution stored as one matrix `lin_wqkv`, the two scalar ones as
`lin_wab`). Where the block's norms sit, the convolution's missing bias, the
1e-6 under the L2 norm and the norms over q's and k's whole width are the
configuration file's `assumed`: the catalog carries `config.json`, not the
modelling code. Nothing is routed: no `FOLLOWS`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"

_LIN = ("lin_wqkv", "lin_wz", "lin_wab", "lin_conv_w", "lin_a_log",
        "lin_dt_bias", "lin_gate_norm", "lin_wo", "lin_ln_mix")
_ATTN = ("wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln_attn")
_MLP = ("wg", "wu", "wd", "ln_mlp")


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=("heads", "dk", "dv", "beta_scale", "eps"))
def linear_layer(x, l, wqkv, wz, wab, conv_w, a_log, dt_bias, gate_norm, wo,
                 ln_mix, *, heads, dk, dv, beta_scale, eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        qkv = x @ wqkv[l].astype(F32)
        z = (x @ wz[l].astype(F32)).reshape(t, heads, dv)
        ab = x @ wab[l].astype(F32)
        w = conv_w[l].astype(F32)  # [C, W], the last tap on the current row
        width = w.shape[-1]
        rows = jnp.concatenate([jnp.zeros((width - 1, qkv.shape[1]), F32), qkv])
        qkv = jax.nn.silu(sum(rows[j:j + t] * w[:, j] for j in range(width)))
        q = unit(qkv[:, :heads * dk].reshape(t, heads, dk)) * dk**-0.5
        k = unit(qkv[:, heads * dk:2 * heads * dk].reshape(t, heads, dk))
        v = qkv[:, 2 * heads * dk:].reshape(t, heads, dv)
        alpha = jnp.exp(-jnp.exp(a_log[l].astype(F32))
                        * jax.nn.softplus(ab[:, :heads]
                                          + dt_bias[l].astype(F32)))
        beta = beta_scale * jax.nn.sigmoid(ab[:, heads:])

        def token(s, inp):
            q_t, k_t, v_t, a_t, b_t = inp  # [H, K] x 2, [H, V], [H] x 2
            s = a_t[:, None, None] * s
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), F32),
                            (q, k, v, alpha, beta))
        o = dense.rms_norm(o, gate_norm[l], eps) * jax.nn.silu(z)
        out = o.reshape(t, heads * dv) @ wo[l].astype(F32)
        return x + dense.rms_norm(out, ln_mix[l], eps)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps"))
def attention_layer(x, l, wq, wk, wv, q_norm, k_norm, wo, ln_attn, *, heads,
                    kv_heads, head_dim, eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        q = dense.rms_norm(x @ wq[l].astype(F32), q_norm[l], eps)
        k = dense.rms_norm(x @ wk[l].astype(F32), k_norm[l], eps)
        q = q.reshape(t, heads, head_dim)
        k = jnp.repeat(k.reshape(t, kv_heads, head_dim), heads // kv_heads, 1)
        v = jnp.repeat((x @ wv[l].astype(F32)).reshape(t, kv_heads, head_dim),
                       heads // kv_heads, 1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                           -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        out = out.reshape(t, -1) @ wo[l].astype(F32)
        return x + dense.rms_norm(out, ln_attn[l], eps)


@partial(jax.jit, static_argnames=("eps",))
def feed_forward(x, l, wg, wu, wd, ln_mlp, *, eps):
    with jax.default_matmul_precision("highest"):
        return x + dense.rms_norm(dense.swiglu(x, wg[l], wu[l], wd[l]),
                                  ln_mlp[l], eps)


def forward(params: dict, hf: dict, ids) -> jnp.ndarray:
    """Logits [T, V] float32 of the token sequence `ids` [T]."""
    eps = float(hf.get("rms_norm_eps", 1e-6))
    heads = hf["num_attention_heads"]
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    seen = {LINEAR: 0, FULL: 0}
    for at, kind in enumerate(hf["layer_types"]):
        l = seen[kind]
        seen[kind] += 1
        if kind == LINEAR:
            x = linear_layer(
                x, l, *(params[n] for n in _LIN),
                heads=hf["linear_num_key_heads"],
                dk=hf["linear_key_head_dim"], dv=hf["linear_value_head_dim"],
                beta_scale=2.0 if hf.get("linear_allow_neg_eigval") else 1.0,
                eps=eps)
        elif kind == FULL:
            x = attention_layer(
                x, l, *(params[n] for n in _ATTN), heads=heads,
                kv_heads=hf.get("num_key_value_heads", heads),
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                eps=eps)
        else:
            raise ValueError(f"no layer type {kind!r} in this reference")
        x = feed_forward(x, at, *(params[n] for n in _MLP), eps=eps)
    return dense.unembed(x, params["ln_final"], params["lm_head"], eps=eps)
