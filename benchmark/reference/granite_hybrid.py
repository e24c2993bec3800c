"""Plain reference of the Granite-4.0-H dense hybrid block (HF
`modeling_granitemoehybrid`, `model_type` `granitemoehybrid` with
`num_local_experts` 0: Granite-4.0-H-Micro; its state-space layer is Bamba's
Mamba-2 mixer). Float32, `jax.default_matmul_precision("highest")`, no cache,
no kernels, no batching, no chunks: one whole-sequence pass, layer by layer.

x0 = embed[ids] * embedding_multiplier. Layer l, by `layer_types[l]`:
x <- x + r mix_l(RMSNorm(x)), then x <- x + r mlp(RMSNorm(x)), r =
`residual_multiplier` on what each sub-layer GIVES. logits = RMSNorm(x)
embed^T / logits_scaling (`tie_word_embeddings`).

`mlp(h) = (silu(h W_g) * (h W_u)) W_d`: the published `input_linear`
[2E' | 2E'] is [W_g | W_u], `output_linear` W_d.
`mamba` (H heads of P channels, G groups, state N, kernel K): [z | xBC | dt]
= h W_in, widths H P | H P + 2 G N | H; xBC <- silu(conv(xBC) + b), a causal
depthwise convolution over time (K - 1 zero rows in front); [x | B | C] =
xBC; dt <- softplus(dt + dt_bias), A = -exp(A_log). Per head h of group
g = h // (H / G), TOKEN BY TOKEN (`jax.lax.scan` over the positions): S_t =
exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t + D_h x_t, S_0 = 0.
Then y <- RMSNorm over ALL H P channels of (y silu(z)), times a weight (the
published gated norm has no groups; at the published G = 1 a norm a group is
the same thing), and W_out.
`attention`: q, k, v = h W_q, h W_k, h W_v; causal softmax(q k^T
`attention_multiplier`) v, W_o — the multiplier in place of 1 / sqrt(d), and
NO rotary embedding (`position_embedding_type` "nope": `rope_theta` is read
by no layer).

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
RUN of like layers, `r0_*`, `r1_*`, .. in layer order; `input_linear` as its
two halves `wg`, `wu`); `time_step_limit` is (0, inf), the family's
default, so dt is not clamped; activations are float32 where the published
code runs bf16.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32

_SSM = ("ln_ssm", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
        "ssm_a_log", "ssm_d", "ln_gate", "ssm_out")
_ATTN = ("ln_attn", "wq", "wk", "wv", "wo")
_MLP = ("ln_mlp", "wg", "wu", "wd")


def _feed_forward(x, l, ln, wg, wu, wd, *, eps, residual):
    return x + residual * dense.swiglu(dense.rms_norm(x, ln[l], eps), wg[l],
                                       wu[l], wd[l])


@partial(jax.jit, static_argnames=("heads", "p", "groups", "n", "eps",
                                   "residual"))
def ssm_layer(x, l, ln, w_in, conv_w, conv_b, dt_bias, a_log, d, ln_gate,
              w_out, ln_mlp, wg, wu, wd, *, heads, p, groups, n, eps,
              residual):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        di, gn = heads * p, groups * n
        proj = dense.rms_norm(x, ln[l], eps) @ w_in[l].astype(F32)
        z, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * gn],
                      proj[:, 2 * di + 2 * gn:])
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
        y = dense.rms_norm(y.reshape(t, di) * jax.nn.silu(z), ln_gate[l], eps)
        x = x + residual * (y @ w_out[l].astype(F32))
        return _feed_forward(x, l, ln_mlp, wg, wu, wd, eps=eps,
                             residual=residual)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "scale",
                                   "eps", "residual"))
def attention_layer(x, l, ln, wq, wk, wv, wo, ln_mlp, wg, wu, wd, *, heads,
                    kv_heads, head_dim, scale, eps, residual):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln[l], eps)
        q = (h @ wq[l].astype(F32)).reshape(t, heads, head_dim)
        k = (h @ wk[l].astype(F32)).reshape(t, kv_heads, head_dim)
        v = (h @ wv[l].astype(F32)).reshape(t, kv_heads, head_dim)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                           -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + residual * (out.reshape(t, -1) @ wo[l].astype(F32))
        return _feed_forward(x, l, ln_mlp, wg, wu, wd, eps=eps,
                             residual=residual)


@partial(jax.jit, static_argnames=("eps", "scaling"))
def tied_head(x, ln_final, embed, *, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return (dense.rms_norm(x, ln_final, eps) @ embed.astype(F32).T
                ) / scaling


def forward(params: dict, hf: dict, ids) -> jnp.ndarray:
    """Logits [T, V] float32 of the token sequence `ids` [T]."""
    if not hf.get("tie_word_embeddings", True):
        raise ValueError("this reference ties the head to the embedding "
                         "table, as the family's dense models do")
    eps = float(hf.get("rms_norm_eps", 1e-5))
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    residual = float(hf.get("residual_multiplier", 1.0))
    x = (params["embed"][jnp.asarray(ids)].astype(F32)
         * float(hf.get("embedding_multiplier", 1.0)))
    if len(hf["layer_types"]) != hf["num_hidden_layers"]:
        raise ValueError(f"{len(hf['layer_types'])} layer_types for "
                         f"{hf['num_hidden_layers']} layers")
    layers = {
        "mamba": (_SSM, partial(
            ssm_layer, heads=hf["mamba_n_heads"], p=hf["mamba_d_head"],
            groups=hf["mamba_n_groups"], n=hf["mamba_d_state"])),
        "attention": (_ATTN, partial(
            attention_layer, heads=heads,
            kv_heads=hf["num_key_value_heads"], head_dim=head_dim,
            scale=float(hf.get("attention_multiplier", head_dim**-0.5)))),
    }
    # a run of like layers is a stack `r<i>_*` of its own, in layer order
    for i, (kind, run) in enumerate(itertools.groupby(hf["layer_types"])):
        if kind not in layers:
            raise ValueError(f"no layer kind {kind!r} in this reference")
        names, layer = layers[kind]
        stacks = [params[f"r{i}_{n}"] for n in names + _MLP]
        for l in range(len(list(run))):
            x = layer(x, l, *stacks, eps=eps, residual=residual)
    return tied_head(x, params["ln_final"], params["embed"], eps=eps,
                     scaling=float(hf.get("logits_scaling", 1.0)))
