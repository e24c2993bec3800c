"""Plain reference of the Kimi-Linear block (`model_type` `kimi_linear`:
moonshotai/Kimi-Linear-48B-A3B): pre-norm RMSNorm before each sub-layer, a
final norm, an untied head; by `linear_attn_config` (its layers count from
1) a KIMI DELTA ATTENTION mixer or latent attention WITHOUT rotary, then a
dense SwiGLU in the first `first_k_dense_replace` layers and a
sigmoid-routed mixture with a shared expert behind them. Float32,
`jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching, no chunks: one whole-sequence pass, layer by layer.

KDA (H heads, keys and values of D, W taps): [q | k | v] = h W_qkv, each
through a causal depthwise convolution over time (W - 1 zero rows in front,
no bias) and SiLU; per head q <- q / sqrt(|q|^2 + 1e-6) D^-1/2, k <- k /
sqrt(|k|^2 + 1e-6); g = -exp(A_log[h]) softplus((h W_fa) W_fb + dt_bias) in
R^{H x D}, a = exp(g) ONE NUMBER A KEY CHANNEL; b = sigmoid(h W_b) in R^H.
Per head, TOKEN BY TOKEN (`jax.lax.scan` over the positions, never the
chunked form), S_0 = 0:

    S <- Diag(a_t) S;  u = b_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

then per head RMSNorm_w(o_t) over the D channels (one weight of D for all
heads) times sigmoid((h W_ga) W_gb), and W_o.
Latent attention (`mla_use_nope`): q = h W_q -> H x [q_nope | q_pe]; h W_kva
-> [c | k_pe]; c <- RMSNorm(c); c W_kvb -> per head [k_nope | v]; score =
(q_nope . k_nope + q_pe . k_pe) / sqrt(Dn + Dr), causal softmax; NOTHING is
rotated.
Mixture: s = sigmoid(h W_r) over all `expert_parallel.experts` outputs;
chosen = top-k of s + b; w = s[chosen] / (sum + 1e-20) x
routed_scaling_factor; F(h) = sum over the chosen experts THIS CHIP HOLDS of
w_i SwiGLU_i(h) + SwiGLU_shared(h): an assignment of another chip's expert
adds nothing here (the configuration's `expert_parallel`), as in the
program.

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
RUN of like layers, `r<i>_`; q | k | v stored as one matrix, and so W_fa |
W_ga | W_b (`w_low`) and the shared expert's gate | up (`ws_gu`); W_kvb split
per head into `wk_b`, `wv_b`); what config.json does not say (no bias anywhere,
b not doubled, the L2 norm's 1e-6, the gate's sigmoid) is the configuration
file's `assumed`. One expert's weights are upcast at a time.

`FOLLOWS = "routing"` (benchmark/reference/moe.py says why).
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32
FOLLOWS = "routing"

_KDA = ("ln_mix", "wqkv", "conv_w", "w_low", "wf_b", "dt_bias", "a_log",
        "wg_b", "gate_norm", "wo_kda")
_MLA = ("ln_attn", "wq", "wkv_a", "ln_kv", "wk_b", "wv_b", "wo")
_DENSE = ("ln_mlp", "wg", "wu", "wd")
_MOE = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
        "ws_gu", "ws_down")


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def layer_plan(hf: dict) -> list[tuple[str, int, bool, bool]]:
    """(the run's prefix, the layer's row of it, is it KDA, is it routed) a
    layer, in order: runs of like layers under `r<i>_`."""
    lin = hf["linear_attn_config"]
    kda = set(lin["kda_layers"])  # counted from 1
    first = hf.get("first_k_dense_replace", 0)
    kinds = [(at + 1 in kda, at >= first)
             for at in range(hf["num_hidden_layers"])]
    plan = []
    for i, (kind, run) in enumerate(itertools.groupby(kinds)):
        plan += [(f"r{i}_", row, *kind) for row, _ in enumerate(run)]
    return plan


@partial(jax.jit, static_argnames=("heads", "d", "eps"))
def kda_mixer(x, l, ln_mix, wqkv, conv_w, w_low, wf_b, dt_bias, a_log, wg_b,
              gate_norm, wo, *, heads, d, eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln_mix[l], eps)
        qkv = h @ wqkv[l].astype(F32)
        w = conv_w[l].astype(F32)  # [C, W], the last tap on the current row
        width = w.shape[-1]
        rows = jnp.concatenate([jnp.zeros((width - 1, qkv.shape[1]), F32), qkv])
        qkv = jax.nn.silu(sum(rows[j:j + t] * w[:, j] for j in range(width)))
        q = unit(qkv[:, :heads * d].reshape(t, heads, d)) * d**-0.5
        k = unit(qkv[:, heads * d:2 * heads * d].reshape(t, heads, d))
        v = qkv[:, 2 * heads * d:].reshape(t, heads, d)
        # stored side by side: [W_fa | W_ga | W_b], widths d, d and H
        wf_a, wg_a, wb = jnp.split(w_low[l].astype(F32), (d, 2 * d), axis=-1)
        f = (h @ wf_a) @ wf_b[l].astype(F32)
        alpha = jnp.exp(-jnp.exp(a_log[l].astype(F32))[:, None]
                        * jax.nn.softplus(f + dt_bias[l].astype(F32))
                        .reshape(t, heads, d))  # [T, H, D]: a key channel's
        beta = jax.nn.sigmoid(h @ wb)  # not doubled

        def token(s, inp):
            q_t, k_t, v_t, a_t, b_t = inp  # [H, D] x 4, [H]
            s = a_t[:, :, None] * s  # Diag(a_t) S: a scale of S's rows
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), F32),
                            (q, k, v, alpha, beta))
        gate = jax.nn.sigmoid((h @ wg_a) @ wg_b[l].astype(F32))
        o = dense.rms_norm(o, gate_norm[l], eps) * gate.reshape(t, heads, d)
        return x + o.reshape(t, heads * d) @ wo[l].astype(F32)


@partial(jax.jit, static_argnames=("heads", "rank", "nope", "eps"))
def latent_mixer(x, l, ln_attn, wq, wkv_a, ln_kv, wk_b, wv_b, wo, *, heads,
                 rank, nope, eps):
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln_attn[l], eps)
        q = (h @ wq[l].astype(F32)).reshape(t, heads, -1)
        kv = h @ wkv_a[l].astype(F32)
        c = dense.rms_norm(kv[:, :rank], ln_kv[l], eps)
        k_pe = kv[:, rank:]  # [T, Dr], one head for all, NOT rotated
        k_nope = jnp.einsum("tc,hcd->thd", c, wk_b[l].astype(F32))
        v = jnp.einsum("tc,hcd->thd", c, wv_b[l].astype(F32))
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
                  + jnp.einsum("qhd,kd->hqk", q[..., nope:], k_pe)
                  ) / jnp.sqrt(F32(q.shape[-1]))
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores,
                           -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        return x + out.reshape(t, -1) @ wo[l].astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def dense_ffn(x, l, ln_mlp, wg, wu, wd, *, eps):
    with jax.default_matmul_precision("highest"):
        return x + dense.swiglu(dense.rms_norm(x, ln_mlp[l], eps), wg[l],
                                wu[l], wd[l])


@partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "first",
                                   "eps"))
def mixture(x, l, ln_mlp, router, router_bias, we_gate, we_up, we_down,
            ws_gu, ws_down, chosen=None, *, top_k, scale, normalize, first,
            eps):
    """`we_*` [run, held, ...]: the experts [first, first + held) of the
    router's outputs, one upcast at a time. Returns the layer's output and
    s + b [T, X]."""
    with jax.default_matmul_precision("highest"):
        h = dense.rms_norm(x, ln_mlp[l], eps)
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
        ws_gate, ws_up = jnp.split(ws_gu[l], 2, axis=-1)  # side by side
        shared = dense.swiglu(h, ws_gate, ws_up, ws_down[l])
        return x + routed + shared, biased


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm mixture layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    lin = hf["linear_attn_config"]
    share = hf.get("expert_parallel") or {}
    first = int(share.get("chip", 0)) * hf["num_experts"]
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    scores = []
    for prefix, row, is_kda, routed in layer_plan(hf):
        def leaves(names):
            return (params[prefix + n] for n in names)

        if is_kda:
            x = kda_mixer(x, row, *leaves(_KDA), heads=lin["num_heads"],
                          d=lin["head_dim"], eps=eps)
        else:
            x = latent_mixer(x, row, *leaves(_MLA),
                             heads=hf["num_attention_heads"],
                             rank=hf["kv_lora_rank"],
                             nope=hf["qk_nope_head_dim"], eps=eps)
        if not routed:
            x = dense_ffn(x, row, *leaves(_DENSE), eps=eps)
            continue
        x, biased = mixture(
            x, row, *leaves(_MOE),
            None if follow is None else jnp.asarray(follow[len(scores)],
                                                    jnp.int32),
            top_k=hf["num_experts_per_token"],
            scale=float(hf.get("routed_scaling_factor", 1.0)),
            normalize=bool(hf.get("moe_renormalize", True)), first=first,
            eps=eps)
        scores.append(biased)
    return (dense.unembed(x, params["ln_final"], params["lm_head"], eps=eps),
            jnp.stack(scores))
