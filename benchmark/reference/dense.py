"""Plain reference of the Llama/Mistral decoder block (HF `modeling_mistral`:
pre-norm RMSNorm, grouped-query attention with rotate-half RoPE, SwiGLU MLP),
in float32 with `jax.default_matmul_precision("highest")` — on a TPU a float32
matmul otherwise runs in bf16 passes.

One whole-sequence causal forward pass: the logits at every position, which
is what prefill-then-decode through the paged cache must reproduce.

Departures from the published model: weights are the program's random bf16
values upcast to float32 (the values the engine serves); no sliding window
(Mistral-7B-Instruct-v0.2 has none).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def rope(x, theta):
    """x: [T, H, D]; HF rotate_half convention, position = row index."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, wq, wk, wv, wo, *, heads, kv_heads, head_dim, theta):
    t = h.shape[0]
    q = rope((h @ wq.astype(F32)).reshape(t, heads, head_dim), theta)
    k = rope((h @ wk.astype(F32)).reshape(t, kv_heads, head_dim), theta)
    v = (h @ wv.astype(F32)).reshape(t, kv_heads, head_dim)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * head_dim) @ wo.astype(F32)


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) @ wd.astype(F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "eps"))
def layer(x, l, wq, wk, wv, wo, wg, wu, wd, ln_attn, ln_mlp, *, heads,
          kv_heads, head_dim, theta, eps):
    """One decoder layer on x [T, E]; weights are the stacked [L, ...] arrays
    and `l` picks the layer."""
    with jax.default_matmul_precision("highest"):
        x = x + attention(rms_norm(x, ln_attn[l], eps), wq[l], wk[l], wv[l],
                          wo[l], heads=heads, kv_heads=kv_heads,
                          head_dim=head_dim, theta=theta)
        return x + swiglu(rms_norm(x, ln_mlp[l], eps), wg[l], wu[l], wd[l])


@partial(jax.jit, static_argnames=("eps",))
def unembed(x, ln_final, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, ln_final, eps) @ head.astype(F32)


def dims(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return {"heads": heads,
            "kv_heads": hf.get("num_key_value_heads", heads),
            "head_dim": hf.get("head_dim") or hf["hidden_size"] // heads,
            "theta": float(hf.get("rope_theta", 10000.0)),
            "eps": float(hf.get("rms_norm_eps", 1e-5))}


def forward(params: dict, hf: dict, ids) -> jnp.ndarray:
    """Logits [T, V] float32 of the token sequence `ids` [T]."""
    d = dims(hf)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    for l in range(hf["num_hidden_layers"]):
        x = layer(x, l, params["wq"], params["wk"], params["wv"], params["wo"],
                  params["wg"], params["wu"], params["wd"], params["ln_attn"],
                  params["ln_mlp"], **d)
    head = (params["embed"].T if hf.get("tie_word_embeddings")
            else params["lm_head"])
    return unembed(x, params["ln_final"], head, eps=d["eps"])
