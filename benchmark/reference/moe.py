"""Plain reference of the Mixtral block (HF `modeling_mixtral`): the dense
reference's attention, and in place of the MLP a top-2 sparse mixture — the
router's logits over all experts, the two largest kept, a softmax over those
two, and the weighted sum of the two experts' SwiGLU outputs. Float32,
`jax.default_matmul_precision("highest")`.

Exact: no capacity, no dropped tokens. The program's large-prefill path
(GShard capacity dispatch, `capacity_factor` 1.25) drops over-capacity tokens
by design and so departs from this; its exact path (decode, and prefills of
at most 4 x experts tokens) must agree. Every expert is applied to every
token and masked, expert by expert, so that one expert's float32 weights are
all that is held at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32


def top2_mixture(h, l, router, we_gate, we_up, we_down, *, top_k):
    """h [T, E]; router [E, X] of layer l; we_* the stacked [L, X, ...]
    expert weights, indexed one expert at a time."""
    logits = h @ router.astype(F32)  # [T, X]
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top_vals, axis=-1)  # over the selected only
    out = jnp.zeros_like(h)
    for e in range(router.shape[-1]):
        w_e = jnp.sum(jnp.where(top_idx == e, weights, 0.0), axis=-1)  # [T]
        out = out + w_e[:, None] * dense.swiglu(
            h, we_gate[l, e], we_up[l, e], we_down[l, e])
    return out


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "eps", "top_k"))
def layer(x, l, wq, wk, wv, wo, router, we_gate, we_up, we_down, ln_attn,
          ln_mlp, *, heads, kv_heads, head_dim, theta, eps, top_k):
    with jax.default_matmul_precision("highest"):
        x = x + dense.attention(dense.rms_norm(x, ln_attn[l], eps), wq[l],
                                wk[l], wv[l], wo[l], heads=heads,
                                kv_heads=kv_heads, head_dim=head_dim,
                                theta=theta)
        return x + top2_mixture(dense.rms_norm(x, ln_mlp[l], eps), l, router[l],
                                we_gate, we_up, we_down, top_k=top_k)


def forward(params: dict, hf: dict, ids) -> jnp.ndarray:
    d = dense.dims(hf)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    for l in range(hf["num_hidden_layers"]):
        x = layer(x, l, params["wq"], params["wk"], params["wv"], params["wo"],
                  params["router"], params["we_gate"], params["we_up"],
                  params["we_down"], params["ln_attn"], params["ln_mlp"], **d,
                  top_k=hf.get("num_experts_per_tok", 2))
    head = (params["embed"].T if hf.get("tie_word_embeddings")
            else params["lm_head"])
    return dense.unembed(x, params["ln_final"], head, eps=d["eps"])
