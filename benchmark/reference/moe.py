"""Plain reference of the Mixtral block (HF `modeling_mixtral`): the dense
reference's attention, and in place of the MLP a top-k sparse mixture — the
router's logits over all experts, the k largest kept, a softmax over those
k, and the weighted sum of those experts' SwiGLU outputs. Float32,
`jax.default_matmul_precision("highest")`.

Exact: no capacity, no dropped tokens. The program's large-prefill path
(GShard capacity dispatch, `capacity_factor` 1.25) drops over-capacity tokens
by design and so departs from this; its exact path (decode, and prefills of
at most 4 x experts tokens) must agree. Every expert is applied to every
token and masked, expert by expert, so that one expert's float32 weights are
all that is held at a time.

`FOLLOWS` says what of the program's this reference can be told to follow:
its routing. A router's scores are often near ties (with random weights, in
about one layer in ten), a bf16 program then picks another expert than
float32 arithmetic would, and another expert is another function: the logits
part by tens of percent though neither side is wrong. So `forward(...,
follow=)` takes the experts the program chose, per layer and token, and
mixes THOSE experts — with weights from its OWN float32 router logits, by
the published rule (a softmax over the chosen ones). Whether the program's
choices were sound is a separate question, which `benchmark/correctness.py`
answers from the router logits this returns beside the logits. Without
`follow` the reference takes its own top-k, as it always did.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32
FOLLOWS = "routing"


def mixture(h, l, router, we_gate, we_up, we_down, chosen, *, top_k):
    """h [T, E]; router [E, X] of layer l; we_* the stacked [L, X, ...]
    expert weights, indexed one expert at a time; `chosen` [T, k] the
    experts to mix, or None for the router's own k largest. Returns the
    mixture's output and the router's logits [T, X]."""
    logits = h @ router.astype(F32)  # [T, X]
    if chosen is None:
        chosen = jax.lax.top_k(logits, top_k)[1]
    picked = jnp.take_along_axis(logits, chosen, axis=-1)
    weights = jax.nn.softmax(picked, axis=-1)  # over the selected only
    out = jnp.zeros_like(h)
    for e in range(router.shape[-1]):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # [T]
        out = out + w_e[:, None] * dense.swiglu(
            h, we_gate[l, e], we_up[l, e], we_down[l, e])
    return out, logits


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "eps", "top_k"))
def layer(x, l, wq, wk, wv, wo, router, we_gate, we_up, we_down, ln_attn,
          ln_mlp, chosen=None, *, heads, kv_heads, head_dim, theta, eps,
          top_k):
    with jax.default_matmul_precision("highest"):
        x = x + dense.attention(dense.rms_norm(x, ln_attn[l], eps), wq[l],
                                wk[l], wv[l], wo[l], heads=heads,
                                kv_heads=kv_heads, head_dim=head_dim,
                                theta=theta)
        out, logits = mixture(dense.rms_norm(x, ln_mlp[l], eps), l, router[l],
                              we_gate, we_up, we_down, chosen, top_k=top_k)
        return x + out, logits


def forward(params: dict, hf: dict, ids, follow=None):
    """Logits [T, V] of the token sequence `ids` [T], and the router's
    logits [L, T, X]. `follow` [L, T, k]: the experts to mix in place of
    the router's own top-k."""
    d = dense.dims(hf)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    router_logits = []
    for l in range(hf["num_hidden_layers"]):
        x, logits = layer(
            x, l, params["wq"], params["wk"], params["wv"], params["wo"],
            params["router"], params["we_gate"], params["we_up"],
            params["we_down"], params["ln_attn"], params["ln_mlp"],
            None if follow is None else jnp.asarray(follow[l], jnp.int32),
            **d, top_k=hf.get("num_experts_per_tok", 2))
        router_logits.append(logits)
    head = (params["embed"].T if hf.get("tie_word_embeddings")
            else params["lm_head"])
    return (dense.unembed(x, params["ln_final"], head, eps=d["eps"]),
            jnp.stack(router_logits))
