"""Plain reference of a decoder block whose query, key and value projections
carry a bias (HF `modeling_qwen2`): the dense reference's block with `bq`,
`bk`, `bv` added before the heads are split and rotated. Float32,
`jax.default_matmul_precision("highest")`.

It lies beside the rehearsal's manifest and is named by its configuration's
file alone (`correctness.reference`): the proof that an architecture reaches
the harness as files, with no file of `benchmark/*.py` knowing its name.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference import dense

F32 = jnp.float32


def attention(h, wq, wk, wv, wo, bq, bk, bv, *, heads, kv_heads, head_dim,
              theta):
    t = h.shape[0]
    q = (h @ wq.astype(F32) + bq.astype(F32)).reshape(t, heads, head_dim)
    k = (h @ wk.astype(F32) + bk.astype(F32)).reshape(t, kv_heads, head_dim)
    v = (h @ wv.astype(F32) + bv.astype(F32)).reshape(t, kv_heads, head_dim)
    q, k = dense.rope(q, theta), dense.rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * head_dim) @ wo.astype(F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "eps"))
def layer(x, l, p, *, heads, kv_heads, head_dim, theta, eps):
    with jax.default_matmul_precision("highest"):
        x = x + attention(dense.rms_norm(x, p["ln_attn"][l], eps), p["wq"][l],
                          p["wk"][l], p["wv"][l], p["wo"][l], p["bq"][l],
                          p["bk"][l], p["bv"][l], heads=heads,
                          kv_heads=kv_heads, head_dim=head_dim, theta=theta)
        return x + dense.swiglu(dense.rms_norm(x, p["ln_mlp"][l], eps),
                                p["wg"][l], p["wu"][l], p["wd"][l])


def forward(params: dict, hf: dict, ids) -> jnp.ndarray:
    """Logits [T, V] float32 of the token sequence `ids` [T]."""
    d = dense.dims(hf)
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    layers = {n: params[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv",
                                     "wg", "wu", "wd", "ln_attn", "ln_mlp")}
    for l in range(hf["num_hidden_layers"]):
        x = layer(x, l, layers, **d)
    head = (params["embed"].T if hf.get("tie_word_embeddings")
            else params["lm_head"])
    return dense.unembed(x, params["ln_final"], head, eps=d["eps"])
