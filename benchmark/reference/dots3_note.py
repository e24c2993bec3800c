"""Plain reference of the dots3-note block (`model_type` `dots3_note`, the
language model of dots-studio/dots3-note-prev): latent attention that
attends only over the cells a learned indexer picks in its FULL layers, latent
attention of other sizes over a sliding window in the others, a sigmoid gate
a head on both, one dense feed-forward and then sigmoid-routed mixtures with
a shared expert. Float32, `jax.default_matmul_precision("highest")`, no
cache, no kernels, no batching: one whole-sequence causal pass, a sub-layer
at a time, the attention's queries a BLOCK at a time (`QUERY_BLOCK`), so that
nothing of queries x heads x context is held whole beside the weights.

Pre-norm RMSNorm (eps `rms_norm_eps`) before each sub-layer, a final norm,
an untied head, no biases. With h = norm(x_t), E the hidden size:

FULL layer (`layer_types[l] == "full_attention"`), H heads:
    c_q = RMSNorm(h W_qa) sqrt(E / q_lora_rank);  q = c_q W_qb -> H x [nope|rope]
    [c | k_r] = h W_kva;  c <- RMSNorm(c) sqrt(E / kv_lora_rank)   (k_r unscaled)
    [k_nope | v]_h = c W_kvb,h;  RoPE on PAIRS (2i, 2i+1) of q_rope and k_r, base
    `rope_theta`;  score = (q_nope . k_nope + q_rope . k_r) / sqrt(Dn + Dr)
  the indexer (DeepSeek-V3.2's):
    q^I = c_q W^I_q -> Hi x Di;  k^I = LayerNorm(h W^I_k) (weight, bias, eps 1e-6)
    RoPE, same base, in SPLIT HALVES on the first Dr numbers of q^I and k^I
    w = h W^I_w Hi^-1/2 Di^-1/2;  I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])
    S_t = the `index_topk` positions s <= t of largest I[t, s], ties to the
          LOWER position; all of them while t < index_topk
  softmax over S_t alone;  o_h <- sigmoid(h W_g)_h o_h;  out = W_o concat_h o_h.
SLIDING layer: the same block without the indexer at the `swa_*` sizes and
    base `swa_rope_theta`, over positions t - (W - 1) .. t, W =
    `sliding_window_size`.
MIXTURE (layers >= `first_k_dense_replace`): s = sigmoid(h W_r) over all X
    router outputs in float32; chosen = top-k of s + b; weights s[chosen] over
    their sum (`norm_topk_prob`) times `routed_scaling_factor`; plus one shared
    SwiGLU. THE SHARE: this is one chip of a deployment whose chips share each
    layer's experts (`expert_parallel`: `chip` of `chips`, `n_routed_experts`
    experts each of the router's `experts`): of the chosen, the experts of
    this chip's range are computed and added, the others are the other
    chips' and add nothing here. With every chip's held part summed and the
    shared expert counted once the layer is the published one
    (tests/engine/test_sparse_family.py holds that).

Departures from the published model: weights are the program's random bf16
values upcast to float32, read by the program's names and layouts (a stack a
run of like layers, `r<i>_`; `W_kvb` split per head into `wk_b` and `wv_b`;
the shared expert's gate and up side by side, `ws_gu`); the reference
implementation's Hadamard rotation of q^I and k^I (orthogonal: no score
changes) and its FP8 storage of k^I are left out; one expert's weights are
upcast at a time. The vocabulary is the configuration's (a slice).

`FOLLOWS = "routing"` (benchmark/reference/moe.py says why): `forward(...,
follow=)` mixes the experts the program chose and returns beside the logits
s + b over all X. THE SELECTION is the reference's own unless
`follow_cells` [n_F, T, T] (bool, row t the cells query t attends over) is
given: benchmark/correctness.check cannot hand it over, so in `correct` a
cell at the 2,048th place decided the other way by bf16 rounding is part of
the reading; benchmark/check_sparse.py hands it over and reports both.
`observe`, a dict, is filled with the reference's own index scores
("index_scores": a [T, T] array a full layer) and selection ("selected").
"""

from __future__ import annotations

import itertools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense
from benchmark.reference.deepseek_v3 import rope_pairs

F32 = jnp.float32
FOLLOWS = "routing"
QUERY_BLOCK = 256  # queries whose scores over the whole context are held

_MLA = ("ln_attn", "wq_a", "ln_q", "wq_b", "wkv_a", "ln_kv", "wk_b", "wv_b",
        "w_gate", "wo")
_INDEX = ("wi_q", "wi_k", "ln_ik", "ln_ik_bias", "wi_w")
_DENSE = ("ln_mlp", "wg", "wu", "wd")
_MOE = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
        "ws_gu", "ws_down")


def layer_plan(hf: dict) -> list[tuple[str, int, bool, bool]]:
    """(the run's prefix, the layer's row of it, is it a full layer, is it
    routed) a layer, in order: runs of like layers under `r<i>_`."""
    first = hf.get("first_k_dense_replace", 0)
    kinds = [(kind == "full_attention", at >= first)
             for at, kind in enumerate(hf["layer_types"])]
    plan = []
    for i, (kind, run) in enumerate(itertools.groupby(kinds)):
        plan += [(f"r{i}_", row, *kind) for row, _ in enumerate(run)]
    return plan


def rope_halves(x, theta, width):
    """x [T, H, D]: RoPE on its first `width` numbers, pair i of them
    (x[i], x[i + width/2]); the rest as they are. Position = row index."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=F32) / width))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., width:]], axis=-1)


def query_blocks(t: int) -> int:
    """The block the queries are taken in: QUERY_BLOCK where it divides the
    sequence, else the largest divisor below it."""
    return next(b for b in range(min(QUERY_BLOCK, t), 0, -1) if t % b == 0)


def select(scores, at, top_k: int):
    """scores [Q, T] of the queries at positions `at` [Q]: [Q, T] bool, the
    `top_k` cells s <= at of largest score, ties to the lower position (a
    stable sort of the negated scores), all of them where there are no more
    than top_k."""
    cell = jnp.arange(scores.shape[1])
    causal = cell[None, :] <= at[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)  # a cell's place in the order
    return causal & (rank < top_k)


@partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rope", "theta", "eps", "q_scale", "kv_scale",
    "window", "top_k", "index_heads", "block"))
def latent_mixer(x, l, ln_attn, wq_a, ln_q, wq_b, wkv_a, ln_kv, wk_b, wv_b,
                 w_gate, wo, index=None, cells=None, *, heads, rank, nope,
                 rope, theta, eps, q_scale, kv_scale, window=0, top_k=0,
                 index_heads=0, block):
    """x + gate . attention(norm(x)) with layer l's weights of a run's
    stacks. `window` > 0: a sliding layer. `index` (a full layer): the
    indexer's five leaves; `cells` [T, T]: a selection to follow in place of
    the indexer's own. Returns (x_out, index scores [T, T] or None, the
    selection [T, T] or None)."""
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h = dense.rms_norm(x, ln_attn[l], eps)
        c_q = dense.rms_norm(h @ wq_a[l].astype(F32), ln_q[l], eps) * q_scale
        q = (c_q @ wq_b[l].astype(F32)).reshape(t, heads, nope + rope)
        q_nope, q_rope = q[..., :nope], rope_pairs(q[..., nope:], theta)
        kv = h @ wkv_a[l].astype(F32)
        c = dense.rms_norm(kv[:, :rank], ln_kv[l], eps) * kv_scale
        k_rope = rope_pairs(kv[:, None, rank:], theta)[:, 0]  # unscaled
        k_nope = jnp.einsum("tc,hcd->thd", c, wk_b[l].astype(F32))
        v = jnp.einsum("tc,hcd->thd", c, wv_b[l].astype(F32))
        if index is not None:
            wi_q, wi_k, ln_ik, ln_ik_bias, wi_w = (w[l].astype(F32)
                                                   for w in index)
            di = wi_k.shape[-1]
            q_i = rope_halves((c_q @ wi_q).reshape(t, index_heads, di),
                              theta, rope)
            k_i = h @ wi_k
            k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
            k_i = k_i * jax.lax.rsqrt(
                jnp.mean(k_i * k_i, axis=-1, keepdims=True) + 1e-6)
            k_i = rope_halves((k_i * ln_ik + ln_ik_bias)[:, None], theta,
                              rope)[:, 0]
            w_i = (h @ wi_w) * (index_heads * di) ** -0.5
        cell = jnp.arange(t)

        def queries(at):  # [Q] positions of a block of queries
            seen = cell[None, :] <= at[:, None]
            scored = chosen = None
            if window:
                seen &= at[:, None] - cell[None, :] < window
            if index is not None:
                scored = jnp.einsum(
                    "qj,jqk->qk", w_i[at], jax.nn.relu(jnp.einsum(
                        "qjd,kd->jqk", q_i[at], k_i)))
                chosen = (select(scored, at, top_k) if cells is None
                          else cells[at])
                seen &= chosen
            scores = (jnp.einsum("qhd,khd->hqk", q_nope[at], k_nope)
                      + jnp.einsum("qhd,kd->hqk", q_rope[at], k_rope)
                      ) / math.sqrt(nope + rope)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                             v)
            return out, scored, chosen

        out, scored, chosen = jax.lax.map(
            queries, cell.reshape(t // block, block))
        out = out.reshape(t, heads, -1)
        out = out * jax.nn.sigmoid(h @ w_gate[l].astype(F32))[:, :, None]
        if index is not None:
            scored, chosen = scored.reshape(t, t), chosen.reshape(t, t)
        return x + out.reshape(t, -1) @ wo[l].astype(F32), scored, chosen


@partial(jax.jit, static_argnames=("eps",))
def dense_ffn(x, l, ln_mlp, wg, wu, wd, *, eps):
    with jax.default_matmul_precision("highest"):
        return x + dense.swiglu(dense.rms_norm(x, ln_mlp[l], eps), wg[l],
                                wu[l], wd[l])


@partial(jax.jit, static_argnames=("top_k", "scale", "normalize", "first",
                                   "eps", "shared"))
def mixture(x, l, ln_mlp, router, router_bias, we_gate, we_up, we_down,
            ws_gu, ws_down, chosen=None, *, top_k, scale, normalize, first,
            eps, shared=True):
    """`we_*` [run, held, ...]: the experts [first, first + held) of the
    router's outputs, one upcast at a time. `shared` False leaves the shared
    expert out (another chip's share, which counts it nowhere). Returns the
    layer's output and s + b [T, X]."""
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

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                              jnp.arange(we_up.shape[1]))
        if shared:
            ws_gate, ws_up = jnp.split(ws_gu[l], 2, axis=-1)  # side by side
            out = out + dense.swiglu(h, ws_gate, ws_up, ws_down[l])
        return x + out, biased


def mixer_dims(hf: dict, full: bool) -> dict:
    """The static sizes of a layer's attention, by its kind."""
    e = hf["hidden_size"]
    pre = "" if full else "swa_"
    scaled = bool(hf.get("apply_mla_qkv_lora_rescale"))

    def lora_scale(rank):
        return math.sqrt(e / rank) if scaled else 1.0

    d = {"heads": hf["num_attention_heads" if full
                     else "swa_num_attention_heads"],
         "rank": hf[pre + "kv_lora_rank"], "nope": hf[pre + "qk_nope_head_dim"],
         "rope": hf[pre + "qk_rope_head_dim"],
         "theta": float(hf[pre + "rope_theta"]),
         "eps": float(hf.get("rms_norm_eps", 1e-5)),
         "q_scale": lora_scale(hf[pre + "q_lora_rank"]),
         "kv_scale": lora_scale(hf[pre + "kv_lora_rank"])}
    if full:
        d.update(top_k=int(hf["index_topk"]),
                 index_heads=int(hf["index_n_heads"]))
    else:
        d.update(window=int(hf["sliding_window_size"]))
    return d


def forward(params: dict, hf: dict, ids, follow=None, follow_cells=None,
            observe: dict | None = None):
    """Logits [T, V] of the token sequence `ids` [T], and s + b [Lm, T, X]
    of the Lm mixture layers. `follow` [Lm, T, k]: the experts to mix in
    place of the rule's own top-k; `follow_cells` [n_F, T, T] bool: the
    cells a full layer's queries attend over in place of the indexer's own
    choice; `observe`: see the module's docstring."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    share = hf.get("expert_parallel") or {}
    first = int(share.get("chip", 0)) * hf["n_routed_experts"]
    x = params["embed"][jnp.asarray(ids)].astype(F32)
    block = query_blocks(x.shape[0])
    scores, full_seen = [], 0
    for prefix, row, full, routed in layer_plan(hf):
        def leaves(names):
            return tuple(params[prefix + n] for n in names)

        cells = None
        if full and follow_cells is not None:
            cells = jnp.asarray(follow_cells[full_seen], bool)
        x, scored, chosen = latent_mixer(
            x, row, *leaves(_MLA), leaves(_INDEX) if full else None, cells,
            block=block, **mixer_dims(hf, full))
        if full:
            full_seen += 1
            if observe is not None:
                observe.setdefault("index_scores", []).append(
                    np.asarray(scored))
                observe.setdefault("selected", []).append(np.asarray(chosen))
        if not routed:
            x = dense_ffn(x, row, *leaves(_DENSE), eps=eps)
            continue
        x, biased = mixture(
            x, row, *leaves(_MOE),
            None if follow is None else jnp.asarray(follow[len(scores)],
                                                    jnp.int32),
            top_k=hf["num_experts_per_tok"],
            scale=float(hf.get("routed_scaling_factor", 1.0)),
            normalize=bool(hf.get("norm_topk_prob", True)), first=first,
            eps=eps)
        scores.append(biased)
    return (dense.unembed(x, params["ln_final"], params["lm_head"], eps=eps),
            jnp.stack(scores))
