"""Mixture-of-experts layer: a routing rule, then grouped expert products
that drop nothing.

One layer at every size (prefill, extend, verify, decode):

- The family passes the ROUTING RULE, `route(router_logits f32 [S, E]) ->
  (weights [S, k] f32, chosen [S, k] int32, scores [S, E] f32)`; `scores` is
  the quantity whose top-k decided. Two rules live here: `top_k_routing`
  (Mixtral: the k largest logits, a softmax over those k) and
  `sigmoid_bias_routing` (DeepSeek-V3 / Kanana `noaux_tc`: sigmoid scores,
  the choice by score plus a per-expert bias, the weights the UNBIASED
  scores of the chosen, normalised and scaled); `softmax_bias_routing` is
  the same rule over a softmax of ALL the router's outputs (LongCat-Flash).
- The S x k assignments are sorted by expert and the three SwiGLU products
  run as grouped matmuls over the experts. On an unpartitioned TPU that is
  ONE route for every family: the experts arrive stacked over the layers
  and ops/pallas_moe.grouped_expert_matmul (`grouped_expert_matmul` in a
  trace) reads them in place; its work-list is the (expert, row tile) pairs
  that hold rows — an expert no token chose is not visited and its weights
  are not read. `jax.lax.ragged_dot` on the layer's slice is the fall-back:
  the CPU, a partitioned mesh, int8 experts. Every assignment is computed:
  there is no capacity and nothing is dropped, so a token's output does not
  depend on which other tokens share the batch.
- Static shapes: S x k rows whatever the routing; the raggedness is the
  run-time `group_sizes`. Padding tokens (`token_valid` false) are sorted
  behind every expert's rows and belong to no group: they cost no product
  and come out as zeros.
- Expert parallelism rides GSPMD as before: the expert-major weights carry
  the mesh `ep` axis and XLA places the collectives (it may gather the
  weights of a grouped product). A layer that is TOLD which experts its
  chip holds (`held=(first, count)`) routes over all of them and computes
  the assignments of its own: the others are another chip's, sorted behind
  every group as padding is, and add nothing here. The exchange between
  the chips of such a deployment is ROADMAP work.
- An expert is three matrices (SwiGLU: `w_gate`, `w_up`, `w_down`) or two
  (`w_gate` None: `act(x W_up) W_down`), through the same products.
- An expert may be NO PRODUCT at all: a router that scores more outputs
  than there are experts (`real=` of them are experts, the first ones) has
  ZERO-COMPUTE experts behind them, and an assignment of one returns the
  token itself times its weight. Such an assignment takes no row of a
  group, belongs to no chip (it is computed where the token lives, so it
  is never `elsewhere`) and costs one multiply-add of the token: it sorts
  behind every group as padding does and `w x` is added outside the
  products (`Routing.zero` counts them).

The reference has no MoE anywhere (it is a gateway; SURVEY.md §2.4 "no EP").
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from llmlb_tpu.ops.attention import _pallas_enabled


class Routing(NamedTuple):
    """What a routed layer decided, for the family's `routing=True` report
    and its load counters."""

    chosen: jnp.ndarray  # [S, k] int32 — the experts of each token
    scores: jnp.ndarray  # [S, E] f32 — the quantity whose top-k decided
    load: jnp.ndarray  # [E] int32 — assignments per expert, padding left out
    # [] int32 — assignments of valid tokens to experts this chip does not
    # hold (`held`); None where the layer holds them all
    elsewhere: jnp.ndarray | None = None
    # [] int32 — assignments of valid tokens to zero-compute experts
    # (`real`); None where every output of the router is an expert
    zero: jnp.ndarray | None = None


def top_k_routing(
    router_logits: jnp.ndarray,  # [S, E] fp32
    num_selected: int,
):
    """Top-k gate: returns (weights [S, k] fp32 normalized, indices [S, k])."""
    gate_vals, gate_idx = lax.top_k(router_logits, num_selected)
    # Mixtral normalizes softmax over the selected k (not over all experts).
    weights = jax.nn.softmax(gate_vals, axis=-1)
    return weights, gate_idx


def _biased_choice(scores, bias, num_selected: int, scale: float,
                   normalize: bool, eps: float = 1e-20):
    """The k largest of score + bias, weighed by their UNBIASED scores,
    over their sum + `eps` where normalised."""
    biased = scores + bias.astype(jnp.float32)
    _, idx = lax.top_k(biased, num_selected)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    return picked * scale, idx, biased


def sigmoid_bias_routing(
    router_logits: jnp.ndarray,  # [S, E] fp32
    bias: jnp.ndarray,  # [E] — e_score_correction_bias
    num_selected: int,
    *,
    scale: float = 1.0,
    normalize: bool = True,
    eps: float = 1e-20,
):
    """DeepSeek-V3's `noaux_tc` gate with one group: scores are sigmoids,
    the k experts are the top-k of score + bias, and the weights are the
    UNBIASED scores of those k, normalised to sum 1 (`norm_topk_prob`: over
    their sum + `eps`, the family's own: 1e-20 as DeepSeek-V3 has it, 1e-6
    in models/lfm2_moe.py) and scaled (`routed_scaling_factor`). Returns
    (weights [S, k], indices [S, k], biased scores [S, E])."""
    return _biased_choice(jax.nn.sigmoid(router_logits), bias, num_selected,
                          scale, normalize, eps)


def softmax_bias_routing(
    router_logits: jnp.ndarray,  # [S, X] fp32 — every output of the router
    bias: jnp.ndarray,  # [X] — e_score_correction_bias
    num_selected: int,
    *,
    scale: float = 1.0,
    normalize: bool = False,
):
    """LongCat-Flash's gate: scores are a softmax over ALL X outputs of the
    router (its zero-compute experts among them), the k chosen are the
    top-k of score + bias, the weights their UNBIASED scores, normalised
    only if asked (`norm_topk_prob`, false as published) and scaled
    (`routed_scaling_factor`). Returns as sigmoid_bias_routing."""
    return _biased_choice(jax.nn.softmax(router_logits, axis=-1), bias,
                          num_selected, scale, normalize)


def _grouped_mm(rows: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
                scale: jnp.ndarray | None, row_expert: jnp.ndarray):
    """rows [N, K] (sorted by expert) x w [E, K, O] -> [N, O] f32, row i
    through its own expert's matrix. Int8 weights (llmlb_tpu/quant) convert
    on the operand read and their per-output-channel scale [E, O] applies to
    the f32 OUTPUT, row by row — exact, the scale being constant along the
    contraction."""
    if scale is not None:
        w = w.astype(rows.dtype)
    y = lax.ragged_dot(rows, w, group_sizes,
                       preferred_element_type=jnp.float32)
    if scale is not None:
        y = y * scale[row_expert]
    return y


def moe_routed(
    x: jnp.ndarray,  # [S, M] tokens (S = B*T)
    router_logits: jnp.ndarray,  # [S, E]
    w_gate: jnp.ndarray | None,  # [E, M, F] gate proj (silu branch), or None
    w_up: jnp.ndarray,  # [E, M, F]
    w_down: jnp.ndarray,  # [E, F, M]
    *,
    route: Callable,  # router_logits f32 -> (weights, chosen[, scores])
    layer=None,  # int32 scalar: the w_* are stacked [L, E, ...], use layer's
    token_valid: jnp.ndarray | None = None,  # [S] bool — False = padding
    w_gate_scale: jnp.ndarray | None = None,  # [E, F] int8 dequant scales
    w_up_scale: jnp.ndarray | None = None,  # [E, F]
    w_down_scale: jnp.ndarray | None = None,  # [E, M]
    held: tuple[int, int] | None = None,  # (first, count) of the w_*'s experts
    real: int | None = None,  # of the router's E the first `real` are experts
    act: Callable = jax.nn.silu,
    up_transposed: bool = False,  # w_gate and w_up are [E, F, M]
) -> tuple[jnp.ndarray, Routing]:
    """SwiGLU experts (`w_gate` None: two-matrix experts, `act(x W_up)
    W_down`) mixed by `route`, every assignment computed. Returns ([S, M],
    Routing). Padding tokens come out as zeros and count in no expert's
    load.

    `held` says that the weights are experts [first, first + count) of the
    router's E: the route is taken over all E and the weights are those of
    all k chosen, an assignment of an expert outside the range adds nothing
    (it is the chip's that holds it) and `Routing.load` counts the held.
    `real` says that only the router's first `real` outputs are experts
    (those `held` divides): an assignment at or past it is a zero-compute
    expert's and adds `weight x token`, here, whatever is held.
    `up_transposed`: `w_up` (and `w_gate`) arrive output-major, [E, F, M], as
    a checkpoint stores a Linear (pallas_moe.grouped_expert_matmul says when
    that is the layout to store).

    With `layer`, the expert weights (and scales) arrive STACKED over the
    layers, [L, E, ...]. On an unpartitioned TPU the products then run in
    ops/pallas_moe.grouped_expert_matmul, which reads the stack in place at
    (layer, expert) and visits only the experts that hold rows; a kernel
    takes whole buffers, so handing one `w[layer]` copies the layer's
    experts on every call (three matrices of 128 experts: 1.2 GB a layer,
    more than half of a decode step on the chip; PERF.md section 6, PR 31).
    Elsewhere (the CPU, a partitioned mesh, int8 experts) the layer is
    sliced for `jax.lax.ragged_dot`."""
    s, m = x.shape
    stacked = layer is not None
    e = w_up.shape[1] if stacked else w_up.shape[0]
    logits = router_logits.astype(jnp.float32)
    weights, chosen, *rest = route(logits)
    scores = rest[0] if rest else logits
    k = chosen.shape[-1]

    # assignment j of token t is flat row t*k + j; padding sorts behind
    # every expert (key E) and lies outside every group
    flat_e = chosen.reshape(s * k).astype(jnp.int32)
    valid = None if token_valid is None else jnp.repeat(token_valid, k)

    def count(which):
        return jnp.sum(which if valid is None else which & valid,
                       dtype=jnp.int32)

    # a zero-compute expert's assignment: no row of any group
    is_zero = None if real is None else flat_e >= real
    zero = None if real is None else count(is_zero)
    outside, elsewhere = is_zero, None
    if held is not None:  # an absent expert's assignment sorts as padding
        flat_e = flat_e - held[0]
        outside = (flat_e < 0) | (flat_e >= e)  # the zero-compute among them
        elsewhere = count(outside if is_zero is None else outside & ~is_zero)
    if outside is not None:
        flat_e = jnp.where(outside, e, flat_e)
    if valid is not None:
        flat_e = jnp.where(valid, flat_e, e)
    order = jnp.argsort(flat_e, stable=True)
    row_expert = jnp.minimum(flat_e[order], e - 1)
    load = jnp.sum(flat_e[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :],
                   axis=0, dtype=jnp.int32)  # [E]
    rows = x[order // k]  # [S*k, M], sorted by expert

    quantized = w_up_scale is not None
    if stacked and not quantized and _pallas_enabled():
        from llmlb_tpu.ops import pallas_moe

        tile = pallas_moe.ROW_TILE
        pad = -(s * k) % tile
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        work = pallas_moe.group_work_list(load, rows=s * k + pad, tile=tile)

        def product(a, w, out_dtype, transposed=False):
            return pallas_moe.grouped_expert_matmul(
                a, w, layer, work, tile=tile, out_dtype=out_dtype,
                transposed=transposed)

        gate = None if w_gate is None else act(
            product(rows, w_gate, x.dtype, up_transposed))
        h = product(rows, w_up, x.dtype, up_transposed)
        h = act(h) if gate is None else gate * h
        y = product(h, w_down, jnp.float32)[:s * k]
    else:
        if stacked:
            w_gate, w_up, w_down, w_gate_scale, w_up_scale, w_down_scale = (
                None if w is None else w[layer]
                for w in (w_gate, w_up, w_down, w_gate_scale, w_up_scale,
                          w_down_scale))
        if up_transposed:
            w_gate, w_up = (None if w is None else jnp.swapaxes(w, -1, -2)
                            for w in (w_gate, w_up))
        gate = None if w_gate is None else act(
            _grouped_mm(rows, w_gate, load, w_gate_scale, row_expert)
            .astype(x.dtype))
        h = _grouped_mm(rows, w_up, load, w_up_scale,
                        row_expert).astype(x.dtype)
        h = act(h) if gate is None else gate * h
        y = _grouped_mm(h, w_down, load, w_down_scale, row_expert)  # [S*k, M]

    # back to (token, choice) order: a gather by the inverse permutation
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(s * k, dtype=order.dtype))
    y = y[inverse].reshape(s, k, m)
    if outside is not None:  # rows behind every group are unspecified
        y = jnp.where(outside.reshape(s, k, 1), 0.0, y)
    out = jnp.sum(y * weights[..., None], axis=1)
    if is_zero is not None:  # the identity experts: their weights x the token
        out = out + x.astype(jnp.float32) * jnp.sum(
            jnp.where(is_zero.reshape(s, k), weights, 0.0), axis=1)[:, None]
    if token_valid is not None:
        out = jnp.where(token_valid[:, None], out, 0.0)
    return out.astype(x.dtype), Routing(chosen, scores, load, elsewhere, zero)
