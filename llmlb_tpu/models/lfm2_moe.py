"""LFM2-MoE-class decoder (`model_type` `lfm2_moe`: LiquidAI/LFM2-24B-A2B): a
stack whose layers mix their tokens by a GATED SHORT CONVOLUTION or by
attention, in the order the config's `layer_types` spells (`conv`,
`full_attention`; three to one as published), under two leading dense
feed-forwards and then sigmoid-routed mixtures without a shared expert.
docs/lfm2-moe.md has the equations.

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them as `LayerGroup`s, two a layer (its mixer,
then its feed-forward), each reading its KIND's stack:

- `conv` (`c_` stacks): `[B | C | u] = norm(x) W_in`, `z = B u`, a causal
  depthwise convolution of `conv_L_cache` taps over `z` with NO bias and NO
  activation behind it (ops/ssm.causal_conv, told so), `y = C conv`,
  `x + y W_out`. It is a group's `mixer`, and what a sequence carries from
  call to call is the last `conv_L_cache - 1` rows of `z`, per SLOT beside
  the page pool (llama.StatePool): `cache_k.state` [n_conv, slots, 2,
  hidden] in the activations' type — the first such state that is no
  recurrence: nothing is accumulated, so nothing is float32, and
  `cache_v.state` holds nothing ([n_conv, slots, 0, hidden]). Prefill
  starts from zeros and leaves the rows that end at the prompt's last
  token; extend reads its slot's, convolves on, writes back (a chunk that
  starts at 0 starts from zeros); decode moves the rows of the `live` rows
  and no other.
- `full_attention` (`a_` stacks): grouped-query attention, each head of q
  and of k RMS-normed with a learnt weight BEFORE the half-split rotary
  embedding (sdar_moe's block), over the page pool of the attention layers
  alone, its heads of 64 stored two to a 128-lane row as
  granite_hybrid's are (`cache_k.pages` [n_A, P, PS, K / 2, 128],
  `pool_pack`, docs/kv-cache.md) — the first packed pool whose keys are
  rotated, which changes nothing: a key is rotated before it is packed.
- The feed-forward of layer l < `num_dense_layers` is a dense SwiGLU
  (`dense_` stacks); behind them ops/moe.py's routed layer with
  `sigmoid_bias_routing` (the choice by score + `expert_bias`, the weights
  the unbiased scores of the chosen over their sum + 1e-6, times
  `routed_scaling_factor`), three-matrix SwiGLU experts, every expert held.

Not served, each refused by name: `conv_bias`, a `layer_types` entry of
another kind, a scaled rotary embedding, `use_expert_bias` false;
speculative decoding (`verify_step_paged` is absent: a rejected draft would
leave the carried rows moved on), an int8 page pool, KV on the wire, int8
weights and LoRA pools; the engine refuses the prefix cache, the offload
tier and the split role for a family with state per slot (scheduler.py).

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/afmoe.py's do: the step's counters, or under the static
`routing=True` what the routers decided.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.models import stacks
from llmlb_tpu.models.deepseek_v3 import (
    EXPERT_LOAD_COUNTERS,
    LOAD_BUCKETS,
    _extra as _routed_extra,
)
from llmlb_tpu.models.family import Family, StepCounter
from llmlb_tpu.models.granite_hybrid import _attention as _packed_attention
from llmlb_tpu.models.llama import (
    LayerGroup,
    LlamaConfig,
    StatePool,
    StateRows,
    _decode_paged_impl,
    _default_mlp_fn,
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj,
    _qkv,
    shard_rules_for,
)
from llmlb_tpu.ops import moe, ssm
from llmlb_tpu.ops.attention import lane_pack, pack_kv
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.rope import apply_rope
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

CONV, ATTENTION = "conv", "full_attention"  # `layer_types`
ROUTE_EPS = 1e-6  # the family's own, under the chosen scores' sum


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(LlamaConfig):
    layer_types: tuple[str, ...] = (CONV, CONV, ATTENTION, CONV)
    conv_taps: int = 3  # `conv_L_cache`: the position itself and 2 before it
    num_dense_layers: int = 2  # leading layers with a dense feed-forward
    num_experts: int = 64
    experts_per_token: int = 4
    moe_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def num_moe_layers(self) -> int:
        return max(0, self.num_layers - self.num_dense_layers)

    @property
    def pool_pack(self) -> int:
        """KV heads side by side in a row of the page pool
        (ops/attention.lane_pack): 2 at the published 8 heads of 64."""
        return lane_pack(self.num_kv_heads, self.head_dim_)

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "Lfm2MoeConfig":
        """Build from a published `config.json`. What this family does not
        compute is refused by name. The rotary embedding's base is
        `rope_parameters.rope_theta` where the file nests it, `rope_theta`
        where it does not."""
        kinds = tuple(hf["layer_types"])
        rope = hf.get("rope_parameters") or {}
        scaling = hf.get("rope_scaling") or {}
        unsupported = {
            "layer_types": (bool(set(kinds) - {CONV, ATTENTION})
                            or len(kinds) != hf["num_hidden_layers"]),
            "conv_bias": bool(hf.get("conv_bias")),
            "conv_L_cache": int(hf.get("conv_L_cache", 3)) < 2,
            "rope_parameters":
                rope.get("rope_type", "default") != "default",
            "rope_scaling": scaling.get(
                "rope_type", scaling.get("type", "default")) != "default",
            "use_expert_bias": not hf.get("use_expert_bias", True),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"lfm2_moe config key(s) {bad} = {[hf.get(k) for k in bad]} "
                "are not supported by models/lfm2_moe.py; refusing to serve "
                "wrong logits")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=len(kinds),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(rope.get("rope_theta",
                                      hf.get("rope_theta", 1000000.0))),
            rms_eps=hf.get("norm_eps", 1e-5),
            tie_word_embeddings=bool(hf.get(
                "tie_word_embeddings", hf.get("tie_embedding", True))),
            max_position_embeddings=hf.get("max_position_embeddings", 128000),
            dtype=dtype,
            layer_types=kinds,
            conv_taps=int(hf.get("conv_L_cache", 3)),
            num_dense_layers=min(len(kinds),
                                 int(hf.get("num_dense_layers", 0))),
            num_experts=hf["num_experts"],
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        )


# ---------------------------------------------------------------------------
# Params: one stack a kind of mixer and a kind of feed-forward
# ---------------------------------------------------------------------------

C, A, DENSE = "c_", "a_", "dense_"  # the stacks' prefixes; the mixtures': ""
_CONV = ("ln_conv", "conv_in", "conv_w", "conv_out")
_ATTN = ("ln_attn", "wq", "wk", "wv", "q_norm", "k_norm", "wo")
_DENSE_MLP = ("ln_mlp", "wg", "wu", "wd")
_MOE_MLP = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down")
_EXPERTS = ("we_gate", "we_up", "we_down")


def _layer_shapes(cfg: Lfm2MoeConfig) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, d = cfg.hidden_size, cfg.head_dim_
    hd, kd = cfg.num_heads * d, cfg.num_kv_heads * d
    f, x, fm = (cfg.intermediate_size, cfg.num_experts,
                cfg.moe_intermediate_size)
    return {
        "ln_conv": ((e,), 0), "conv_in": ((e, 3 * e), e),
        "conv_w": ((e, cfg.conv_taps), 0), "conv_out": ((e, e), e),
        "ln_attn": ((e,), 0), "wq": ((e, hd), e), "wk": ((e, kd), e),
        "wv": ((e, kd), e), "wo": ((hd, e), hd),
        "q_norm": ((d,), 0), "k_norm": ((d,), 0),
        "ln_mlp": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, x), e), "router_bias": ((x,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
    }


def _leaves(cfg: Lfm2MoeConfig) -> list[stacks.Leaf]:
    """Every stacked leaf the config calls for."""
    dense = cfg.num_layers - cfg.num_moe_layers
    return stacks.stack_leaves(_layer_shapes(cfg), [
        (C, _CONV, cfg.layers_of(CONV)), (A, _ATTN, cfg.layers_of(ATTENTION)),
        (DENSE, _DENSE_MLP, dense), ("", _MOE_MLP, cfg.num_moe_layers)])


def seeded_vector(cfg, name: str, k, shape):
    """A seeded leaf that is no matrix: the convolution's taps uniform
    within +-taps^-0.5 (a Conv1d's own initialisation); the router's choice
    bias a seeded normal of sd 0.02 in float32, NOT zero
    (deepseek_v3.init_params says why); the norms ones."""
    if name == "conv_w":
        bound = cfg.conv_taps**-0.5
        return jax.random.uniform(k, shape, F32, -bound, bound
                                  ).astype(cfg.dtype)
    return stacks.seeded_bias(0.02)(cfg, name, k, shape)


def init_params(cfg: Lfm2MoeConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): matrices normal x fan_in^-0.5, the rest by
    `seeded_vector`. The head is the embedding table when the config ties
    them."""
    return stacks.init_params(cfg, key, _leaves(cfg), seeded_vector)


def param_logical_axes(cfg: Lfm2MoeConfig) -> dict[str, tuple]:
    """Attention, the dense feed-forward and the experts shard as in the
    other families; the convolution's projections replicate (`conv_in`'s
    output is three parts that meet element by element)."""
    layer = {**stacks.GQA_AXES, **stacks.MLP_AXES, **stacks.EXPERT_AXES}
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: Lfm2MoeConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: pages of the attention layers, two rows a slot of the conv layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: Lfm2MoeConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: K (V) pages of the attention layers [n_A, P, PS,
    K / f, f D] — `f` = `pool_pack` KV heads side by side in a row — and per
    slot, in cache_k alone, the rows of `z` the convolution looks back on
    [n_conv, slots, taps - 1, hidden] (cache_v's state has no row). Page 0
    is the trash page; the rows have none (a row that does not advance is
    masked). `num_slots` 1 serves a caller with one row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    f = cfg.pool_pack
    pages = (cfg.layers_of(ATTENTION), num_pages, page_size,
             cfg.num_kv_heads // f, f * cfg.head_dim_)
    rows = (cfg.layers_of(CONV), num_slots, cfg.conv_taps - 1,
            cfg.hidden_size)
    return (StatePool(jnp.zeros(pages, dtype), jnp.zeros(rows, dtype)),
            StatePool(jnp.zeros(pages, dtype),
                      jnp.zeros((*rows[:2], 0, rows[3]), dtype)))


def kv_pages_shardings(cfg: Lfm2MoeConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Pages as granite_hybrid's, their packed rows split over tp where they
    divide; the carried rows replicate (param_logical_axes)."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    rows = cfg.num_kv_heads // cfg.pool_pack
    pages = logical_to_sharding(
        mesh, rules, "layers", None, "seq",
        "kv_heads" if rows % mesh.shape["tp"] == 0 else None, "head_dim")
    conv = logical_to_sharding(mesh, rules, "layers", None, None, None)
    return (StatePool(pages, conv), StatePool(pages, conv))


def kv_pool_layers(cfg: Lfm2MoeConfig) -> int:
    """Layers of the page pool: the attention layers alone."""
    return cfg.layers_of(ATTENTION)


def kv_token_layer_bytes(cfg: Lfm2MoeConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool (K and V of
    every kv head); a conv layer leaves nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return (2 * cfg.num_kv_heads * cfg.head_dim_
            * jnp.dtype(cfg.dtype).itemsize)


def state_slot_bytes(cfg: Lfm2MoeConfig) -> int:
    """HBM bytes one slot holds beside its pages, whatever its context: the
    carried rows of every conv layer."""
    return (cfg.layers_of(CONV) * (cfg.conv_taps - 1) * cfg.hidden_size
            * jnp.dtype(cfg.dtype).itemsize)


def kv_wire_cell(cfg: Lfm2MoeConfig) -> None:
    """Nothing ships: the carried rows have no KVSH wire form, and pages
    without them are a quarter of the mixers. A handoff, resume or park
    replays its tokens instead."""
    return None


# ---------------------------------------------------------------------------
# The two mixers and the mixture
# ---------------------------------------------------------------------------

def _bcu(cfg: Lfm2MoeConfig, lp: Params, x):
    """The three equal parts of a conv layer's input projection, in the
    published order: the gate in front of the convolution, the gate behind
    it, what is convolved."""
    e = cfg.hidden_size
    bcu = rms_norm(x, lp["ln_conv"], cfg.rms_eps) @ lp["conv_in"]
    return bcu[..., :e], bcu[..., e:2 * e], bcu[..., 2 * e:]


def conv_mixer(cfg: Lfm2MoeConfig):
    """llama.LayerGroup's `mixer` for a gated short convolution: gates on
    both sides of ops/ssm.causal_conv, the rows it carries in
    `cache_k.state`."""

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b = x.shape[0]
        carried = cache_k.state  # [n_conv, slots, taps - 1, E]
        gate_in, gate_out, u = _bcu(cfg, lp, x)
        z = gate_in * u
        if rows.lens is None:  # decode: one token a row
            at = (layer,) if rows.slots is None else (layer, rows.slots)
            lens = jnp.ones((b,), jnp.int32)
            before = carried[at]
        else:
            at = (layer, jnp.arange(b) if rows.slots is None else rows.slots)
            lens = rows.lens
            fresh = (jnp.ones((b,), bool) if rows.start_pos is None
                     else rows.start_pos == 0)
            before = jnp.where(fresh[:, None, None], 0, carried[at])
        conv, after = ssm.causal_conv(z, before, lp["conv_w"], None, lens,
                                      act=None)
        if rows.live is not None:
            after = jnp.where(rows.live[:, None, None], after, before)
        carried = carried.at[at].set(after.astype(carried.dtype))
        return (x + (gate_out * conv) @ lp["conv_out"],
                cache_k._replace(state=carried), cache_v)

    return mixer


def _qk_norm_block(cfg: Lfm2MoeConfig, lp: Params, x, positions, inv_freq,
                   attn_fn, lora_idx=None):
    """llama._attn_block with each head of q and of k RMS-normed before the
    rotary embedding (sdar_moe's), the keys and values handed on as the
    pool's packed rows hold them (granite_hybrid's): the bodies write what
    the block hands them. Returns (x_out, k, v)."""
    b, t, _ = x.shape
    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    q, k, v = _qkv(cfg, lp, h, lora_idx)
    q = apply_rope(rms_norm(q, lp["q_norm"], cfg.rms_eps), positions,
                   inv_freq)
    k = apply_rope(rms_norm(k, lp["k_norm"], cfg.rms_eps), positions,
                   inv_freq)
    k, v = pack_kv(k, cfg.pool_pack), pack_kv(v, cfg.pool_pack)
    out = _proj(lp, "wo", attn_fn(q, k, v).reshape(b, t, -1), lora_idx)
    return x + out, k, v


def _attention(cfg: Lfm2MoeConfig):
    """granite_hybrid's attention over a pool of packed rows, with this
    family's block."""
    return _packed_attention(cfg)._replace(block=_qk_norm_block)


def _moe_mlp_fn(cfg: Lfm2MoeConfig, live=None):
    """llama's `mlp_fn` for a mixture layer: every routed expert by the
    sigmoid-and-bias rule at this family's epsilon, no shared expert, and
    as aux the layer's ops/moe.Routing. `live`: as deepseek_v3._moe_mlp_fn."""

    def fn(lp, h, token_valid, lora_idx=None):
        b, t, m = h.shape
        flat = h.reshape(b * t, m)
        if token_valid is None and live is not None:
            token_valid = jnp.broadcast_to(live[:, None], (b, t))
        logits = jnp.einsum("sm,mx->sx", flat, lp["router"],
                            preferred_element_type=F32)
        routed, routing = moe.moe_routed(
            flat, logits, lp["we_gate"], lp["we_up"], lp["we_down"],
            layer=lp["layer"],
            route=lambda r: moe.sigmoid_bias_routing(
                r, lp["router_bias"], cfg.experts_per_token,
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob, eps=ROUTE_EPS),
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
        )
        return routed.reshape(b, t, m), routing

    return fn


def _groups(cfg: Lfm2MoeConfig, live=None) -> list[LayerGroup]:
    """Two groups a layer, in `layer_types`' order: its mixer (an attention
    layer attends over the page pool, a conv layer is a mixer over its
    carried rows), then its feed-forward. A group's parameters and its
    place in its pool are its kind's next row."""
    moe_fn, mixer = _moe_mlp_fn(cfg, live), conv_mixer(cfg)
    seen = dict.fromkeys((CONV, ATTENTION, "dense", "moe"), 0)

    def take(kind):
        seen[kind] += 1
        return seen[kind] - 1

    groups = []
    for layer, kind in enumerate(cfg.layer_types):
        at = take(kind)
        if kind == CONV:
            groups.append(LayerGroup(
                _CONV, None, 1, C, start=at, pool_layer=at, attends=False,
                mixer=mixer, scope="short_conv"))
        else:
            groups.append(LayerGroup(
                _ATTN, None, 1, A, start=at, pool_layer=at,
                scope="global_attention"))
        if layer < cfg.num_dense_layers:
            groups.append(LayerGroup(
                _DENSE_MLP, _default_mlp_fn, 1, DENSE, start=take("dense"),
                attends=False, scope="dense_feed_forward"))
        else:
            groups.append(LayerGroup(
                _MOE_MLP, moe_fn, 1, whole=_EXPERTS, start=take("moe"),
                attends=False, scope="expert_mixture"))
    return groups


def step_counters(cfg: Lfm2MoeConfig) -> dict[str, tuple]:
    """The counters a call returns, by name and shape (all int32): the
    (row, layer) pairs whose carried rows it moved, the cells its
    attentions read (a live row's whole length in every attention layer),
    and deepseek_v3's expert load."""
    shapes: dict[str, tuple] = {"conv_rows": (), "global_kv_tokens": ()}
    if cfg.num_moe_layers:
        shapes.update({
            "experts_touched": (), "expert_assignments": (),
            "expert_load_max": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)})
    return shapes


def _extra(cfg: Lfm2MoeConfig, aux, shape, routing: bool, moved, kv_lens):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a group;
    the mixtures' are stacked here in layer order. `moved`: rows whose
    carried rows the call moved; `kv_lens` [B]: the cells each row's context
    holds once the call is done, 0 for a row not live."""
    # two groups a layer (_groups): its mixer, then its feed-forward
    routed = [flag for layer in range(cfg.num_layers)
              for flag in (False, layer >= cfg.num_dense_layers)]
    found = [a[0] if isinstance(a, list) else
             jax.tree.map(lambda v: v[0], a)
             for a, is_moe in zip(aux, routed) if is_moe]
    stacked = ([jax.tree.map(lambda *v: jnp.stack(v), *found)]
               if found else [None])
    out = _routed_extra(cfg, stacked, shape, routing)
    if routing:
        return out
    counters = dict(out[0]) if out else {}
    counters["conv_rows"] = cfg.layers_of(CONV) * jnp.asarray(moved,
                                                              jnp.int32)
    counters["global_kv_tokens"] = cfg.layers_of(ATTENTION) * jnp.sum(
        kv_lens, dtype=jnp.int32)
    return (counters,)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: Lfm2MoeConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False, slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose carried rows the rows write, from zeros."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_attention(cfg),
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, input_ids.shape[0], prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: Lfm2MoeConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False, slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' carried rows are read from their
    slots, convolved on from `start_pos` and left as the rows that end at
    the chunk's last true token."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_attention(cfg), slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, input_ids.shape[0],
        start_pos + chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: Lfm2MoeConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False,
                      slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged; a row that is not `live` keeps its carried
    rows bit for bit."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_attention(cfg),
        slot_ids=slot_ids)
    kv_lens, moved = seq_lens + 1, input_ids.shape[0]
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
        moved = jnp.sum(live, dtype=jnp.int32)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, (input_ids.shape[0], 1), routing, moved, kv_lens))


# It verifies no draft: a rejected token would leave the carried rows moved
# on, and there is no snapshot to roll back to. `slot_ids`: the rows' slots
# (default row i in slot i); `num_slots`: the slot count of the pool's
# state. `num_experts_per_tok`, `norm_topk_prob` and `routed_scaling_factor`
# are read and not listed: other mixtures' configs carry them, and a key
# listed here is refused of every family that does not list it (afmoe's
# FAMILY says so).
FAMILY = Family(
    name="lfm2_moe", config_class=Lfm2MoeConfig, model_types=("lfm2_moe",),
    mechanism_keys=("layer_types", "conv_L_cache", "conv_bias",
                    "num_dense_layers", "num_experts",
                    "moe_intermediate_size", "use_expert_bias"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="page pool beside a convolution's carried rows",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "conv_rows": StepCounter("sum", "conv_rows_total"),
        "global_kv_tokens": StepCounter("sum", "global_kv_tokens_total")},
    step_counters=step_counters, paged_keywords=("routing", "slot_ids"),
    keywords_of={"init_kv_pages": ("num_slots",)})
