"""AFMoE-class decoder (`model_type` `afmoe`: arcee-ai/Trinity-Mini): WINDOW
attention layers and GLOBAL ones in the order the config's `layer_types`
spells (three to one as published), every sub-layer normed on BOTH sides,
under two leading dense feed-forwards and then sigmoid-routed mixtures with
one shared expert (docs/afmoe.md has the equations).

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them as `LayerGroup`s, two a layer (its attention,
then its feed-forward), each reading its KIND's stack:

- Both attentions: `a = norm(x)`; 32 query heads of 128 on 4 KV heads, an
  RMS norm with weight over each head of q and of k, an OUTPUT GATE
  `sigmoid(a W_g)` on what the softmax mixed, `W_o`, and a second norm on
  the result before it joins the residual (`ln_attn`, `ln_attn_out`).
  Scores q.k / sqrt(128), no sink, no bias.
- GLOBAL (`g_` stacks): NO rotary embedding, the whole context. It is the
  bodies' GQA `Attention` with another `block`, over the PAGE pool of the
  global layers alone (`cache_k.pages` [n_G, P, PS, 4, 128]): block tables,
  page growth and paged_flash_decode as every GQA family has them.
- WINDOW (`w_` stacks): half-split rotary over the whole head, and position
  i sees j iff 0 <= i - j < `sliding_window`. It is a group's `mixer` whose
  state is a BAND of pages a slot beside the page pool (llama.StatePool):
  `cache_k.state` [n_W, (slots + 1) x R, BP, 4, 128], BP `band_page_size`
  and R = ceil(W / BP) + 1 pages a slot, position p in page (p // BP) mod R
  of its slot, cell p mod BP — a ring of R x BP cells that always holds the
  last W positions whole, whatever the context; the last slot's pages are
  the trash, where rows that are not `live` write. Decode writes its cell
  and attends in ONE call of paged_flash_decode (`paged_band_decode` in a
  trace) over the row's own R pages with a LOWER bound a row: the work-list
  names only the pages that hold positions > len - 1 - W, the oldest masked
  below the bound and the newest at the length
  (ops/attention.paged_band_decode). A window layer's decode therefore
  reads at most W + BP cells a row and ceil(len / BP) pages while len <= W.
  Prefill and extend attend over the band as it stood and the chunk's own
  keys under the window mask (mimo_v2._attend: a block of keys at a time),
  then write what of old and new is the last W (exact for a chunk longer
  than the band).
- The embedding is scaled by sqrt(hidden) (`mup_enabled`) where layer 0's
  mixer takes it: the bodies know no such factor, so a config whose first
  layer is not a window layer is refused while `mup_enabled` is true.
- The mixture: ops/moe.py's routed layer with `sigmoid_bias_routing` (the
  choice by score + bias, the weights the unbiased scores of the chosen
  over their sum + 1e-20 times `route_scale`), three-matrix SwiGLU experts
  and one shared expert. A chip may hold a SHARE of the experts
  (`expert_parallel` in the config, `held_experts`): the router scores all
  of them, the assignments of the others are another chip's.

Not served, each refused by name: speculative decoding (a rejected draft's
cell has overwritten the position a band before it), an int8 pool, KV on
the wire (`kv_wire_cell` None: a band has no wire form), int8 weights and
LoRA pools; the engine refuses the prefix cache, the offload tier and the
split role for a family with state per slot (scheduler.py).

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/mimo_v2.py's do: the step's counters, or under the
static `routing=True` what the routers decided.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from llmlb_tpu.models import stacks
from llmlb_tpu.models.deepseek_v3 import (
    EXPERT_LOAD_COUNTERS,
    LOAD_BUCKETS,
    _extra as _routed_extra,
    held_share,
)
from llmlb_tpu.models.family import Family, StepCounter
from llmlb_tpu.models.llama import (
    GQA_ATTENTION,
    LayerGroup,
    LlamaConfig,
    StatePool,
    StateRows,
    _decode_paged_impl,
    _default_mlp_fn,
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj,
    _proj_heads,
    shard_rules_for,
)
from llmlb_tpu.models.mimo_v2 import _attend, _own_blocks
from llmlb_tpu.ops import moe
from llmlb_tpu.ops.attention import (
    _traced,
    band_positions,
    paged_band_decode,
    paged_band_work,
)
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.rope import apply_rope, rope_frequencies
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

WINDOW, GLOBAL = "sliding_attention", "full_attention"  # `layer_types`


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(LlamaConfig):
    layer_types: tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL)
    sliding_window: int = 2048  # a position and the W - 1 before it
    band_page_size: int = 128  # cells of a page of the window layers' band
    num_dense_layers: int = 2  # leading layers with a dense feed-forward
    # the routed experts THIS CHIP holds (the weights' expert axis): all
    # the router scores, or a share of them [first_expert, + num_experts)
    num_experts: int = 128
    experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    router_experts: int = 128  # what the router scores
    first_expert: int = 0
    mup_enabled: bool = True  # the embedding times sqrt(hidden)

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the router's experts this chip holds."""
        return self.first_expert, self.num_experts

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def num_moe_layers(self) -> int:
        return max(0, self.num_layers - self.num_dense_layers)

    @property
    def band_pages(self) -> int:
        """R: the pages of a slot's band, a page more than the window
        fills, so that the last W positions are whole in it at any length."""
        return -(-self.sliding_window // self.band_page_size) + 1

    @property
    def band_cells(self) -> int:
        return self.band_pages * self.band_page_size

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "AfmoeConfig":
        """Build from a published `config.json`. What this family does not
        compute is refused by name. `expert_parallel` ({"chips", "chip",
        "experts"}) and `band_page_size` are the deployment's, not the
        checkpoint's: this chip holds `num_experts` of the router's
        `experts`, the `chip`-th such share."""
        kinds = tuple(hf["layer_types"])
        every = hf.get("global_attn_every_n_layers")
        held, experts, first = held_share(hf, "num_experts")
        unsupported = {
            "layer_types": bool(set(kinds) - {WINDOW, GLOBAL}),
            "num_hidden_layers": hf["num_hidden_layers"] != len(kinds),
            # the list decides; a period stated beside it has to agree
            "global_attn_every_n_layers": bool(every) and any(
                (kind == GLOBAL) != ((at + 1) % every == 0)
                for at, kind in enumerate(kinds)),
            "sliding_window": not hf.get("sliding_window"),
            "rope_scaling": hf.get("rope_scaling") is not None,
            "score_func": hf.get("score_func", "sigmoid") != "sigmoid",
            "n_group": hf.get("n_group", 1) != 1,
            "topk_group": hf.get("topk_group", 1) != 1,
            "num_expert_groups": hf.get("num_expert_groups", 1) != 1,
            "num_limited_groups": hf.get("num_limited_groups", 1) != 1,
            "num_shared_experts": hf.get("num_shared_experts", 1) < 1,
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(hf.get("attention_bias")),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
            # the factor rides layer 0's mixer (the module's docstring)
            "mup_enabled": bool(hf.get("mup_enabled")) and kinds[0] != WINDOW,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"afmoe config key(s) {bad} = {[hf.get(k) for k in bad]} "
                "are not supported by models/afmoe.py; refusing to serve "
                "wrong logits")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=len(kinds),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 131072),
            dtype=dtype,
            layer_types=kinds,
            sliding_window=int(hf["sliding_window"]),
            band_page_size=int(hf.get("band_page_size", 128)),
            num_dense_layers=min(len(kinds), int(hf.get("num_dense_layers", 0))),
            num_experts=held,
            router_experts=experts,
            first_expert=first,
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_shared_experts=int(hf.get("num_shared_experts", 1)),
            route_norm=bool(hf.get("route_norm", True)),
            route_scale=float(hf.get("route_scale") or 1.0),
            mup_enabled=bool(hf.get("mup_enabled")),
        )


# ---------------------------------------------------------------------------
# Params: one stack a kind of attention and a kind of feed-forward
# ---------------------------------------------------------------------------

G, W, DENSE = "g_", "w_", "dense_"  # the stacks' prefixes; the mixtures': ""
_ATTN = ("ln_attn", "wq", "wk", "wv", "wgate", "q_norm", "k_norm", "wo",
         "ln_attn_out")
_DENSE_MLP = ("ln_mlp", "wg", "wu", "wd", "ln_mlp_out")
_MOE_MLP = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
            "ws_gate", "ws_up", "ws_down", "ln_mlp_out")
_EXPERTS = ("we_gate", "we_up", "we_down")


def _layer_shapes(cfg: AfmoeConfig) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, d = cfg.hidden_size, cfg.head_dim_
    hd, kd = cfg.num_heads * d, cfg.num_kv_heads * d
    f, x, fm = (cfg.intermediate_size, cfg.num_experts,
                cfg.moe_intermediate_size)
    fs = fm * cfg.num_shared_experts
    return {
        "ln_attn": ((e,), 0), "ln_attn_out": ((e,), 0),
        "wq": ((e, hd), e), "wk": ((e, kd), e), "wv": ((e, kd), e),
        "wgate": ((e, hd), e), "wo": ((hd, e), hd),
        "q_norm": ((d,), 0), "k_norm": ((d,), 0),
        "ln_mlp": ((e,), 0), "ln_mlp_out": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, cfg.router_experts), e),
        "router_bias": ((cfg.router_experts,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
        "ws_gate": ((e, fs), e), "ws_up": ((e, fs), e), "ws_down": ((fs, e), fs),
    }


def _leaves(cfg: AfmoeConfig) -> list[stacks.Leaf]:
    """Every stacked leaf the config calls for: a stack a kind of attention
    and a kind of feed-forward."""
    dense = cfg.num_layers - cfg.num_moe_layers
    return stacks.stack_leaves(_layer_shapes(cfg), [
        (G, _ATTN, cfg.layers_of(GLOBAL)), (W, _ATTN, cfg.layers_of(WINDOW)),
        (DENSE, _DENSE_MLP, dense), ("", _MOE_MLP, cfg.num_moe_layers)])


def init_params(cfg: AfmoeConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): matrices normal x fan_in^-0.5, norms ones, the router's
    choice bias a seeded normal of sd 0.02 in float32 (deepseek_v3.
    init_params says why it is not zero)."""
    return stacks.init_params(cfg, key, _leaves(cfg), stacks.seeded_bias(0.02))


def param_logical_axes(cfg: AfmoeConfig) -> dict[str, tuple]:
    layer = {**stacks.GQA_AXES, **stacks.MLP_AXES, **stacks.EXPERT_AXES,
             "wgate": ("embed", "heads")}
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: AfmoeConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: pages of the global layers, a band of pages a slot of the window
# layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: AfmoeConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: pages of the GLOBAL layers [n_G, P, PS, K, D] as
    llama's, and per slot the WINDOW layers' band [n_W, (slots + 1) x R, BP,
    K, D] (slot s holds pages s x R .. s x R + R - 1). Page 0 is the trash
    page and the last slot's band the trash band: a decode row that is not
    live writes there. `num_slots` 1 serves a caller with one row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    cell = (cfg.num_kv_heads, cfg.head_dim_)

    def pool():
        return StatePool(
            jnp.zeros((cfg.layers_of(GLOBAL), num_pages, page_size, *cell),
                      dtype),
            jnp.zeros((cfg.layers_of(WINDOW),
                       (num_slots + 1) * cfg.band_pages, cfg.band_page_size,
                       *cell), dtype))

    return pool(), pool()


def kv_pages_shardings(cfg: AfmoeConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Pages and band alike shard over their heads as llama's pages do."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    pages = logical_to_sharding(mesh, rules, "layers", None, "seq",
                                "kv_heads", "head_dim")
    band = logical_to_sharding(mesh, rules, "layers", None, None,
                               "kv_heads", "head_dim")
    return (StatePool(pages, band), StatePool(pages, band))


def kv_pool_layers(cfg: AfmoeConfig) -> int:
    """Layers of the page pool: the global layers alone."""
    return cfg.layers_of(GLOBAL)


def _cell_bytes(cfg: AfmoeConfig) -> int:
    return (2 * cfg.num_kv_heads * cfg.head_dim_
            * jnp.dtype(cfg.dtype).itemsize)


def kv_token_layer_bytes(cfg: AfmoeConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool: a key and a
    value on every KV head; a window layer leaves nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return _cell_bytes(cfg)


def state_slot_bytes(cfg: AfmoeConfig) -> int:
    """HBM bytes one slot holds beside its pages, whatever its context: R
    pages of keys and values in every window layer."""
    return cfg.layers_of(WINDOW) * cfg.band_cells * _cell_bytes(cfg)


def kv_wire_cell(cfg: AfmoeConfig) -> None:
    """Nothing ships: a band has no KVSH wire form, and pages without it
    are the global layers' quarter of a sequence. A handoff, resume or park
    replays its tokens instead."""
    return None


# ---------------------------------------------------------------------------
# The two attentions
# ---------------------------------------------------------------------------

def _qkvg(cfg: AfmoeConfig, lp: Params, x, positions, kind: str):
    """(q [B, T, H, D] and k [B, T, K, D] normed a head and, in a WINDOW
    layer alone, rotated; v [B, T, K, D]; the gate's logits [B, T, H x D])
    of the layer's input, normed here."""
    b, t, _ = x.shape
    d = cfg.head_dim_
    a = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    q = _proj_heads(lp, "wq", a).reshape(b, t, cfg.num_heads, d)
    k = _proj_heads(lp, "wk", a).reshape(b, t, cfg.num_kv_heads, d)
    v = _proj_heads(lp, "wv", a).reshape(b, t, cfg.num_kv_heads, d)
    q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
    k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    if kind == WINDOW:
        inv_freq = rope_frequencies(d, cfg.rope_theta)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
    return q, k, v, _proj(lp, "wgate", a)


def _gated_out(cfg: AfmoeConfig, lp: Params, x, attn, gate):
    """x + norm((attn * sigmoid(gate)) W_o): the gate on what the softmax
    mixed, the second norm on what the sub-layer gives."""
    b, t, _ = x.shape
    mixed = (attn.reshape(b, t, -1).astype(F32)
             * jax.nn.sigmoid(gate.astype(F32))).astype(x.dtype)
    return x + rms_norm(_proj(lp, "wo", mixed), lp["ln_attn_out"],
                        cfg.rms_eps)


def _global_block(cfg: AfmoeConfig, lp: Params, x, positions, inv_freq,
                  attn_fn, lora_idx=None):
    """llama._attn_block for a global layer: none rotated, q and k normed
    a head, the output gated and normed. Returns (x_out, k, v)."""
    del inv_freq, lora_idx
    q, k, v, gate = _qkvg(cfg, lp, x, positions, GLOBAL)
    return _gated_out(cfg, lp, x, attn_fn(q, k, v), gate), k, v


_ATTENTION = GQA_ATTENTION._replace(block=_global_block)


def _band_blocks(old_k, old_v, before, size: int):
    """A band as it stood [B, cells, K, D], the positions its cells held
    `before` [B, cells], as a source of mimo_v2._attend: a page a block."""

    def fetch(j):
        return tuple(lax.dynamic_slice_in_dim(a, j * size, size, axis=1)
                     for a in (old_k, old_v, before))

    return old_k.shape[1] // size, fetch


def _window_mixer(cfg: AfmoeConfig, first: bool, shared: dict):
    """llama.LayerGroup's `mixer` for a window layer: attention over the
    row's band (`cache_k.state`, `cache_v.state`), which it keeps. `first`:
    the model's layer 0, which takes the embedding and scales it. `shared`:
    a decode step's band work-list, built by its first window layer for all
    of them (the step is unrolled: one trace)."""
    w, r, bp = cfg.sliding_window, cfg.band_pages, cfg.band_page_size
    cells = r * bp
    shape = {"kv_heads": cfg.num_kv_heads, "v_dim": cfg.head_dim_}

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b, t, _ = x.shape
        if first and cfg.mup_enabled:
            x = (x.astype(F32) * math.sqrt(cfg.hidden_size)).astype(x.dtype)
        band_k, band_v = cache_k.state, cache_v.state  # [n_W, S x R, BP, ..]
        slots = (jnp.arange(b, dtype=jnp.int32) if rows.slots is None
                 else rows.slots)
        if rows.lens is None:  # decode: one token a row
            pos = rows.start_pos
            q, k, v, gate = _qkvg(cfg, lp, x, pos[:, None], WINDOW)
            kv_lens, into = pos + 1, slots
            if rows.live is not None:
                kv_lens = jnp.where(rows.live, kv_lens, 0)
                into = jnp.where(rows.live, slots, band_k.shape[1] // r - 1)
            page = into * r + pos // bp % r
            band_k = band_k.at[layer, page, pos % bp].set(k[:, 0])
            band_v = band_v.at[layer, page, pos % bp].set(v[:, 0])
            kv_from = jnp.maximum(kv_lens - w, 0)
            tables = slots[:, None] * r + jnp.arange(r, dtype=jnp.int32)[None]
            if "work" not in shared:
                shared["work"] = paged_band_work(band_k, tables, kv_lens,
                                                 kv_from)
            attn = paged_band_decode(q, band_k, band_v, layer, tables,
                                     kv_lens, kv_from, work=shared["work"])
        else:
            start = (jnp.zeros((b,), jnp.int32) if rows.start_pos is None
                     else rows.start_pos)
            positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            q, k, v, gate = _qkvg(cfg, lp, x, positions, WINDOW)
            sources = [_own_blocks(k, v, rows.lens, start)]
            # a slot's band cell by cell, (page, cell) as the bodies write
            # the page pool: a view of the stack as rings, or whole pages
            # gathered and scattered, had the chip's compiler lay the stack
            # out anew and copy it (the compile for a described v5e said so)
            ring = jnp.arange(cells, dtype=jnp.int32)[None]
            at = (layer, slots[:, None] * r + ring // bp, ring % bp)
            # the band once the chunk is in: a cell's position is the
            # chunk's where that is at or past `start`, else what it held
            held = band_positions(start + rows.lens, cells)  # [B, cells]
            pick = jnp.clip(held - start[:, None], 0, t - 1)[:, :, None, None]
            new_k = jnp.take_along_axis(k, pick, axis=1)
            new_v = jnp.take_along_axis(v, pick, axis=1)
            if rows.start_pos is not None:  # the band as it stood
                old_k, old_v = band_k[at], band_v[at]  # [B, cells, K, D]
                sources.insert(0, _band_blocks(
                    old_k, old_v, band_positions(start, cells), bp))
                new = (held >= start[:, None])[:, :, None, None]
                new_k = jnp.where(new, new_k, old_k)
                new_v = jnp.where(new, new_v, old_v)
            _traced["band_chunk"] = "xla"
            attn = _attend(q, positions, sources, window=w, **shape)
            band_k = band_k.at[at].set(new_k)
            band_v = band_v.at[at].set(new_v)
        return (_gated_out(cfg, lp, x, attn, gate),
                cache_k._replace(state=band_k), cache_v._replace(state=band_v))

    return mixer


def _moe_mlp_fn(cfg: AfmoeConfig, live=None):
    """llama's `mlp_fn` for a mixture layer: the routed experts this chip
    holds by the sigmoid-and-bias rule plus the shared expert, and as aux
    the layer's ops/moe.Routing. `live`: as deepseek_v3._moe_mlp_fn."""
    held = (None if cfg.num_experts == cfg.router_experts
            else cfg.held_experts)

    def fn(lp, h, token_valid, lora_idx=None):
        b, t, m = h.shape
        flat = h.reshape(b * t, m)
        if token_valid is None and live is not None:
            token_valid = jnp.broadcast_to(live[:, None], (b, t))
        logits = jnp.einsum("sm,mx->sx", flat, lp["router"],
                            preferred_element_type=F32)
        routed, routing = moe.moe_routed(
            flat, logits, lp["we_gate"], lp["we_up"], lp["we_down"],
            layer=lp["layer"], held=held,
            route=lambda r: moe.sigmoid_bias_routing(
                r, lp["router_bias"], cfg.experts_per_token,
                scale=cfg.route_scale, normalize=cfg.route_norm),
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
        )
        shared = (jax.nn.silu(flat @ lp["ws_gate"]) * (flat @ lp["ws_up"])
                  ) @ lp["ws_down"]
        return (routed + shared).reshape(b, t, m), routing

    return fn


def _groups(cfg: AfmoeConfig, live=None) -> list[LayerGroup]:
    """Two groups a layer, in `layer_types`' order: its attention (a global
    layer attends over the page pool, a window layer is a mixer over its
    band), then its feed-forward, normed on both sides. A group's
    parameters and its place in its pool are its kind's next row."""
    moe_fn, shared = _moe_mlp_fn(cfg, live), {}
    seen = dict.fromkeys((GLOBAL, WINDOW, "dense", "moe"), 0)

    def take(kind):
        seen[kind] += 1
        return seen[kind] - 1

    groups = []
    for layer, kind in enumerate(cfg.layer_types):
        at = take(kind)
        if kind == WINDOW:
            groups.append(LayerGroup(
                _ATTN, None, 1, W, start=at, pool_layer=at, attends=False,
                mixer=_window_mixer(cfg, layer == 0, shared),
                scope="window_attention"))
        else:
            groups.append(LayerGroup(
                _ATTN, None, 1, G, start=at, pool_layer=at,
                scope="global_attention"))
        if layer < cfg.num_dense_layers:
            groups.append(LayerGroup(
                _DENSE_MLP, _default_mlp_fn, 1, DENSE, start=take("dense"),
                attends=False, scope="dense_feed_forward",
                out_norm="ln_mlp_out"))
        else:
            groups.append(LayerGroup(
                _MOE_MLP, moe_fn, 1, whole=_EXPERTS, start=take("moe"),
                attends=False, scope="expert_mixture",
                out_norm="ln_mlp_out"))
    return groups


def band_pages_read(cfg: AfmoeConfig, kv_lens):
    """Pages of its band a row's decode reads in one window layer, [B]:
    those that hold positions `max(len - W, 0) <= p < len`; 0 for a row of
    length 0. At most R, and ceil(len / BP) while len <= W."""
    bp = cfg.band_page_size
    first = jnp.maximum(kv_lens - cfg.sliding_window, 0) // bp
    return jnp.where(kv_lens > 0, (kv_lens - 1) // bp - first + 1, 0)


def step_counters(cfg: AfmoeConfig) -> dict[str, tuple]:
    """The counters a call returns, by name and shape (all int32): the
    cells the step's attentions read — a live row's min(len, W) in every
    window layer, its whole length in every global one —, the band's pages
    a decode step's work-lists named (0 from a prefill or an extend, which
    build none), and deepseek_v3's expert load over the HELD experts beside
    the assignments that went to experts this chip does not hold."""
    shapes: dict[str, tuple] = {"window_kv_tokens": (), "global_kv_tokens": (),
                                "window_pages_read": ()}
    if cfg.num_moe_layers:
        shapes.update({
            "experts_touched": (), "expert_assignments": (),
            "expert_load_max": (), "assignments_elsewhere": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)})
    return shapes


def _extra(cfg: AfmoeConfig, aux, shape, routing: bool, kv_lens,
           decoding: bool = False):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a group;
    the mixtures' are stacked here in layer order. `kv_lens` [B]: the cells
    each row's context holds once the call is done, 0 for a row not live."""
    # two groups a layer (_groups): its attention, then its feed-forward
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
    n_w, n_g = cfg.layers_of(WINDOW), cfg.layers_of(GLOBAL)
    counters = dict(out[0]) if out else {}
    counters["window_kv_tokens"] = n_w * jnp.sum(
        jnp.minimum(kv_lens, cfg.sliding_window), dtype=jnp.int32)
    counters["global_kv_tokens"] = n_g * jnp.sum(kv_lens, dtype=jnp.int32)
    counters["window_pages_read"] = (
        n_w * jnp.sum(band_pages_read(cfg, kv_lens), dtype=jnp.int32)
        if decoding else jnp.zeros((), jnp.int32))
    if found:
        counters["assignments_elsewhere"] = (
            jnp.zeros((), jnp.int32) if stacked[0].elsewhere is None
            else jnp.sum(stacked[0].elsewhere, dtype=jnp.int32))
    return (counters,)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: AfmoeConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False, slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose bands the rows write: whatever a band held is void."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_ATTENTION,
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: AfmoeConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False, slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' bands are read as they stood,
    attended over with the chunk's own keys, and left holding the last
    positions of `start_pos + chunk_lens`."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_ATTENTION, slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, start_pos + chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: AfmoeConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False,
                      slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged (`window`, static, is the engine's context
    bucket for the GLOBAL layers' sweep, not the model's sliding window,
    which is a lower bound a row inside the window layers' mixer); a row
    that is not `live` writes the trash band and reads no cell."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_ATTENTION, slot_ids=slot_ids)
    kv_lens = seq_lens + 1
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, (input_ids.shape[0], 1), routing, kv_lens, decoding=True))


# It verifies no draft: a rejected token's cell has overwritten the position
# a band before it, and there is no snapshot to roll back to. `slot_ids`:
# the rows' slots (default row i in slot i); `num_slots`: the bands of the
# pool. `num_experts_per_tok` is read and not listed: every mixture's config
# carries it, and a key listed here is refused of every family that does not
# list it (models/__init__.config_from_hf).
FAMILY = Family(
    name="afmoe", config_class=AfmoeConfig, model_types=("afmoe",),
    mechanism_keys=("layer_types", "sliding_window", "num_dense_layers",
                    "num_experts", "num_shared_experts", "route_norm",
                    "route_scale", "score_func", "mup_enabled",
                    "global_attn_every_n_layers", "moe_intermediate_size",
                    "expert_parallel"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="page pool beside a band of pages a slot",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "assignments_elsewhere": StepCounter(
            "sum", "moe_assignments_elsewhere_total"),
        "window_kv_tokens": StepCounter("sum", "window_kv_tokens_total"),
        "global_kv_tokens": StepCounter("sum", "global_kv_tokens_total"),
        "window_pages_read": StepCounter("sum", "window_pages_read_total")},
    step_counters=step_counters, paged_keywords=("routing", "slot_ids"),
    keywords_of={"init_kv_pages": ("num_slots",)})
