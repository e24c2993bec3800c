"""dots3-note-class decoder (`model_type` `dots3_note`:
dots-studio/dots3-note-prev's language model): a stack whose layers attend
either over the WHOLE context through a learned SPARSE selection or over a
sliding WINDOW, in the order `layer_types` spells (F F then S S S F as
published), each kind latent attention at sizes of its own, with a sigmoid
gate a head on the attention's output, under one leading dense feed-forward
and then sigmoid-routed mixtures with a shared expert.
docs/sparse-attention.md has the equations.

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them as `LayerGroup`s, one a RUN of like layers
(granite_hybrid's runs: `F | F | SSS` at the benchmark's cut), each with
stacks of its own:

- FULL layers are the bodies' `Attention` over the PAGE pool of the full
  layers alone: deepseek_v3's block (`_mla_block`) with a low-rank query,
  both latents scaled (`apply_mla_qkv_lora_rescale`, read as LongCat-Flash's
  two scales), the gate, and DeepSeek-V3.2's learned INDEXER: a token leaves
  THREE values under one page id — its latent `cache_k.pages` [n_F, P, PS,
  kv_lora_rank], and in ONE row of `cache_v.pages` [n_F, P, PS, 128 + Di]
  the rotated shared key's 128-lane cell and behind it the index key. A
  query scores every cell it may see (ops/attention.index_scores), the
  `index_topk` of largest score are chosen EXACTLY, ties to the lower
  position (ops/attention.topk_mask), and its softmax runs over the chosen
  cells and nothing else — prefill, extend and decode alike. While a query
  sees no more than `index_topk` cells the choice is all of them and the
  layer is the unrestricted one. HOW the restriction is realised is a mask
  over whole pages (ops/pallas_attention.sparse_latent_decode in decode, a
  block of pages at a time in extend); reading only the chosen cells is
  ROADMAP work.
- SLIDING layers are a group's `mixer`: the same block at the `swa_*` sizes
  (more latent, fewer heads, a base of its own, no indexer) over the last
  `sliding_window_size` positions (itself and the 512 before it), whose
  cache is a LATENT RING a slot beside the pages (llama.StatePool):
  `cache_k.state` [n_S, slots + 1, R, swa_kv_lora_rank] and `cache_v.state`
  [n_S, slots + 1, R, 128], R the window in whole tiles of 128 cells.
  Position p lives in cell p mod W (the cells past W are never written); the
  last slot is the trash ring, where rows that are not `live` write. Decode
  writes its cell and attends over the row's min(len, W) cells in one call
  of paged_latent_decode — a ring is a page of the kernel's own shape, the
  table [B, 1] the rows' slots; prefill leaves a prompt's last min(n, W)
  positions in the ring; an extend chunk attends over the ring as it stood
  and over its own latents under the window's mask, then writes what of old
  and new is the last W (a chunk longer than the ring is exact).
- The mixture: kimi_linear's (`sigmoid_bias_routing`, three-matrix SwiGLU
  experts, one shared expert beside them), a chip holding a SHARE of the
  experts (`expert_parallel`, `held_experts`).

Not served, each refused by name: a grouped choice of experts, a router that
is no sigmoid, a gate that is not head-wise, latents that are not rescaled,
rope scaling; speculative decoding (a rejected draft's cell has overwritten
the position W before it), an int8 pool, KV on the wire, int8 weights and
LoRA pools, a real checkpoint (engine/weights.py); the engine refuses the
prefix cache, the offload tier and the split role for a family with state
per slot (scheduler.py). Outside this module: the vision tower, the audio
encoder and the multi-token-prediction layers of dots3-note.

The paged serving functions return one value after (logits, cache_k,
cache_v): the step's counters, or under the static `routing=True` what the
routers decided.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from llmlb_tpu.models import granite_hybrid, stacks
from llmlb_tpu.models.deepseek_v3 import (
    EXPERT_LOAD_COUNTERS,
    LOAD_BUCKETS,
    ROPE_CELL,
    DeepseekV3Config,
    _extra as _routed_extra,
    _mla_block,
    _scale,
    absorb,
    carry_out,
    held_share,
)
from llmlb_tpu.models.family import Family, StepCounter
from llmlb_tpu.models.kimi_linear import _moe_mlp_fn
from llmlb_tpu.models.llama import (
    Attention,
    LayerGroup,
    StatePool,
    StateRows,
    _decode_paged_impl,
    _default_mlp_fn,
    _prefill_extend_paged_impl,
    _prefill_impl,
    shard_rules_for,
)
from llmlb_tpu.ops.attention import (
    _latent_attend,
    _pad_last,
    _pallas_enabled,
    _traced,
    band_positions,
    index_scores,
    note_decode_group,
    paged_decode_work,
    paged_index_scores,
    paged_latent_decode,
    paged_latent_extend,
    topk_mask,
)
from llmlb_tpu.ops.rope import rope_frequencies
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

FULL, SLIDING = "full", "sliding"  # the two attentions
_KINDS = {"full_attention": FULL, "sliding_attention": SLIDING}
WINDOW_DECODE = "window_latent_decode"  # the ring's decode call in a trace
RING_TILE = 128  # a ring holds its window in whole tiles of this many cells


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig(DeepseekV3Config):
    # The inherited latent-attention fields are the FULL layers'; the
    # sliding layers' sizes are the `swa_*` fields (`window`).
    attn_gate: bool = True
    index_topk: int = 2048
    index_heads: int = 64
    index_head_dim: int = 128
    q_lora_rank: int | None = 1024
    layer_types: tuple[str, ...] = (FULL, FULL, SLIDING, SLIDING, SLIDING)
    sliding_window: int = 513  # a position sees itself and the W - 1 before
    swa_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    swa_gate: bool = True
    lora_rescale: bool = True  # both latents of both kinds
    # the routed experts THIS CHIP holds (`num_experts`, the weights' expert
    # axis): all the router scores, or a share [first_expert, + num_experts)
    router_experts: int = 256
    first_expert: int = 0

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the router's experts this chip holds."""
        return self.first_expert, self.num_experts

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """A layer's attention and feed-forward, in order."""
        return tuple(
            f"{kind}_{'dense' if at < self.first_k_dense else 'moe'}"
            for at, kind in enumerate(self.layer_types))

    @property
    def ring_cells(self) -> int:
        """Cells of a slot's ring as stored: the window in whole tiles."""
        return -(-self.sliding_window // RING_TILE) * RING_TILE

    @property
    def window(self) -> "Dots3NoteConfig":
        """The sliding layers' block as deepseek_v3._mla_block reads it: the
        `swa_*` sizes in the latent-attention fields, no indexer."""
        e = self.hidden_size
        return dataclasses.replace(
            self, num_heads=self.swa_heads, num_kv_heads=self.swa_heads,
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
            q_lora_scale=_lora_scale(self.lora_rescale, e,
                                     self.swa_q_lora_rank),
            kv_lora_scale=_lora_scale(self.lora_rescale, e,
                                      self.swa_kv_lora_rank),
            attn_gate=self.swa_gate, index_topk=0)

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "Dots3NoteConfig":
        """Build from a published `config.json`. What this family does not
        compute is refused by name. `expert_parallel` ({"chips", "chip",
        "experts"}) is the deployment's, not the checkpoint's: this chip
        holds `n_routed_experts` of the router's `experts`, the `chip`-th
        such share."""
        layers = hf["num_hidden_layers"]
        kinds = tuple(_KINDS.get(k) for k in hf.get("layer_types") or ())
        held, experts, first = held_share(hf)
        unsupported = {
            "layer_types": len(kinds) != layers or None in kinds,
            "attention_gate_type": hf.get("attention_gate_type") != "headwise",
            "swa_attention_gate_type": hf.get(
                "swa_attention_gate_type") != "headwise",
            "apply_mla_qkv_lora_rescale": not hf.get(
                "apply_mla_qkv_lora_rescale"),
            "q_lora_rank": not hf.get("q_lora_rank"),
            "swa_q_lora_rank": not hf.get("swa_q_lora_rank"),
            "index_topk": not hf.get("index_topk"),
            "rope_scaling": hf.get("rope_scaling") is not None,
            "attention_bias": bool(hf.get("attention_bias")),
            "n_group": hf.get("n_group", 1) != 1,
            "topk_group": hf.get("topk_group", 1) != 1,
            "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
            "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
            "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
            "n_shared_experts": hf.get("n_shared_experts", 1) < 1,
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
            "num_key_value_heads": hf.get(
                "num_key_value_heads", hf["num_attention_heads"])
            != hf["num_attention_heads"],
            "swa_num_key_value_heads": hf.get(
                "swa_num_key_value_heads", hf["swa_num_attention_heads"])
            != hf["swa_num_attention_heads"],
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"dots3_note config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/dots3_note.py; refusing to serve wrong logits")
        e, rope = hf["hidden_size"], hf.get("qk_rope_head_dim", 64)
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=e,
            intermediate_size=hf["intermediate_size"],
            num_layers=layers,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=rope,  # what RoPE turns: the bodies' rope_frequencies
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            dtype=dtype,
            kv_lora_rank=hf["kv_lora_rank"],
            q_lora_rank=hf["q_lora_rank"],
            q_lora_scale=_lora_scale(True, e, hf["q_lora_rank"]),
            kv_lora_scale=_lora_scale(True, e, hf["kv_lora_rank"]),
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
            num_experts=held,
            router_experts=experts,
            first_expert=first,
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_shared_experts=int(hf.get("n_shared_experts", 1)),
            first_k_dense=min(layers, int(hf.get("first_k_dense_replace", 0))),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            rope_interleave=bool(hf.get("rope_interleave", True)),
            index_topk=int(hf["index_topk"]),
            index_heads=int(hf["index_n_heads"]),
            index_head_dim=int(hf["index_head_dim"]),
            layer_types=kinds,
            sliding_window=int(hf["sliding_window_size"]),
            swa_heads=hf["swa_num_attention_heads"],
            swa_q_lora_rank=hf["swa_q_lora_rank"],
            swa_kv_lora_rank=hf["swa_kv_lora_rank"],
            swa_qk_nope_head_dim=hf["swa_qk_nope_head_dim"],
            swa_qk_rope_head_dim=hf["swa_qk_rope_head_dim"],
            swa_v_head_dim=hf["swa_v_head_dim"],
            swa_rope_theta=float(hf.get("swa_rope_theta", 10000.0)),
        )


def _lora_scale(on: bool, hidden: int, rank: int) -> float:
    """`apply_mla_qkv_lora_rescale`: a latent of `rank` numbers is scaled to
    stand for a hidden-wide input, sqrt(hidden / rank)."""
    return math.sqrt(hidden / rank) if on else 1.0


# ---------------------------------------------------------------------------
# Params: a stack a run of like layers
# ---------------------------------------------------------------------------

_MLA = ("ln_attn", "wq_a", "ln_q", "wq_b", "wkv_a", "ln_kv", "wk_b", "wv_b",
        "w_gate", "wo")
_INDEX = ("wi_q", "wi_k", "ln_ik", "ln_ik_bias", "wi_w")
_DENSE_MLP = ("ln_mlp", "wg", "wu", "wd")
_MOE_MLP = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
            "ws_gu", "ws_down")
_EXPERTS = ("we_gate", "we_up", "we_down")
_NAMES = {FULL + "_dense": _MLA + _INDEX + _DENSE_MLP,
          FULL + "_moe": _MLA + _INDEX + _MOE_MLP,
          SLIDING + "_dense": _MLA + _DENSE_MLP,
          SLIDING + "_moe": _MLA + _MOE_MLP}


def runs(cfg: Dots3NoteConfig) -> list[tuple[str, str, int]]:
    """(prefix of its keys in the pytree, kind, layers) of every run of like
    layers, in order: `r0_` .. (granite_hybrid.runs says why a run is a
    stack of its own)."""
    return granite_hybrid.runs(cfg.layer_kinds)


def _layer_shapes(cfg: Dots3NoteConfig, kind: str
                  ) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule) of a
    layer that attends by `kind`. The fan-in of a projection behind a SCALED
    latent counts the scale (longcat_flash._layer_shapes says why)."""
    a = cfg if kind == FULL else cfg.window
    e, h, c, r = a.hidden_size, a.num_heads, a.kv_lora_rank, a.q_lora_rank
    q_in = round(r * a.q_lora_scale ** 2)
    kv_in = round(c * a.kv_lora_scale ** 2)
    dn, dr, dv = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    f, x, fm = cfg.intermediate_size, cfg.num_experts, cfg.moe_intermediate_size
    fs = fm * cfg.num_shared_experts
    hi, di = cfg.index_heads, cfg.index_head_dim
    return {
        "ln_attn": ((e,), 0), "wq_a": ((e, r), e), "ln_q": ((r,), 0),
        "wq_b": ((r, h * (dn + dr)), q_in), "wkv_a": ((e, c + dr), e),
        "ln_kv": ((c,), 0),
        "wk_b": ((h, c, dn), kv_in), "wv_b": ((h, c, dv), kv_in),
        "w_gate": ((e, h), e), "wo": ((h * dv, e), h * dv),
        "wi_q": ((r, hi * di), q_in), "wi_k": ((e, di), e),
        "ln_ik": ((di,), 0), "ln_ik_bias": ((di,), 0), "wi_w": ((e, hi), e),
        "ln_mlp": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, cfg.router_experts), e),
        "router_bias": ((cfg.router_experts,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
        "ws_gu": ((e, 2 * fs), e), "ws_down": ((fs, e), fs),
    }


def _leaves(cfg: Dots3NoteConfig) -> list[stacks.Leaf]:
    """Every stacked leaf the config calls for: a stack a run, at its own
    kind's sizes."""
    return [leaf for prefix, kind, count in runs(cfg)
            for leaf in stacks.stack_leaves(
                _layer_shapes(cfg, kind.split("_")[0]),
                [(prefix, _NAMES[kind], count)])]


def _own_rule(cfg, name: str, k, shape):
    """The index key's LayerNorm BIAS a seeded normal of sd 0.1 in float32,
    not zero (a program that leaves it out then differs); the rest as the
    other mixtures' (deepseek_v3.init_params says why the router's choice
    bias is not zero either)."""
    if name == "ln_ik_bias":
        return 0.1 * jax.random.normal(k, shape, F32)
    return stacks.seeded_bias(0.02)(cfg, name, k, shape)


def init_params(cfg: Dots3NoteConfig, key: jax.Array) -> Params:
    """Random init (this backs tests and the benchmark; a checkpoint is
    refused, engine/weights.py): matrices normal x fan_in^-0.5
    (_layer_shapes), norms ones, the seeded vectors by `_own_rule`."""
    return stacks.init_params(cfg, key, _leaves(cfg), _own_rule)


def param_logical_axes(cfg: Dots3NoteConfig) -> dict[str, tuple]:
    layer = {
        **stacks.MLP_AXES, **stacks.EXPERT_AXES,
        "wq_b": (None, "heads"), "wk_b": ("heads", None, None),
        "wv_b": ("heads", None, None), "wo": ("heads", "embed"),
        "router": ("embed", None), "ws_gu": ("embed", "ffn"),
    }
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: Dots3NoteConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: latent pages with the index key of the full layers, a latent
# ring a slot of the sliding layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: Dots3NoteConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: over the FULL layers the latents [n_F, P, PS,
    kv_lora_rank] and, in one row, the shared key's cell and the index key
    [n_F, P, PS, 128 + Di]; per slot the SLIDING layers' ring [n_S, slots +
    1, R, swa_kv_lora_rank] and [n_S, slots + 1, R, 128]. Page 0 is the
    trash page and the last ring the trash ring. `num_slots` 1 serves a
    caller with one row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    pages = (cfg.layers_of(FULL), num_pages, page_size)
    ring = (cfg.layers_of(SLIDING), num_slots + 1, cfg.ring_cells)
    return (
        StatePool(jnp.zeros((*pages, cfg.kv_lora_rank), dtype),
                  jnp.zeros((*ring, cfg.swa_kv_lora_rank), dtype)),
        StatePool(jnp.zeros((*pages, ROPE_CELL + cfg.index_head_dim), dtype),
                  jnp.zeros((*ring, ROPE_CELL), dtype)),
    )


def kv_pages_shardings(cfg: Dots3NoteConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Everything replicates: every head reads the whole latent
    (deepseek_v3.kv_pages_shardings), in a page and in a ring alike."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    pages = logical_to_sharding(mesh, rules, "layers", None, "seq", None)
    ring = logical_to_sharding(mesh, rules, "layers", None, None, None)
    return (StatePool(pages, ring), StatePool(pages, ring))


def kv_pool_layers(cfg: Dots3NoteConfig) -> int:
    """Layers of the page pool: the full layers alone."""
    return cfg.layers_of(FULL)


def kv_token_layer_bytes(cfg: Dots3NoteConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool: the latent,
    the shared key's tile-wide cell AND the index key; a sliding layer
    leaves nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return ((cfg.kv_lora_rank + ROPE_CELL + cfg.index_head_dim)
            * jnp.dtype(cfg.dtype).itemsize)


def state_slot_bytes(cfg: Dots3NoteConfig) -> int:
    """HBM bytes one slot holds beside its pages: the ring's cells (as
    stored: whole tiles) of every sliding layer."""
    return (cfg.layers_of(SLIDING) * cfg.ring_cells
            * (cfg.swa_kv_lora_rank + ROPE_CELL)
            * jnp.dtype(cfg.dtype).itemsize)


def kv_wire_cell(cfg: Dots3NoteConfig) -> None:
    """Nothing ships: neither a ring nor a latent page with its index key
    has a KVSH wire form. A handoff, resume or park replays its tokens."""
    return None


# ---------------------------------------------------------------------------
# The two attentions
# ---------------------------------------------------------------------------

SELECTIONS: list = []  # what `selection=True` calls heard, in call order


def _heard(positions, scores, chosen):
    SELECTIONS.append(tuple(np.asarray(v) for v in (positions, scores,
                                                    chosen)))


def _sparse_attention(cfg: Dots3NoteConfig, selection: bool = False
                      ) -> Attention:
    """The full layers' llama.Attention: deepseek_v3's block with the
    indexer, and three ops that attend over the chosen cells alone. `q` is a
    deepseek_v3.IndexedQuery; the second pool's row is the shared key's cell
    and, behind it, the index key. `selection` (static, off in serving):
    every call of an op sends the host its queries' positions [B, T], their
    index scores and their choice [B, T, S] (`SELECTIONS`, layer by layer in
    order: benchmark/check_sparse.py hands them to the reference)."""
    k, scale = cfg.index_topk, _scale(cfg)

    def hear(positions, scores, chosen):
        if selection:
            jax.debug.callback(_heard, positions, scores, chosen,
                               ordered=True)

    def prefill(q, c, cell, prompt_lens):
        t = c.shape[1]
        pos = jnp.arange(t, dtype=jnp.int32)
        seen = (pos[None, :, None] >= pos[None, None, :]) & (
            pos[None, None, :] < prompt_lens[:, None, None])  # [B, T, T]
        with jax.named_scope("index_select"):
            _traced["index_scores_chunk"] = "xla"
            scores = index_scores(q.index_q, q.index_w,
                                  cell[..., ROPE_CELL:])
            chosen = topk_mask(scores, seen, k)
        hear(jnp.broadcast_to(pos[None], c.shape[:2]), scores, chosen)
        _traced["sparse_latent_prefill"] = "xla"
        return carry_out(_latent_attend(
            absorb(q), q.rope, c, cell[..., :ROPE_CELL], chosen, scale), q)

    def extend(q, c_pool, r_pool, layer, tables, positions, chunk_lens):
        with jax.named_scope("index_select"):
            scores = paged_index_scores(q.index_q, q.index_w, r_pool, layer,
                                        tables)
            cells = jnp.arange(scores.shape[-1], dtype=jnp.int32)
            chosen = topk_mask(
                scores, cells[None, None, :] <= positions[:, :, None], k)
        hear(positions, scores, chosen)
        with jax.named_scope("sparse_latent_extend"):
            return carry_out(paged_latent_extend(
                absorb(q), q.rope, c_pool, r_pool, layer, tables, positions,
                scale=scale, selected=chosen, chunk_lens=chunk_lens), q)

    def decode(q, c_pool, r_pool, layer, tables, kv_lens, *, window=None,
               work=None):
        with jax.named_scope("index_select"):
            scores = paged_index_scores(q.index_q, q.index_w, r_pool, layer,
                                        tables, window)
            cells = jnp.arange(scores.shape[-1], dtype=jnp.int32)
            chosen = topk_mask(
                scores, cells[None, None, :] < kv_lens[:, None, None], k)
        hear(kv_lens[:, None] - 1, scores, chosen)
        with jax.named_scope("sparse_latent_decode"):
            return carry_out(paged_latent_decode(
                absorb(q), q.rope, c_pool, r_pool, layer, tables, kv_lens,
                scale=scale, window=window, work=work, selected=chosen), q)

    return Attention(_mla_block, prefill, extend, decode, paged_decode_work)


def _window_mixer(cfg: Dots3NoteConfig):
    """llama.LayerGroup's `mixer` for a sliding layer: deepseek_v3's block
    at the `swa_*` sizes over the row's latent ring (`cache_k.state`,
    `cache_v.state`), which it keeps."""
    a, w, cells = cfg.window, cfg.sliding_window, cfg.ring_cells
    scale = _scale(a)
    inv_freq = rope_frequencies(a.qk_rope_head_dim, a.rope_theta)
    shared: dict = {}  # a decode step's ring work-list, built by its first
    # sliding layer for all of them (the step is unrolled: one trace)

    def held_positions(lens):
        """[B, R]: the position each cell of a ring holds at length `lens`
        (band_positions over the W cells in use), below 0 where none."""
        held = band_positions(lens, w)
        return jnp.pad(held, ((0, 0), (0, cells - w)), constant_values=-1)

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b, t, _ = x.shape
        ring_c, ring_r = cache_k.state, cache_v.state
        slots = (jnp.arange(b, dtype=jnp.int32) if rows.slots is None
                 else rows.slots)
        if rows.lens is None:  # decode: one token a row
            pos = rows.start_pos
            positions = pos[:, None]
            kv_lens = jnp.minimum(pos + 1, w)
            into = slots
            if rows.live is not None:
                kv_lens = jnp.where(rows.live, kv_lens, 0)
                into = jnp.where(rows.live, slots, ring_c.shape[1] - 1)

            def attn_fn(q, c, cell):
                nonlocal ring_c, ring_r  # the write precedes the attention
                ring_c = ring_c.at[layer, into, pos % w].set(c[:, 0])
                ring_r = ring_r.at[layer, into, pos % w].set(cell[:, 0])
                with jax.named_scope("window_latent_decode"):
                    return carry_out(_ring_decode(
                        absorb(q), q.rope, ring_c, ring_r, layer, slots,
                        kv_lens, scale, shared), q)
        else:
            start = (jnp.zeros((b,), jnp.int32) if rows.start_pos is None
                     else rows.start_pos)
            positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            at = (layer, slots)

            def attn_fn(q, c, cell):
                nonlocal ring_c, ring_r
                own = jnp.where(
                    jnp.arange(t, dtype=jnp.int32)[None] < rows.lens[:, None],
                    positions, -1)  # [B, T], void past the chunk's length
                keys_c, keys_r, key_pos = c, cell, own
                # the ring once the chunk is in: a cell's position is the
                # chunk's where that is at or past `start`, else what it held
                held = held_positions(start + rows.lens)  # [B, R]
                pick = jnp.clip(held - start[:, None], 0, t - 1)[:, :, None]
                used = (held >= 0)[:, :, None]  # the W cells of the R stored
                new_c = jnp.where(used, jnp.take_along_axis(c, pick, axis=1),
                                  0)
                new_r = jnp.where(used,
                                  jnp.take_along_axis(cell, pick, axis=1), 0)
                if rows.start_pos is not None:  # the ring as it stood
                    old_c, old_r = ring_c[at], ring_r[at]  # [B, R, .]
                    keys_c = jnp.concatenate([old_c, c], axis=1)
                    keys_r = jnp.concatenate([old_r, cell], axis=1)
                    key_pos = jnp.concatenate([held_positions(start), own],
                                              axis=1)
                    new = (held >= start[:, None])[:, :, None]
                    new_c = jnp.where(new, new_c, old_c)
                    new_r = jnp.where(new, new_r, old_r)
                seen = ((key_pos[:, None, :] >= 0)
                        & (key_pos[:, None, :] <= positions[:, :, None])
                        & (positions[:, :, None] - key_pos[:, None, :] < w))
                _traced["window_latent_chunk"] = "xla"
                out = _latent_attend(absorb(q), q.rope, keys_c, keys_r,
                                     seen, scale)
                ring_c = ring_c.at[at].set(new_c)
                ring_r = ring_r.at[at].set(new_r)
                return carry_out(out, q)

        x, _, _ = _mla_block(a, lp, x, positions, inv_freq, attn_fn)
        return (x, cache_k._replace(state=ring_c),
                cache_v._replace(state=ring_r))

    return mixer


def _ring_decode(q_abs, q_rope, ring_c, ring_r, layer, slots, kv_lens, scale,
                 shared):
    """One token a row over its slot's ring: ONE call of paged_latent_decode
    over the live rows, a ring a page (its table the rows' slots, its length
    the cells in use), under a name of its own in a device trace; the XLA
    route reads the rings of the rows' slots."""
    if _pallas_enabled():
        from llmlb_tpu.ops import pallas_attention as kernels

        tables = slots[:, None]
        if "work" not in shared:
            shared["work"] = kernels.decode_work_list(
                tables, kv_lens, page_size=ring_c.shape[2], group=1)
        _traced["window_decode"] = "pallas:" + WINDOW_DECODE
        note_decode_group(WINDOW_DECODE, shared["work"])
        return kernels.paged_latent_decode(
            q_abs[:, 0], _pad_last(q_rope[:, 0], ROPE_CELL), ring_c, ring_r,
            layer, tables, kv_lens, scale=scale, work=shared["work"],
            name=WINDOW_DECODE)[:, None]
    _traced["window_decode"] = "xla"
    at = (layer, slots)
    cell = jnp.arange(ring_c.shape[2], dtype=jnp.int32)
    seen = (cell[None, :] < kv_lens[:, None])[:, None, :]
    return _latent_attend(q_abs, q_rope, ring_c[at], ring_r[at], seen, scale)


def _groups(cfg: Dots3NoteConfig, live=None) -> list[LayerGroup]:
    """A group a RUN of like layers, in order: the run's own stacks whole,
    its place in its pool (the pages or the rings) its kind's next rows."""
    moe_fn, mixer = _moe_mlp_fn(cfg, live), _window_mixer(cfg)
    seen = {FULL: 0, SLIDING: 0}
    groups = []
    for prefix, kind, count in runs(cfg):
        attn = kind.split("_")[0]
        routed = kind.endswith("_moe")
        groups.append(LayerGroup(
            _NAMES[kind], moe_fn if routed else _default_mlp_fn, count,
            prefix, whole=_EXPERTS if routed else (), pool_layer=seen[attn],
            **(dict(attends=False, mixer=mixer, scope="window_layers")
               if attn == SLIDING else dict(scope="sparse_layers"))))
        seen[attn] += count
    return groups


def step_counters(cfg: Dots3NoteConfig) -> dict[str, tuple]:
    """The counters a call returns, by name and shape (all int32): the cells
    the full layers' indexers scored and those their attentions then read
    (a live query's whole sight, and min(that, index_topk), in every full
    layer), the ring cells the sliding layers read (min(len, W) a live row
    and layer), and deepseek_v3's expert load over the HELD experts beside
    the assignments that went to experts this chip does not hold."""
    shapes: dict[str, tuple] = {"index_scored_cells": (),
                                "index_selected_cells": (),
                                "window_kv_tokens": ()}
    if cfg.num_moe_layers:
        shapes.update({
            "experts_touched": (), "expert_assignments": (),
            "expert_load_max": (), "assignments_elsewhere": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)})
    return shapes


def _extra(cfg: Dots3NoteConfig, aux, shape, routing: bool, first, tokens):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a run,
    stacked over its layers (prefill, extend) or a list over them (decode);
    the mixtures' are joined here in layer order. `first` [B]: the position
    of a row's first query; `tokens` [B]: its valid queries (0: the row is
    not live). Query i of a row sees first + i + 1 cells."""
    found = [jax.tree.map(lambda *v: jnp.stack(v), *a)
             if isinstance(a, list) else a
             for a in aux if (a[0] if isinstance(a, list) else a) is not None]
    stacked = ([jax.tree.map(lambda *v: jnp.concatenate(v), *found)]
               if found else [None])
    if found and not routing:
        stacked = [stacked[0]._replace(
            load=jax.lax.optimization_barrier(stacked[0].load))]
    out = _routed_extra(cfg, stacked, shape, routing)
    if routing:
        return out
    counters = dict(out[0]) if out else {}
    sight = first[:, None] + 1 + jnp.arange(shape[1], dtype=jnp.int32)[None]
    valid = jnp.arange(shape[1], dtype=jnp.int32)[None] < tokens[:, None]

    def cells(limit):
        return jnp.sum(jnp.where(valid, jnp.minimum(sight, limit), 0),
                       dtype=jnp.int32)

    n_f, n_s = cfg.layers_of(FULL), cfg.layers_of(SLIDING)
    counters["index_scored_cells"] = n_f * jnp.sum(
        jnp.where(valid, sight, 0), dtype=jnp.int32)
    counters["index_selected_cells"] = n_f * cells(cfg.index_topk)
    counters["window_kv_tokens"] = n_s * cells(cfg.sliding_window)
    if found:
        counters["assignments_elsewhere"] = (
            cfg.num_moe_layers * cfg.experts_per_token
            * jnp.sum(tokens, dtype=jnp.int32)
            - counters["expert_assignments"])
    return (counters,)


_STATIC = ("cfg", "mesh", "routing", "selection")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: Dots3NoteConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False, slot_ids=None,
                       selection: bool = False):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose rings the rows write."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg),
        attention=_sparse_attention(cfg, selection), slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, jnp.zeros_like(prompt_lens),
        prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: Dots3NoteConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False, slot_ids=None,
                         selection: bool = False):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' rings are read from their slots,
    taken on from `start_pos` and written back."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_sparse_attention(cfg, selection), slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, start_pos, chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: Dots3NoteConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False,
                      slot_ids=None, selection: bool = False):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged; a row that is not `live` writes the trash page
    and the trash ring."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live),
        attention=_sparse_attention(cfg, selection), slot_ids=slot_ids)
    tokens = jnp.ones_like(seq_lens)
    if live is not None:
        tokens = live.astype(jnp.int32)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, (input_ids.shape[0], 1), routing, seq_lens, tokens))


# It verifies no draft (a rejected token's cell has overwritten the position
# W before it). `mixed_step` stays False: a state per slot needs its mixer
# called twice a layer first (family.py). `slot_ids`: the rows' slots
# (default row i in slot i); `num_slots`: the slot count of the pool's rings.
FAMILY = Family(
    name="dots3_note", config_class=Dots3NoteConfig,
    model_types=("dots3_note",),
    # (`sliding_window_size` and `swa_num_attention_heads` are read and not
    # listed: mimo_v2's configs carry them, and a key listed here is refused
    # of every family that does not list it)
    mechanism_keys=("layer_types", "index_topk", "index_n_heads",
                    "index_head_dim", "attention_gate_type",
                    "swa_attention_gate_type", "apply_mla_qkv_lora_rescale",
                    "kv_lora_rank", "q_lora_rank", "swa_kv_lora_rank",
                    "swa_q_lora_rank", "swa_num_key_value_heads",
                    "n_routed_experts",
                    "n_shared_experts", "first_k_dense_replace",
                    "moe_intermediate_size", "expert_parallel"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="latent page pool with index keys beside a latent ring a slot",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "assignments_elsewhere": StepCounter(
            "sum", "moe_assignments_elsewhere_total"),
        "index_scored_cells": StepCounter("sum", "index_scored_cells_total"),
        "index_selected_cells": StepCounter(
            "sum", "index_selected_cells_total"),
        "window_kv_tokens": StepCounter("sum", "window_kv_tokens_total")},
    step_counters=step_counters,
    paged_keywords=("routing", "slot_ids", "selection"),
    keywords_of={"init_kv_pages": ("num_slots",)})
