"""SDAR-MoE-class decoder (`model_type` `sdar_moe`: SDAR-30B-A3B-Chat): a
Qwen3-MoE-shaped stack that generates by DIFFUSION OVER BLOCKS.

Same serving contract and the same shared bodies as models/llama.py
(docs/block-diffusion.md); what differs is handed to them:

- Attention is grouped-query with an RMS norm of each query and key head
  before RoPE (one weight vector of head_dim for all heads, Qwen3's rule),
  under a BLOCK-CAUSAL mask: position j is visible to i iff j // B <=
  i // B (ops/attention._block_end) — causal across blocks of
  `block_length`, bidirectional inside one. Prefill, extend and the block
  pass all run under it; chunks begin and end on block boundaries (the
  scheduler's to keep).
- Every layer's feed-forward is the routed layer of ops/moe.py with
  Mixtral's rule (`top_k_routing`: the k largest of the router's logits, a
  softmax over those k — the same numbers as a softmax over all experts
  renormalised over the chosen, `norm_topk_prob`), at `moe_intermediate_size`
  (the config's `intermediate_size` is the width of dense layers this model
  has none of). No shared expert.
- A decode step is a BLOCK PASS: `verify_step_paged` with T = B tokens a row
  (the open block's ids, `mask_token_id` where still masked) behind the
  row's committed cache, logits at every position — position i's logits
  predict position i's OWN token, no shift — and the chunk's K and V written
  past the committed length. The block program (engine/programs.py
  _build_block_many) unmasks by confidence and commits a block whose pass
  it entered complete. `decode_step_paged` is kept for the family contract;
  the engine does not dispatch it for a family with BLOCK_LENGTH > 1.

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/deepseek_v3.py's do: the step's expert-load counters,
or under the static `routing=True` what the routers decided (chosen,
router logits f32, kept all true: benchmark/routing.py's contract).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.models import mixtral, stacks
from llmlb_tpu.models.deepseek_v3 import (
    EXPERT_LOAD_COUNTERS,
    _extra,
    step_counters,
)
from llmlb_tpu.models.family import Family
from llmlb_tpu.models.llama import (
    Attention,
    LayerGroup,
    LlamaConfig,
    _decode_paged_impl,
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj,
    _qkv,
)
from llmlb_tpu.models.llama import (  # noqa: F401 — the GQA page pool, reused
    init_kv_pages,
    kv_pages_shardings,
    kv_token_layer_bytes,
    kv_wire_cell,
)
from llmlb_tpu.ops import moe
from llmlb_tpu.ops.attention import (
    gqa_attention_prefill,
    paged_attention_decode,
    paged_attention_extend,
    paged_decode_work,
)
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.rope import apply_rope

Params = dict[str, Any]

REMASKING = ("low_confidence_dynamic", "low_confidence_static")


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig(LlamaConfig):
    num_experts: int = 128
    experts_per_token: int = 8
    moe_intermediate_size: int = 768
    # generation by diffusion over blocks: the engine's defaults for a
    # request that names none (docs/block-diffusion.md)
    block_length: int = 4
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669

    @property
    def num_moe_layers(self) -> int:  # every layer (deepseek_v3._extra)
        return self.num_layers

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "SdarMoeConfig":
        """Build from a published `config.json`, with the generation keys
        the checkpoint's config does not carry read from the top level or
        from an `assumed` group (the benchmark's configuration files). What
        this family does not compute is refused by name."""
        unsupported = {
            "mlp_only_layers": bool(hf.get("mlp_only_layers")),
            "decoder_sparse_step": hf.get("decoder_sparse_step", 1) != 1,
            "use_sliding_window": bool(hf.get("use_sliding_window")),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
            "rope_scaling": hf.get("rope_scaling") is not None,
            "attention_bias": bool(hf.get("attention_bias")),
            "norm_topk_prob": not hf.get("norm_topk_prob", True),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"sdar_moe config key(s) {bad} = {[hf.get(k) for k in bad]} "
                "are not supported by models/sdar_moe.py; refusing to serve "
                "another model under this one's name")
        base = LlamaConfig.from_hf_config({**hf, "attention_bias": False},
                                          dtype)
        fields = {f.name: getattr(base, f.name)
                  for f in dataclasses.fields(base)}
        assumed = hf.get("assumed") or {}

        def generation(key, default):
            return hf.get(key, assumed.get(key, default))

        cfg = cls(
            **fields,
            num_experts=hf["num_experts"],
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            block_length=int(generation("block_length", cls.block_length)),
            denoising_steps=int(generation("denoising_steps",
                                           cls.denoising_steps)),
            remasking_strategy=str(generation("remasking_strategy",
                                              cls.remasking_strategy)),
            confidence_threshold=float(generation("confidence_threshold",
                                                  cls.confidence_threshold)),
            mask_token_id=int(generation("mask_token_id", cls.mask_token_id)),
        )
        check_generation(cfg.block_length, cfg.denoising_steps,
                         cfg.remasking_strategy, cfg.confidence_threshold)
        if not 0 <= cfg.mask_token_id < cfg.vocab_size:
            raise ValueError(f"mask_token_id {cfg.mask_token_id} is outside "
                             f"the vocabulary of {cfg.vocab_size}")
        return cfg


def check_generation(block_length: int, denoising_steps: int,
                     remasking_strategy: str,
                     confidence_threshold: float) -> None:
    """Raise ValueError for generation parameters the procedure has no
    meaning for (a configuration's defaults, and a request's own)."""
    if block_length < 1:
        raise ValueError(f"block_length must be >= 1, got {block_length}")
    if not 1 <= denoising_steps <= block_length \
            or block_length % denoising_steps:
        raise ValueError(
            f"denoising_steps must divide block_length {block_length}, got "
            f"{denoising_steps}")
    if remasking_strategy not in REMASKING:
        raise ValueError(f"remasking_strategy must be one of {REMASKING}, "
                         f"got {remasking_strategy!r}")
    if not 0.0 <= confidence_threshold <= 1.0:
        raise ValueError("confidence_threshold must lie in [0, 1], got "
                         f"{confidence_threshold}")


def block_length(cfg: SdarMoeConfig) -> int:
    """The family's declaration to the scheduler: a decode step of this
    configuration is a block pass over this many positions a row."""
    return cfg.block_length


def init_params(cfg: SdarMoeConfig, key: jax.Array) -> Params:
    """Random init for tests/benches: Mixtral's leaves at the expert width,
    and the two head norms (ones, as every norm)."""
    params = mixtral.init_params(
        dataclasses.replace(cfg, intermediate_size=cfg.moe_intermediate_size),
        key)
    params["q_norm"] = jnp.ones((cfg.num_layers, cfg.head_dim_), cfg.dtype)
    params["k_norm"] = jnp.ones((cfg.num_layers, cfg.head_dim_), cfg.dtype)
    return params


def param_shardings(cfg: SdarMoeConfig, mesh: Mesh, rules=None):
    axes = mixtral.param_logical_axes(cfg)
    axes["q_norm"] = axes["k_norm"] = ("layers", "head_dim")
    return stacks.param_shardings(cfg, mesh, rules, axes)


_STACKED = (*mixtral._STACKED, "q_norm", "k_norm")


def _qk_norm_block(cfg: SdarMoeConfig, lp: Params, x, positions, inv_freq,
                   attn_fn, lora_idx=None):
    """llama._attn_block with each query and key head RMS-normed before
    RoPE. Returns (x_out, roped_k, v)."""
    b, t, _ = x.shape
    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    q, k, v = _qkv(cfg, lp, h, lora_idx)
    q = apply_rope(rms_norm(q, lp["q_norm"], cfg.rms_eps), positions,
                   inv_freq)
    k = apply_rope(rms_norm(k, lp["k_norm"], cfg.rms_eps), positions,
                   inv_freq)
    attn = attn_fn(q, k, v)
    return x + _proj(lp, "wo", attn.reshape(b, t, -1), lora_idx), k, v


def _attention(cfg: SdarMoeConfig) -> Attention:
    b = cfg.block_length
    return Attention(_qk_norm_block,
                     partial(gqa_attention_prefill, block=b),
                     partial(paged_attention_extend, block=b),
                     paged_attention_decode, paged_decode_work)


def _moe_mlp_fn(cfg: SdarMoeConfig, live=None):
    """mixtral._moe_mlp_fn giving the layer's ops/moe.Routing as aux.
    `live` ([B] bool) stands in for `token_valid` where the body has none
    (decode)."""

    def route(logits):
        return moe.top_k_routing(logits, cfg.experts_per_token)

    def fn(lp, h, token_valid, lora_idx=None):
        b, t, m = h.shape
        flat = h.reshape(b * t, m)
        if token_valid is None and live is not None:
            token_valid = jnp.broadcast_to(live[:, None], (b, t))
        logits = jnp.einsum("sm,mx->sx", flat, lp["router"],
                            preferred_element_type=jnp.float32)
        out, routing = moe.moe_routed(
            flat, logits, lp["we_gate"], lp["we_up"], lp["we_down"],
            route=route, layer=lp["layer"],
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)))
        return out.reshape(b, t, m), routing

    return fn


def _groups(cfg: SdarMoeConfig, live=None) -> list[LayerGroup]:
    return [LayerGroup(_STACKED, _moe_mlp_fn(cfg, live), cfg.num_layers,
                       whole=("we_gate", "we_up", "we_down"))]


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: SdarMoeConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False):
    """Continuous-batching insert path under the block mask: the prompt's
    WHOLE blocks (`prompt_lens` a multiple of the block length). Same
    contract as llama.prefill_into_pages otherwise; the last position's
    logits predict that position's own token and the engine discards them."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, input_ids.shape, routing))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: SdarMoeConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False):
    """Chunked-prefill append path under the block mask (`start_pos` and
    `chunk_lens` multiples of the block length)."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, input_ids.shape, routing))


@partial(jax.jit, static_argnames=_STATIC + ("window", "logits_len"),
         donate_argnames=("cache_k", "cache_v"))
def verify_step_paged(params, cfg: SdarMoeConfig, input_ids, chunk_lens,
                      start_pos, block_tables, cache_k, cache_v,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, routing: bool = False,
                      logits_from=None, logits_len: int | None = None):
    """The BLOCK PASS: T ids a row (whole blocks: one, or a complete block
    and the block of masks behind it) after `start_pos` committed tokens,
    logits at every position ([B, T, V] fp32) under the block mask, the
    chunk's K and V written past the committed length. Positions at and
    past a row's `chunk_lens` are padding: they go to no expert and their
    logits are to be discarded; a row with `chunk_lens` 0 is not decoding.
    `logits_from` [B]: logits at `logits_len` positions a row from that
    offset alone, [B, logits_len, V] (llama._prefill_extend_paged_impl)."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, all_logits=True, window=window, lora_idx=lora_idx,
        groups=_groups(cfg), attention=_attention(cfg),
        logits_from=logits_from, logits_len=logits_len)
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, input_ids.shape, routing))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: SdarMoeConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False):
    """One token a row, for the family contract (a block of one position:
    what it sees of the cache is what the block mask lets it see)."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, (input_ids.shape[0], 1), routing))


# Refused: int8 weights (the quant names do not cover the head norms' place
# in the projections' numerics, and the precision control of the benchmark
# is that very substitution) and LoRA pools. `logits_from`: one block's
# logits a row from the row's own offset into a chunk of two.
FAMILY = Family(
    name="sdar_moe", config_class=SdarMoeConfig, model_types=("sdar_moe",),
    mechanism_keys=("num_experts", "moe_intermediate_size"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    block_length=block_length, check_generation=check_generation,
    int8_weights=False, lora=False,
    counters=EXPERT_LOAD_COUNTERS, step_counters=step_counters,
    paged_keywords=("routing",),
    keywords_of={"verify_step_paged": ("logits_from", "logits_len")})
