"""Mixtral-family sparse-MoE decoder (Mixtral-8x7B/8x22B, Qwen-MoE-class).

Same serving-shaped skeleton as models/llama.py (stacked layers + lax.scan,
static-shape prefill/decode over the paged KV pool, GQA attention ops) with the
dense SwiGLU MLP swapped for top-k routed experts (ops/moe.py: assignments
sorted by expert, grouped products, nothing dropped). Expert weights
carry an `experts` logical axis mapped to the mesh `ep` axis, so a
Mixtral-8x7B spans a multi-chip mesh as dp × ep × tp with GSPMD inserting the
dispatch/combine all-to-alls (BASELINE.json config #5 class).

The reference gateway does no inference and has no MoE (SURVEY.md §2.4); this
model family is new TPU-native design for the in-tree tpu:// engine.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.models import stacks
from llmlb_tpu.models.family import Family
from llmlb_tpu.models.llama import (
    LayerGroup,
    LlamaConfig,
    _decode_paged_impl,
    _mixed_paged_impl,
    _prefill_extend_paged_impl,
    _prefill_impl,
)
from llmlb_tpu.ops import moe

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "MixtralConfig":
        base = LlamaConfig.from_hf_config(hf, dtype)
        fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
        return cls(
            **fields,
            num_experts=hf.get("num_local_experts", hf.get("num_experts", 8)),
            experts_per_token=hf.get("num_experts_per_tok", 2),
        )


def init_params(cfg: MixtralConfig, key: jax.Array) -> Params:
    """Random init for tests/benches; serving loads HF checkpoints."""
    d = cfg.head_dim_
    h, k_, e = cfg.num_heads, cfg.num_kv_heads, cfg.hidden_size
    f, l_, x_ = cfg.intermediate_size, cfg.num_layers, cfg.num_experts
    keys = iter(jax.random.split(key, 16))

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(
            cfg.dtype
        )

    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, e), e),
        "wq": w(next(keys), (l_, e, h * d), e),
        "wk": w(next(keys), (l_, e, k_ * d), e),
        "wv": w(next(keys), (l_, e, k_ * d), e),
        "wo": w(next(keys), (l_, h * d, e), h * d),
        "router": w(next(keys), (l_, e, x_), e),
        "we_gate": w(next(keys), (l_, x_, e, f), e),
        "we_up": w(next(keys), (l_, x_, e, f), e),
        "we_down": w(next(keys), (l_, x_, f, e), f),
        "ln_attn": jnp.ones((l_, e), cfg.dtype),
        "ln_mlp": jnp.ones((l_, e), cfg.dtype),
        "ln_final": jnp.ones((e,), cfg.dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (e, cfg.vocab_size), e)
    return params


def param_logical_axes(cfg: MixtralConfig) -> dict[str, tuple]:
    axes = {
        "embed": ("vocab", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "router": ("layers", "embed", None),  # router replicated: tiny
        "we_gate": ("layers", "experts", "embed", "ffn"),
        "we_up": ("layers", "experts", "embed", "ffn"),
        "we_down": ("layers", "experts", "ffn", "embed"),
        "ln_attn": ("layers", "embed"),
        "ln_mlp": ("layers", "embed"),
        "ln_final": ("embed",),
    }
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    # Int8 per-output-channel scales (llmlb_tpu/quant): the weight's axes
    # with the input (contraction) axis dropped. Extra entries for absent
    # leaves are never consulted.
    for name in ("wq", "wk", "wv", "wo"):
        axes[name + "_scale"] = (axes[name][0], axes[name][2])
    for name in ("we_gate", "we_up", "we_down"):
        w_axes = axes[name]
        axes[name + "_scale"] = (w_axes[0], w_axes[1], w_axes[3])
    # LoRA adapter pools (llmlb_tpu/lora): attention projections only — MoE
    # engines serve attention-target adapters; expert-FFN deltas are out of
    # scope (the routed dispatch would need per-expert per-adapter factors).
    for name in ("wq", "wk", "wv", "wo"):
        w_axes = axes[name]
        axes[name + "_lora_a"] = (w_axes[0], None, w_axes[1], None)
        axes[name + "_lora_b"] = (w_axes[0], None, None, w_axes[2])
    return axes


def param_shardings(cfg: MixtralConfig, mesh: Mesh, rules=None):
    # the dense family's rules (ShardingRules already maps experts -> "ep")
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# The KV page pool is identical to llama's — reuse.
from llmlb_tpu.models.llama import (  # noqa: E402,F401
    init_kv_pages,
    kv_pages_shardings,
    kv_token_layer_bytes,
    kv_wire_cell,
)


_STACKED = ["wq", "wk", "wv", "wo", "router", "we_gate", "we_up", "we_down",
            "ln_attn", "ln_mlp"]


def _moe_mlp_fn(cfg: MixtralConfig):
    """Adapter matching llama's `mlp_fn(lp, h, token_valid, lora_idx)`
    contract: x [B, T, E] -> [B, T, E] through the routed layer of
    ops/moe.py, at every size — every assignment is computed, nothing is
    dropped, so a request's logits do not depend on which other rows share
    the batch. `token_valid` keeps padding out of the grouped products.
    `lora_idx` is accepted and ignored: MoE engines serve attention-target
    adapters only (the expert FFNs carry no LoRA pools, so there is
    nothing for the index to select)."""

    def route(logits):
        # looked up at trace time: benchmark/routing.py's tap wraps it
        return moe.top_k_routing(logits, cfg.experts_per_token)

    def fn(lp, h, token_valid, lora_idx=None):
        b, t, m = h.shape
        flat = h.reshape(b * t, m)
        # int8 expert weights carry per-output-channel scales
        # (llmlb_tpu/quant); absent on bf16 pytrees
        scales = {
            f"w_{k}_scale": lp.get(f"we_{k}_scale")
            for k in ("gate", "up", "down")
        }
        out, _ = moe.moe_routed(
            flat, flat @ lp["router"], lp["we_gate"], lp["we_up"],
            lp["we_down"], route=route,
            layer=lp["layer"],  # the stacks whole: llama.LayerGroup.whole
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
            **scales,
        )
        return out.reshape(b, t, m)

    return fn


def _groups(cfg: MixtralConfig) -> list[LayerGroup]:
    """One group of expert layers, its experts handed whole beside the
    layer's index: on the chip the grouped products read the stack in place
    (ops/pallas_moe.py), as the latent family's do."""
    return [LayerGroup(tuple(_STACKED), _moe_mlp_fn(cfg), cfg.num_layers,
                       whole=("we_gate", "we_up", "we_down"))]


@partial(jax.jit, static_argnames=("cfg", "mesh"),
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: MixtralConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages — including its HANDOFF CONTRACT
    (docs/disaggregation.md): final-row logits aligned to batch rows and
    position-exact KV, so split-mode
    staging and cross-process replay hold for MoE engines too (the router
    is position-independent; expert choice rides the token, not the
    slot, so a handed-off stream routes identically on the adopter)."""
    return _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        groups=_groups(cfg), lora_idx=lora_idx,
    )[:3]


@partial(jax.jit, static_argnames=("cfg", "mesh"),
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: MixtralConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages."""
    return _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, groups=_groups(cfg), lora_idx=lora_idx,
    )[:3]


@partial(jax.jit, static_argnames=("cfg", "mesh", "window"),
         donate_argnames=("cache_k", "cache_v"))
def verify_step_paged(params, cfg: MixtralConfig, input_ids, chunk_lens,
                      start_pos, block_tables, cache_k, cache_v,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None):
    """Speculative verification. Same contract as llama.verify_step_paged."""
    return _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, groups=_groups(cfg),
        all_logits=True, window=window, lora_idx=lora_idx,
    )[:3]


@partial(jax.jit, static_argnames=("cfg", "mesh", "window"),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: MixtralConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged."""
    return _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        groups=_groups(cfg), window=window, lora_idx=lora_idx, live=live,
    )[:3]


@partial(jax.jit, static_argnames=("cfg", "mesh", "window"),
         donate_argnames=("cache_k", "cache_v"))
def mixed_step_paged(params, cfg: MixtralConfig, input_ids, seq_lens,
                     cache_k, cache_v, block_tables, prompt_ids, prompt_len,
                     prompt_row, mesh: Mesh | None = None,
                     window: int | None = None, live=None):
    """One decode step with one arrival's prompt in the same pass. Same
    contract as llama.mixed_step_paged: every assignment is computed at
    every size, so a token's experts do not depend on what shares the pass,
    and the prompt's padding is kept out of the grouped products."""
    return _mixed_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        prompt_ids, prompt_len, prompt_row, groups=_groups(cfg),
        window=window, live=live,
    )[:3]


FAMILY = Family(
    name="mixtral", config_class=MixtralConfig, model_types=("mixtral",),
    mechanism_keys=("num_local_experts", "num_experts"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    mixed_step=True)
