"""LongCat-Flash-class decoder (`model_type` `longcat_flash`: the language
model of LongCat-Flash / LongCat-Flash-Omni): DOUBLE layers whose mixture of
experts is a shortcut across two latent attentions, and a router whose
outputs are experts AND zero-compute experts.

Same serving contract and the same three shared bodies as models/llama.py
(docs/longcat-flash.md); what differs is handed to them:

- One layer of the config is two SUB-LAYERS, each a latent attention and a
  dense SwiGLU on the plain residual path, and one mixture M:

      a = x + A_0(N_0(x))     h = N_1(a)
      b = a + F_0(h)          s = M(h)          # not added here
      c = b + A_1(N_2(b))
      y = c + F_1(N_3(c)) + s                   # joins after sub-layer 1

  The walk is a llama.LayerGroup a sub-layer: sub-layer 0 (`s0_` leaves,
  [L, ...]) has the mixture as its deferred `branch`, sub-layer 1 (`s1_`)
  `joins` it. The page pool's layer axis counts attention sub-layers
  (2 L: `kv_pool_layers`), sub-layer j of layer i at 2 i + j.
- The attention is models/deepseek_v3.py's latent block and `Attention`,
  with a low-rank query (`wq_a`, `ln_q`, `wq_b`) and the two latents scaled
  behind their norms (`mla_scale_q_lora`: sqrt(hidden / q_lora_rank);
  `mla_scale_kv_lora`: sqrt(hidden / kv_lora_rank)). The pool keeps the
  SCALED latent and the unscaled rope key: one of each a token and
  sub-layer, as that family's.
- The mixture is ops/moe.py's routed layer with `softmax_bias_routing` over
  ALL the router's outputs, `router_experts` experts and behind them
  `zero_experts` identity experts (`real=`): an assignment of one adds the
  token itself times its weight and is no product. A chip may hold a SHARE
  of the experts (`expert_parallel` in the config: `held_experts`); the
  identity experts need no chip.

Not served, each refused by name: int8 weights, LoRA pools, an int8 page
pool, KV on the wire (`kv_wire_cell` None: a handoff, resume or park replays
its tokens); what `from_hf_config` lists. There is no checkpoint loader yet
(engine/weights.py): seeded weights serve the tests and the benchmark.

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/deepseek_v3.py's do: the step's counters, or under the
static `routing=True` what the routers decided (scores over all the
router's outputs).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.models import stacks
from llmlb_tpu.models.deepseek_v3 import (  # noqa: F401 — family contract
    EXPERT_LOAD_COUNTERS,
    LOAD_BUCKETS,
    ROPE_CELL,
    DeepseekV3Config,
    _attention,
    _extra as _routed_extra,
    held_share,
    kv_pages_shardings,
    kv_token_layer_bytes,
    kv_wire_cell,
)
from llmlb_tpu.models.family import Family, StepCounter
from llmlb_tpu.models.llama import (
    LayerGroup,
    _decode_paged_impl,
    _default_mlp_fn,
    _prefill_extend_paged_impl,
    _prefill_impl,
)
from llmlb_tpu.ops import moe

Params = dict[str, Any]
F32 = jnp.float32

SUB = ("s0_", "s1_")  # the prefixes of a layer's two sub-layers' leaves


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig(DeepseekV3Config):
    """`num_layers` counts DOUBLE layers; `intermediate_size` is the dense
    feed-forwards' width and `moe_intermediate_size` the experts'.
    `num_experts` are the experts THIS CHIP holds, [first_expert, +
    num_experts) of the `router_experts` the router scores before its
    `zero_experts` identity outputs."""

    q_lora_rank: int | None = 1536
    num_shared_experts: int = 0
    first_k_dense: int = 0
    norm_topk_prob: bool = False
    zero_experts: int = 256
    router_experts: int = 512
    first_expert: int = 0

    @property
    def router_width(self) -> int:
        return self.router_experts + self.zero_experts

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the router's experts this chip holds."""
        return self.first_expert, self.num_experts

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16
                       ) -> "LongcatFlashConfig":
        """Build from a published `config.json`, by the names the family
        publishes (`num_layers`, `ffn_hidden_size`, `expert_ffn_hidden_size`,
        `moe_topk`, `zero_expert_num`). What this family does not compute is
        refused by name. `expert_parallel` ({"chips", "chip", "experts"}) is
        the deployment's, not the checkpoint's: this chip holds
        `n_routed_experts` of the router's `experts`, the `chip`-th share."""
        unsupported = {
            "attention_method": hf.get("attention_method", "MLA") != "MLA",
            "zero_expert_type": (hf.get("zero_expert_type", "identity")
                                 != "identity"),
            "rope_scaling": hf.get("rope_scaling") is not None,
            "attention_bias": bool(hf.get("attention_bias")),
            "router_bias": bool(hf.get("router_bias")),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
            "q_lora_rank": not hf.get("q_lora_rank"),
            "n_shared_experts": bool(hf.get("n_shared_experts")),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"longcat_flash config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/longcat_flash.py; refusing to serve wrong logits")
        held, experts, first = held_share(hf)
        hidden, rope = hf["hidden_size"], hf.get("qk_rope_head_dim", 64)

        def lora_scale(key: str, rank: int) -> float:
            return math.sqrt(hidden / rank) if hf.get(key) else 1.0

        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            intermediate_size=hf["ffn_hidden_size"],
            num_layers=hf["num_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=rope,  # what RoPE turns: the bodies' rope_frequencies
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            dtype=dtype,
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
            q_lora_rank=hf["q_lora_rank"],
            q_lora_scale=lora_scale("mla_scale_q_lora", hf["q_lora_rank"]),
            kv_lora_scale=lora_scale("mla_scale_kv_lora", hf["kv_lora_rank"]),
            num_experts=held,
            router_experts=experts,
            first_expert=first,
            zero_experts=int(hf.get("zero_expert_num", 0)),
            experts_per_token=hf["moe_topk"],
            moe_intermediate_size=hf["expert_ffn_hidden_size"],
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            rope_interleave=bool(hf.get("rope_interleave", True)),
        )


# ---------------------------------------------------------------------------
# Params: a stack a sub-layer, the mixture's with sub-layer 0
# ---------------------------------------------------------------------------

_ATTN = ("ln_attn", "wq_a", "ln_q", "wq_b", "wkv_a", "ln_kv", "wk_b", "wv_b",
         "wo")
_DENSE_MLP = ("ln_mlp", "wg", "wu", "wd")
_MOE = ("router", "router_bias", "we_gate", "we_up", "we_down")
_EXPERTS = ("we_gate", "we_up", "we_down")
_NAMES = (_ATTN + _DENSE_MLP + _MOE, _ATTN + _DENSE_MLP)


def _layer_shapes(cfg: LongcatFlashConfig) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule). The
    fan-in of a projection behind a SCALED latent counts the scale: its
    input has a root mean square of `scale`, so rank x scale^2 inputs of 1
    (the hidden size, for the published scales: they make a latent stand
    for a hidden-wide input). By the rank alone the attention's logits
    would have a standard deviation of 5.8 at the published widths, each
    sub-layer would multiply the error it is handed, and a bf16 program of
    random weights read 0.45 against float32 (PERF.md section 6, PR 41)."""
    e, h, c, r = (cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank,
                  cfg.q_lora_rank)
    q_in = round(r * cfg.q_lora_scale ** 2)
    kv_in = round(c * cfg.kv_lora_scale ** 2)
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f, x, fm = cfg.intermediate_size, cfg.num_experts, cfg.moe_intermediate_size
    return {
        "ln_attn": ((e,), 0), "wq_a": ((e, r), e), "ln_q": ((r,), 0),
        "wq_b": ((r, h * (dn + dr)), q_in), "wkv_a": ((e, c + dr), e),
        "ln_kv": ((c,), 0),
        "wk_b": ((h, c, dn), kv_in), "wv_b": ((h, c, dv), kv_in),
        "wo": ((h * dv, e), h * dv),
        "ln_mlp": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, cfg.router_width), e),
        "router_bias": ((cfg.router_width,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
    }


def _leaves(cfg: LongcatFlashConfig) -> list[stacks.Leaf]:
    """Every stacked leaf [num_layers, ...]: a stack a sub-layer."""
    return stacks.stack_leaves(_layer_shapes(cfg), [
        (prefix, names, cfg.num_layers) for prefix, names in zip(SUB, _NAMES)])


def router_bias_sd(cfg: LongcatFlashConfig) -> float:
    """Standard deviation of the seeded choice bias: a tenth of the mean
    score of a softmax over the router's outputs (1 / width). The other
    mixtures' 0.02 is a tenth of a sigmoid score's spread; here it would be
    fifteen times the mean score and the bias alone would choose."""
    return 0.1 / cfg.router_width


def init_params(cfg: LongcatFlashConfig, key: jax.Array) -> Params:
    """Random init (this backs tests and the benchmark): matrices normal x
    fan_in^-0.5 (_layer_shapes says what a scaled latent's fan-in is), norms
    ones, the router's choice bias a seeded normal that
    is NOT zero (deepseek_v3.init_params says why), of router_bias_sd."""
    return stacks.init_params(cfg, key, _leaves(cfg),
                              stacks.seeded_bias(router_bias_sd(cfg)))


def param_logical_axes(cfg: LongcatFlashConfig) -> dict[str, tuple]:
    layer = {
        **stacks.MLP_AXES, **stacks.EXPERT_AXES,
        "wq_b": (None, "heads"), "wk_b": ("heads", None, None),
        "wv_b": ("heads", None, None), "wo": ("heads", "embed"),
    }
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: LongcatFlashConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The latent page pool, a layer of it an attention SUB-layer
# ---------------------------------------------------------------------------

def kv_pool_layers(cfg: LongcatFlashConfig) -> int:
    """Layers of the page pool: two attention sub-layers a layer."""
    return len(SUB) * cfg.num_layers


def init_kv_pages(cfg: LongcatFlashConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False):
    """deepseek_v3's latent pool with a layer an attention sub-layer: the
    scaled latent [2 L, P, PS, kv_lora_rank] and the rope key's tile-wide
    row [2 L, P, PS, 128]. Page 0 is the trash page."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    lead = (kv_pool_layers(cfg), num_pages, page_size)
    return (jnp.zeros((*lead, cfg.kv_lora_rank), dtype),
            jnp.zeros((*lead, ROPE_CELL), dtype))


# ---------------------------------------------------------------------------
# The mixture and the stack
# ---------------------------------------------------------------------------

def _mixture_fn(cfg: LongcatFlashConfig, live=None):
    """llama.LayerGroup's `branch(lp, h, token_valid, lora_idx)`: the
    routed experts this chip holds and the identity experts, by the
    family's rule over all the router's outputs, and as aux the layer's
    ops/moe.Routing. `live`: as deepseek_v3._moe_mlp_fn."""
    held = (None if cfg.num_experts == cfg.router_experts
            else cfg.held_experts)

    def fn(lp, h, token_valid, lora_idx=None):
        b, t, m = h.shape
        flat = h.reshape(b * t, m)
        if token_valid is None and live is not None:
            token_valid = jnp.broadcast_to(live[:, None], (b, t))
        logits = jnp.einsum("sm,mx->sx", flat, lp["router"],
                            preferred_element_type=F32)
        out, routing = moe.moe_routed(
            flat, logits, lp["we_gate"], lp["we_up"], lp["we_down"],
            layer=lp["layer"], held=held, real=cfg.router_experts,
            route=lambda r: moe.softmax_bias_routing(
                r, lp["router_bias"], cfg.experts_per_token,
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob),
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
        )
        return out.reshape(b, t, m), routing

    return fn


def _groups(cfg: LongcatFlashConfig, live=None) -> list[LayerGroup]:
    """A group a sub-layer, in order: layer i's sub-layer 0 leaves the
    mixture's output for its sub-layer 1 to add."""
    mixture = _mixture_fn(cfg, live)
    groups = []
    for i in range(cfg.num_layers):
        for j, (prefix, names) in enumerate(zip(SUB, _NAMES)):
            groups.append(LayerGroup(
                names=names, mlp_fn=_default_mlp_fn, count=1, prefix=prefix,
                start=i, pool_layer=len(SUB) * i + j, scope=f"sublayer{j}",
                **(dict(joins=True) if j else dict(branch=mixture,
                                                   whole=_EXPERTS))))
    return groups


def step_counters(cfg: LongcatFlashConfig) -> dict[str, tuple]:
    """The counters a paged serving call returns, by name and shape (all
    int32): deepseek_v3's expert load over the HELD experts, the
    assignments that went to experts another chip holds and those that
    went to zero-compute experts. The three assignment counts add up to
    rows x experts_per_token a layer."""
    return {"experts_touched": (), "expert_assignments": (),
            "expert_load_max": (), "assignments_elsewhere": (),
            "zero_assignments": (),
            "expert_load_hist": (cfg.num_layers, len(LOAD_BUCKETS) + 1)}


def _extra(cfg: LongcatFlashConfig, aux, shape, routing: bool):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a group;
    the sub-layers 0 have the mixtures', stacked here over the layers."""
    found = [a[0] if isinstance(a, list) else jax.tree.map(lambda v: v[0], a)
             for a in aux[::len(SUB)]]
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *found)
    out = _routed_extra(cfg, [stacked], shape, routing)
    if routing:
        return out

    def total(counts):
        return (jnp.zeros((), jnp.int32) if counts is None
                else jnp.sum(counts, dtype=jnp.int32))

    return ({**out[0], "assignments_elsewhere": total(stacked.elsewhere),
             "zero_assignments": total(stacked.zero)},)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: LongcatFlashConfig, input_ids,
                       prompt_lens, block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages, its HANDOFF CONTRACT included."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, input_ids.shape, routing))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: LongcatFlashConfig, input_ids,
                         chunk_lens, start_pos, block_tables, cache_k,
                         cache_v, mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, input_ids.shape, routing))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def verify_step_paged(params, cfg: LongcatFlashConfig, input_ids, chunk_lens,
                      start_pos, block_tables, cache_k, cache_v,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, routing: bool = False):
    """Speculative verification. Same contract as llama.verify_step_paged."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, all_logits=True, window=window, lora_idx=lora_idx,
        groups=_groups(cfg), attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, input_ids.shape, routing))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: LongcatFlashConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_attention(cfg))
    return (logits, cache_k, cache_v,
            *_extra(cfg, aux, (input_ids.shape[0], 1), routing))


FAMILY = Family(
    name="longcat_flash", config_class=LongcatFlashConfig,
    model_types=("longcat_flash",),
    mechanism_keys=("kv_lora_rank", "q_lora_rank", "zero_expert_num",
                    "n_routed_experts", "expert_parallel"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, pool="latent page pool",
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "assignments_elsewhere": StepCounter(
            "sum", "moe_assignments_elsewhere_total"),
        "zero_assignments": StepCounter("sum", "moe_zero_assignments_total")},
    step_counters=step_counters, paged_keywords=("routing",))
