"""Kimi-Linear-class decoder (`model_type` `kimi_linear`:
moonshotai/Kimi-Linear-48B-A3B): a stack whose layers mix their tokens by
KIMI DELTA ATTENTION (KDA: a gated delta rule whose decay is a number a KEY
CHANNEL, not a number a head) or by multi-head LATENT attention WITHOUT a
rotary embedding, in the order `linear_attn_config` spells (three to one as
published), under one leading dense feed-forward and then sigmoid-routed
mixtures with a shared expert. docs/kimi-linear.md has the equations.

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them as `LayerGroup`s, one a RUN of like layers
(granite_hybrid's runs: `K | KK A | KKK A x 5 | KK A` as published), each
with stacks of its own:

- KDA (`kda_mixer`): pre-norm; `[q | k | v] = h W_qkv`, a causal depthwise
  convolution of `short_conv_kernel_size` taps over time and SiLU; per head
  q and k L2-normalised (q times K^-1/2); the decay `g = -exp(A_log[h])
  softplus((h W_fa) W_fb + dt_bias)` in R^{H x K}, `b = sigmoid(h W_b)` in
  R^H; the rule of ops/delta_rule.py with a decay a key channel; per head
  an RMS norm over the V channels (one weight for all heads) times
  `sigmoid((h W_ga) W_gb)`; `W_o` (`W_fa | W_ga | W_b` stored side by side
  as `w_low`, one product: `_SIDE_BY_SIDE`). What a sequence carries
  between calls lives per SLOT beside the page pool (llama.StatePool), as
  models/olmo_hybrid.py's does: `cache_k.state` [n_K, slots, K, H * V]
  float32 and `cache_v.state` [n_K, taps - 1, slots, channels], the rows of
  [q | k | v] before the convolution.
- Latent attention: deepseek_v3's block and its two pools under the same
  page ids (`cache_k.pages` the latents [n_A, P, PS, kv_lora_rank],
  `cache_v.pages` the shared key's cell [n_A, P, PS, 128]) with `mla_nope`:
  the 64 numbers are projected, cached and scored at (128 + 64)^-1/2, and
  nothing is rotated. The FIRST family whose slot holds a recurrent state
  beside a LATENT pool; the bodies and the scheduler needed no branch.
- The mixture: ops/moe.py's routed layer with `sigmoid_bias_routing` (the
  choice by score + `e_score_correction_bias`, the weights the unbiased
  scores of the chosen over their sum, times `routed_scaling_factor`),
  three-matrix SwiGLU experts and one shared expert. A chip may hold a
  SHARE of the experts (`expert_parallel` in the config, `held_experts`):
  the router scores all of them, the assignments of the others are another
  chip's.

Not served, each refused by name: a grouped choice of experts
(`num_expert_group` / `topk_group` other than 1), a router that is no
sigmoid, `mla_use_nope` false, a low-rank query; speculative decoding
(`verify_step_paged` is absent: a rejected draft would need the state rolled
back), an int8 pool, KV on the wire, int8 weights and LoRA pools; the engine
refuses the prefix cache, the offload tier and the split role for a family
with state per slot (scheduler.py).

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

from llmlb_tpu.models import granite_hybrid, stacks
from llmlb_tpu.models.deepseek_v3 import (
    EXPERT_LOAD_COUNTERS,
    LOAD_BUCKETS,
    ROPE_CELL,
    DeepseekV3Config,
    _attention,
    _extra as _routed_extra,
    held_share,
)
from llmlb_tpu.models.family import Family, StepCounter
from llmlb_tpu.models.llama import (
    LayerGroup,
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
from llmlb_tpu.models.olmo_hybrid import _conv_step, _own_rule, _unit
from llmlb_tpu.ops import delta_rule, moe, ssm
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

KDA, MLA = "kda", "mla"  # the two mixers
KDA_DENSE, KDA_MOE, MLA_MOE = "kda_dense", "kda_moe", "mla_moe"  # a layer


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(DeepseekV3Config):
    mla_nope: bool = True
    mixers: tuple[str, ...] = (KDA, KDA, KDA, MLA)  # a layer's, in order
    kda_heads: int = 32
    kda_head_dim: int = 128  # of a key and of a value
    conv_kernel: int = 4
    chunk_size: int = delta_rule.CHUNK
    # the routed experts THIS CHIP holds (`num_experts`, the weights' expert
    # axis): all the router scores, or a share [first_expert, + num_experts)
    router_experts: int = 256
    first_expert: int = 0

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the router's experts this chip holds."""
        return self.first_expert, self.num_experts

    @property
    def kda_rank(self) -> int:
        """Of the decay's and the gate's low-rank pairs: a head's width."""
        return self.kda_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of [q | k | v], what the convolution runs over."""
        return 3 * self.kda_heads * self.kda_head_dim

    def layers_of(self, mixer: str) -> int:
        return self.mixers.count(mixer)

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """A layer's mixer and feed-forward, in order."""
        return tuple(
            f"{mixer}_{'dense' if at < self.first_k_dense else 'moe'}"
            for at, mixer in enumerate(self.mixers))

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "KimiLinearConfig":
        """Build from a published `config.json`. `linear_attn_config` counts
        its layers from 1. What this family does not compute is refused by
        name. `expert_parallel` ({"chips", "chip", "experts"}) is the
        deployment's, not the checkpoint's: this chip holds `num_experts` of
        the router's `experts`, the `chip`-th such share."""
        lin = hf.get("linear_attn_config") or {}
        layers = hf["num_hidden_layers"]
        kda = set(lin.get("kda_layers") or ())
        full = set(lin.get("full_attn_layers") or ())
        held, experts, first = held_share(hf, "num_experts")
        unsupported = {
            "linear_attn_config": (
                bool(kda & full) or kda | full != set(range(1, layers + 1))
                or bool(set(lin) - {"kda_layers", "full_attn_layers",
                                    "head_dim", "num_heads",
                                    "short_conv_kernel_size"})),
            "mla_use_nope": not hf.get("mla_use_nope"),
            "q_lora_rank": hf.get("q_lora_rank") is not None,
            "rope_scaling": hf.get("rope_scaling") is not None,
            # the grouped choice is not computed here: one group, of which
            # one is taken, is the plain top-k whatever `use_grouped_topk`
            "num_expert_group": hf.get("num_expert_group", 1) != 1,
            "topk_group": hf.get("topk_group", 1) != 1,
            "moe_router_activation_func": hf.get(
                "moe_router_activation_func", "sigmoid") != "sigmoid",
            "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
            "num_shared_experts": hf.get("num_shared_experts", 1) < 1,
            "num_nextn_predict_layers": bool(
                hf.get("num_nextn_predict_layers")),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"kimi_linear config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/kimi_linear.py; refusing to serve wrong logits")
        rope = hf.get("qk_rope_head_dim", 64)
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=layers,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=rope,  # the bodies' rope_frequencies; nothing rotates
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get(
                "model_max_length", hf.get("max_position_embeddings", 4096)),
            dtype=dtype,
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
            num_experts=held,
            router_experts=experts,
            first_expert=first,
            experts_per_token=hf["num_experts_per_token"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_shared_experts=int(hf.get("num_shared_experts", 1)),
            first_k_dense=min(layers, int(hf.get("first_k_dense_replace", 0))),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("moe_renormalize", True)),
            mixers=tuple(KDA if at in kda else MLA
                         for at in range(1, layers + 1)),
            kda_heads=lin["num_heads"],
            kda_head_dim=lin["head_dim"],
            conv_kernel=int(lin.get("short_conv_kernel_size", 4)),
        )


# ---------------------------------------------------------------------------
# Params: a stack a run of like layers
# ---------------------------------------------------------------------------

_KDA = ("ln_mix", "wqkv", "conv_w", "w_low", "wf_b", "dt_bias", "a_log",
        "wg_b", "gate_norm", "wo_kda")
_MLA = ("ln_attn", "wq", "wkv_a", "ln_kv", "wk_b", "wv_b", "wo")
_DENSE_MLP = ("ln_mlp", "wg", "wu", "wd")
_MOE_MLP = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down",
            "ws_gu", "ws_down")
_EXPERTS = ("we_gate", "we_up", "we_down")
_NAMES = {KDA_DENSE: _KDA + _DENSE_MLP, KDA_MOE: _KDA + _MOE_MLP,
          MLA_MOE: _MLA + _MOE_MLP, MLA + "_dense": _MLA + _DENSE_MLP}
# Projections of one input stored SIDE BY SIDE, which changes no value: the
# decay's first matrix, the gate's first matrix and the write strength's
# (`w_low` [E, 128 + 128 + H]); the shared expert's gate and up (`ws_gu`).
# A product and a staged operand fewer each a layer and decode step, and the
# step's operation COUNT is what a traced window pays for (PERF.md section
# 7, From PR 62). They are DRAWN apart (`_DRAWN`: a kind's leaves in the
# order their keys are split in), so a seed's weights are what they were
# before they were laid side by side.
_SIDE_BY_SIDE = {"w_low": ("wf_a", "wg_a", "wb"),
                 "ws_gu": ("ws_gate", "ws_up")}
_KDA_DRAWN = ("ln_mix", "wqkv", "conv_w", "wf_a", "wf_b", "dt_bias", "a_log",
              "wb", "wg_a", "wg_b", "gate_norm", "wo_kda")
_MOE_DRAWN = ("ln_mlp", "router", "router_bias", "we_gate", "we_up",
              "we_down", "ws_gate", "ws_up", "ws_down")
_DRAWN = {KDA_DENSE: _KDA_DRAWN + _DENSE_MLP, KDA_MOE: _KDA_DRAWN + _MOE_DRAWN,
          MLA_MOE: _MLA + _MOE_DRAWN, MLA + "_dense": _MLA + _DENSE_MLP}


def runs(cfg: KimiLinearConfig) -> list[tuple[str, str, int]]:
    """(prefix of its keys in the pytree, kind, layers) of every run of like
    layers, in order: `r0_` .. (fifteen as published; granite_hybrid.runs
    says why a run is a stack of its own)."""
    return granite_hybrid.runs(cfg.layer_kinds)


def _layer_shapes(cfg: KimiLinearConfig) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, h, c = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f, x, fm = cfg.intermediate_size, cfg.num_experts, cfg.moe_intermediate_size
    fs = fm * cfg.num_shared_experts
    hk, r = cfg.kda_heads * cfg.kda_head_dim, cfg.kda_rank
    return {
        "ln_mix": ((e,), 0), "wqkv": ((e, cfg.conv_dim), e),
        "conv_w": ((cfg.conv_dim, cfg.conv_kernel), 0),
        "w_low": ((e, 2 * r + cfg.kda_heads), e),
        "wf_a": ((e, r), e), "wf_b": ((r, hk), r), "dt_bias": ((hk,), 0),
        "a_log": ((cfg.kda_heads,), 0), "wb": ((e, cfg.kda_heads), e),
        "wg_a": ((e, r), e), "wg_b": ((r, hk), r),
        "gate_norm": ((cfg.kda_head_dim,), 0), "wo_kda": ((hk, e), hk),
        "ln_attn": ((e,), 0), "wq": ((e, h * (dn + dr)), e),
        "wkv_a": ((e, c + dr), e), "ln_kv": ((c,), 0),
        "wk_b": ((h, c, dn), c), "wv_b": ((h, c, dv), c),
        "wo": ((h * dv, e), h * dv),
        "ln_mlp": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, cfg.router_experts), e),
        "router_bias": ((cfg.router_experts,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
        "ws_gate": ((e, fs), e), "ws_up": ((e, fs), e),
        "ws_gu": ((e, 2 * fs), e), "ws_down": ((fs, e), fs),
    }


def _leaves(cfg: KimiLinearConfig, drawn: bool = False) -> list[stacks.Leaf]:
    """Every stacked leaf the config calls for: a stack a run. `drawn`: as
    they are drawn, the leaves of `_SIDE_BY_SIDE` apart."""
    names = _DRAWN if drawn else _NAMES
    return stacks.stack_leaves(_layer_shapes(cfg), [
        (prefix, names[kind], count) for prefix, kind, count in runs(cfg)])


def seeded_vector(cfg, name: str, k, shape):
    """A seeded leaf that is no matrix: the convolution, `A_log` and
    `dt_bias` by the gated-delta-net layer's own rule (olmo_hybrid's, under
    its names for them); the router's choice bias a seeded normal of sd 0.02
    in float32, NOT zero (deepseek_v3.init_params says why); the norms
    ones."""
    if name in ("conv_w", "a_log", "dt_bias"):
        return _own_rule(cfg, "lin_" + name, k, shape)
    return stacks.seeded_bias(0.02)(cfg, name, k, shape)


def init_params(cfg: KimiLinearConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): matrices normal x fan_in^-0.5, the rest by
    `seeded_vector`, so that the decay of a key channel spreads over its
    working range (exp(A_log) in [1, 16] times a softplus around
    `dt_bias`)."""
    params = stacks.init_params(cfg, key, _leaves(cfg, drawn=True),
                                seeded_vector)
    for prefix, kind, _ in runs(cfg):
        for name, parts in _SIDE_BY_SIDE.items():
            if name in _NAMES[kind]:
                params[prefix + name] = jnp.concatenate(
                    [params.pop(prefix + part) for part in parts], axis=-1)
    return params


def param_logical_axes(cfg: KimiLinearConfig) -> dict[str, tuple]:
    """Latent attention, the dense feed-forward and the experts shard as
    deepseek_v3's; the KDA layers' projections replicate (their heads are
    not split: the state pool is one slot's whole)."""
    layer = {
        **stacks.MLP_AXES, **stacks.EXPERT_AXES,
        "wq": ("embed", "heads"), "wkv_a": ("embed", None),
        "wk_b": ("heads", None, None), "wv_b": ("heads", None, None),
        "wo": ("heads", "embed"), "router": ("embed", None),
        "ws_gu": ("embed", "ffn"),
    }
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: KimiLinearConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: latent pages of the attention layers, state of the KDA layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: KimiLinearConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: deepseek_v3's two latent pools over the attention
    layers alone (c [n_A, P, PS, kv_lora_rank] and the shared key's cell
    [n_A, P, PS, 128]) and per slot the rule's state [n_K, slots, K, H * V]
    float32 beside the rows of [q | k | v] the convolution looks back on
    [n_K, taps - 1, slots, channels]. Page 0 is the trash page; the state
    has none (a row that does not advance is masked). `num_slots` 1 serves
    a caller with one row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    lead = (cfg.layers_of(MLA), num_pages, page_size)
    n_k, d = cfg.layers_of(KDA), cfg.kda_head_dim
    return (
        StatePool(jnp.zeros((*lead, cfg.kv_lora_rank), dtype),
                  jnp.zeros((n_k, num_slots, d, cfg.kda_heads * d), F32)),
        StatePool(jnp.zeros((*lead, ROPE_CELL), dtype),
                  jnp.zeros((n_k, cfg.conv_kernel - 1, num_slots,
                             cfg.conv_dim), dtype)),
    )


def kv_pages_shardings(cfg: KimiLinearConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Everything replicates: every head reads the whole latent
    (deepseek_v3.kv_pages_shardings), and a slot's state is one whole."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    pages = logical_to_sharding(mesh, rules, "layers", None, "seq", None)
    state = logical_to_sharding(mesh, rules, "layers", None, None, None)
    return (StatePool(pages, state), StatePool(pages, state))


def kv_pool_layers(cfg: KimiLinearConfig) -> int:
    """Layers of the page pool: the latent-attention layers alone."""
    return cfg.layers_of(MLA)


def kv_token_layer_bytes(cfg: KimiLinearConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool: the latent
    and the shared key's tile-wide cell; a KDA layer leaves nothing per
    token."""
    FAMILY.refuse(int8_kv=quantized)
    return (cfg.kv_lora_rank + ROPE_CELL) * jnp.dtype(cfg.dtype).itemsize


def state_slot_bytes(cfg: KimiLinearConfig) -> int:
    """HBM bytes one slot holds beside its pages, whatever its context: the
    rule's state and the convolution's rows of every KDA layer."""
    per_layer = (cfg.kda_heads * cfg.kda_head_dim**2 * 4
                 + (cfg.conv_kernel - 1) * cfg.conv_dim
                 * jnp.dtype(cfg.dtype).itemsize)
    return cfg.layers_of(KDA) * per_layer


def kv_wire_cell(cfg: KimiLinearConfig) -> None:
    """Nothing ships: neither the state nor the latent pool has a KVSH wire
    form. A handoff, resume or park replays its tokens instead."""
    return None


# ---------------------------------------------------------------------------
# The mixers and the mixture
# ---------------------------------------------------------------------------

def _low_rank(cfg: KimiLinearConfig, lp: Params, h):
    """(g [B, T, H, K] f32 the log of the decay a key channel, beta
    [B, T, H] f32, the output gate's logits [B, T, H * V]) of the normed
    input `h`: one product for the three that start at it (`w_low`), then
    the decay's and the gate's second matrices."""
    b, t, _ = h.shape
    heads, d, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank
    low = _proj(lp, "w_low", h)
    # split into heads behind it: kept a plain product (llama._proj_heads)
    f = _proj_heads(lp, "wf_b", low[..., :r]).astype(F32)
    g = -(jnp.exp(lp["a_log"].astype(F32))[:, None] * jax.nn.softplus(
        f + lp["dt_bias"].astype(F32)).reshape(b, t, heads, d))
    return (g, jax.nn.sigmoid(low[..., 2 * r:].astype(F32)),
            _proj_heads(lp, "wg_b", low[..., r:2 * r]))


def kda_mixer(cfg: KimiLinearConfig):
    """llama.LayerGroup's `mixer` for a KDA layer (olmo_hybrid's delta
    mixer with a decay a key channel, a low-rank sigmoid gate and the
    pre-norm block)."""
    h_, d = cfg.kda_heads, cfg.kda_head_dim

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b, t, _ = x.shape
        state, conv = cache_k.state, cache_v.state
        h = rms_norm(x, lp["ln_mix"], cfg.rms_eps)
        qkv = _proj_heads(lp, "wqkv", h)
        g, beta, gate = _low_rank(cfg, lp, h)
        decoding = rows.lens is None  # one token a row
        # a burst's rows are the pool's slots in order: no gather, no scatter
        whole = decoding and rows.slots is None
        slots = jnp.arange(b) if rows.slots is None else rows.slots
        held = conv[layer]  # [W - 1, slots, C]: the slots down the tiles
        before = held if whole else held[:, slots]
        if decoding:
            qkv, carried = _conv_step(qkv[:, 0], before, lp["conv_w"])
            if rows.live is not None:
                carried = jnp.where(rows.live[None, :, None], carried, before)
        else:
            fresh = (jnp.ones((b,), bool) if rows.start_pos is None
                     else rows.start_pos == 0)
            qkv, carried = ssm.causal_conv(
                qkv, jnp.where(fresh[:, None, None], 0,
                               jnp.moveaxis(before, 0, 1)),
                lp["conv_w"], jnp.zeros((cfg.conv_dim,), F32), rows.lens)
            carried = jnp.moveaxis(carried, 1, 0)
        q = _unit(qkv[..., :h_ * d].reshape(b, t, h_, d), d**-0.5)
        k = _unit(qkv[..., h_ * d:2 * h_ * d].reshape(b, t, h_, d))
        v = qkv[..., 2 * h_ * d:].reshape(b, t, h_, d)
        if decoding:
            o, state = delta_rule.delta_rule_step(
                state, layer, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                beta[:, 0], slots=rows.slots, live=rows.live)
            o = o[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           delta_rule.from_pool(state[layer, slots], h_))
            o, s = delta_rule.delta_rule_chunked(q, k, v, g, beta, s0,
                                                 rows.lens,
                                                 chunk=cfg.chunk_size)
            state = state.at[layer, slots].set(delta_rule.to_pool(s))
        carried = carried.astype(conv.dtype)
        conv = conv.at[layer].set(
            carried if whole else held.at[:, slots].set(carried))
        # per head an RMS norm over the V channels, times the sigmoid gate
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_eps)
        o = (o * lp["gate_norm"].astype(F32)
             * jax.nn.sigmoid(gate.astype(F32)).reshape(b, t, h_, d))
        out = _proj(lp, "wo_kda", o.reshape(b, t, h_ * d).astype(x.dtype))
        return (x + out, cache_k._replace(state=state),
                cache_v._replace(state=conv))

    return mixer


def _moe_mlp_fn(cfg: KimiLinearConfig, live=None):
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
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob),
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
        )
        gu = flat @ lp["ws_gu"]  # the shared expert's gate | up
        fs = gu.shape[-1] // 2
        shared = (jax.nn.silu(gu[:, :fs]) * gu[:, fs:]) @ lp["ws_down"]
        return (routed + shared).reshape(b, t, m), routing

    return fn


def _groups(cfg: KimiLinearConfig, live=None) -> list[LayerGroup]:
    """A group a RUN of like layers, in order: the run's own stacks whole,
    its place in its pool (the latent pages or the state) its mixer's next
    rows."""
    moe_fn, mixer = _moe_mlp_fn(cfg, live), kda_mixer(cfg)
    seen = {KDA: 0, MLA: 0}
    groups = []
    for prefix, kind, count in runs(cfg):
        mix = kind.split("_")[0]
        routed = kind.endswith("_moe")
        groups.append(LayerGroup(
            _NAMES[kind], moe_fn if routed else _default_mlp_fn, count,
            prefix, whole=_EXPERTS if routed else (), pool_layer=seen[mix],
            **(dict(attends=False, mixer=mixer, scope="kda_layers")
               if mix == KDA else dict(scope="latent_layers"))))
        seen[mix] += count
    return groups


def step_counters(cfg: KimiLinearConfig) -> dict[str, tuple]:
    """The counters a call returns, by name and shape (all int32): the rows
    whose state it advanced (each in every KDA layer: the state's bytes a
    step are this times `state_slot_bytes` read and written), the cells its
    attentions read (a live row's whole length in every latent layer), and
    deepseek_v3's expert load over the HELD experts beside the assignments
    that went to experts this chip does not hold."""
    shapes: dict[str, tuple] = {"state_rows": (), "global_kv_tokens": ()}
    if cfg.num_moe_layers:
        shapes.update({
            "experts_touched": (), "expert_assignments": (),
            "expert_load_max": (), "assignments_elsewhere": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)})
    return shapes


def _extra(cfg: KimiLinearConfig, aux, shape, routing: bool, advanced,
           kv_lens, scanned=None):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a run,
    stacked over its layers (prefill, extend) or a list over them (decode);
    the mixtures' are joined here in layer order. `advanced`: rows whose
    state moved; `kv_lens` [B]: the cells each row's context holds once the
    call is done, 0 for a row not live; `scanned` [B]: a prefill's or an
    extend's tokens a row, through the chunked form in chunks of
    `chunk_size` (olmo_hybrid's `scan_tokens`, `scan_chunks`)."""
    found = [jax.tree.map(lambda *v: jnp.stack(v), *a)
             if isinstance(a, list) else a
             for a in aux if (a[0] if isinstance(a, list) else a) is not None]
    stacked = ([jax.tree.map(lambda *v: jnp.concatenate(v), *found)]
               if found else [None])
    if found and not routing:
        # the loads as ONE array [Lm, X] before they are counted: without
        # the barrier the compiler counts a layer at a time and sums the
        # scalars in chains, some 130 small operations a decode step
        stacked = [stacked[0]._replace(
            load=jax.lax.optimization_barrier(stacked[0].load))]
    out = _routed_extra(cfg, stacked, shape, routing)
    if routing:
        return out
    counters = dict(out[0]) if out else {}
    counters["state_rows"] = jnp.asarray(advanced, jnp.int32)
    counters["global_kv_tokens"] = cfg.layers_of(MLA) * jnp.sum(
        kv_lens, dtype=jnp.int32)
    if scanned is not None:
        counters["scan_tokens"] = jnp.sum(scanned, dtype=jnp.int32)
        counters["scan_chunks"] = jnp.asarray(
            shape[0] * -(-shape[1] // cfg.chunk_size), jnp.int32)
    if found:
        # every valid token makes k assignments a mixture, each a held
        # expert's (counted in its load) or another chip's: by difference,
        # not by a count a layer (26 small reductions and their sum a step)
        tokens = advanced if scanned is None else counters["scan_tokens"]
        counters["assignments_elsewhere"] = (
            cfg.num_moe_layers * cfg.experts_per_token
            * jnp.asarray(tokens, jnp.int32) - counters["expert_assignments"])
    return (counters,)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: KimiLinearConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False, slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose state the rows write, from zeros."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_attention(cfg),
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, input_ids.shape[0], prompt_lens,
        scanned=prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: KimiLinearConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False, slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' state is read from their slots,
    taken on from `start_pos` and written back."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_attention(cfg), slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, input_ids.shape[0],
        start_pos + chunk_lens, scanned=chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: KimiLinearConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False,
                      slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged; a row that is not `live` keeps its state and
    its carried rows bit for bit."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_attention(cfg),
        slot_ids=slot_ids)
    kv_lens, advanced = seq_lens + 1, input_ids.shape[0]
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
        advanced = jnp.sum(live, dtype=jnp.int32)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, (input_ids.shape[0], 1), routing, advanced, kv_lens))


# It verifies no draft: a rejected token would leave the state advanced, and
# there is no snapshot to roll back to. `mixed_step` stays False: a state per
# slot needs its mixer called twice a layer first (family.py). `slot_ids`:
# the rows' slots (default row i in slot i); `num_slots`: the slot count of
# the pool's state. `routed_scaling_factor` and `topk_group` are read and not
# listed: older mixtures' configs carry them, and a key listed here is
# refused of every family that does not list it (lfm2_moe's FAMILY says so).
FAMILY = Family(
    name="kimi_linear", config_class=KimiLinearConfig,
    model_types=("kimi_linear",),
    mechanism_keys=("linear_attn_config", "mla_use_nope", "kv_lora_rank",
                    "num_experts", "num_experts_per_token",
                    "num_shared_experts", "moe_router_activation_func",
                    "moe_renormalize", "first_k_dense_replace",
                    "use_grouped_topk", "num_expert_group",
                    "moe_intermediate_size", "expert_parallel"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="latent page pool beside a delta-rule state",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "assignments_elsewhere": StepCounter(
            "sum", "moe_assignments_elsewhere_total"),
        "state_rows": StepCounter("sum", "ssm_state_rows_total"),
        "global_kv_tokens": StepCounter("sum", "global_kv_tokens_total")},
    step_counters=step_counters, paged_keywords=("routing", "slot_ids"),
    keywords_of={"init_kv_pages": ("num_slots",)})
