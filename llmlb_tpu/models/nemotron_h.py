"""Nemotron-H-class hybrid decoder (`model_type` `nemotron_h`:
NVIDIA-Nemotron-3-Nano-30B-A3B): a stack whose layers are ONE mixer each —
state-space (Mamba-2), attention, or a mixture of experts — in the order the
config's `hybrid_override_pattern` spells (`M`, `*`, `E`).

Same serving contract and the same three shared bodies as models/llama.py
(docs/hybrid-state.md); what differs is handed to them:

- Every layer is `x + mixer(RMSNorm(x))`. The walk follows the pattern: a
  llama.LayerGroup a layer (no two neighbours are alike), its parameters the
  layer's row of its KIND's stack (`M` [n_M, ...], `*` [n_A, ...], `E`
  [n_E, ...]), so that the grouped expert kernel and the state step read
  whole buffers at (layer). An `M` group has a `mixer` and no feed-forward,
  a `*` group attends and has no feed-forward, an `E` group neither attends
  nor mixes and has the routed feed-forward.
- `M`: `[z | xBC | dt] = in_proj(h)`; `xBC <- silu(conv1d(xBC) + b)` (causal,
  depthwise, width 4); `[x | B | C] = xBC`; `dt <- softplus(dt + dt_bias)`,
  `A = -exp(A_log)`; the recurrence of ops/ssm.py; `y <- y silu(z)`, an RMS
  norm over each group's channels times a weight, `out_proj`. What a
  sequence carries between calls lives per SLOT beside the page pool
  (llama.StatePool): `cache_k.state` [n_M, slots, H, P, N] float32 and
  `cache_v.state` [n_M, slots, 3, conv channels], the rows of xBC before the
  convolution. Prefill starts from zeros and writes the state after the
  prompt's last token; extend reads its slot's, scans, writes back (a chunk
  that starts at 0 starts from zeros); decode advances the `live` rows.
- `*`: grouped-query attention WITHOUT rotary embedding (the family's
  modelling code applies none) over the page pool of the attention layers
  alone (`cache_k.pages` [n_A, P, PS, K, D]).
- `E`: ops/moe.py's routed layer with DeepSeek-V3's rule
  (`sigmoid_bias_routing`), experts of two matrices with relu(x)^2 between
  them, a shared expert of the same form outside the routing. A chip may
  hold a SHARE of the experts (`expert_parallel` in the config:
  `held_experts`): the router scores all of them, the assignments of the
  others are another chip's.

Not served, each refused by name: speculative decoding (`verify_step_paged`
is absent: a rejected draft would need the state rolled back), an int8 page
pool, KV on the wire (`kv_wire_cell` None: the state has no wire form), int8
weights and LoRA pools; the engine refuses the prefix cache, the offload
tier and the split role for a family with state per slot (scheduler.py).

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/deepseek_v3.py's do: the step's counters, or under the
static `routing=True` what the routers decided.
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
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj,
    _qkv,
    shard_rules_for,
)
from llmlb_tpu.ops import moe, ssm
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

KINDS = "M*E"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(LlamaConfig):
    pattern: str = "M*E"  # hybrid_override_pattern, a character a layer
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the routed experts THIS CHIP holds (the weights' expert axis): all
    # the router scores, or a share of them [first_expert, + num_experts)
    num_experts: int = 128
    experts_per_token: int = 6
    moe_intermediate_size: int = 1856
    shared_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    router_experts: int = 128  # what the router scores
    first_expert: int = 0

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the router's experts this chip holds."""
        return self.first_expert, self.num_experts

    def layers_of(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def num_moe_layers(self) -> int:
        return self.layers_of("E")

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "NemotronHConfig":
        """Build from a published `config.json`. What this family does not
        compute is refused by name. `expert_parallel` ({"chips", "chip",
        "experts"}) is the deployment's, not the checkpoint's: this chip
        holds `n_routed_experts` of the router's `experts`, the `chip`-th
        such share."""
        pattern = hf["hybrid_override_pattern"]
        limit = hf.get("time_step_limit") or (0.0, None)
        unsupported = {
            "hybrid_override_pattern": bool(set(pattern) - set(KINDS)),
            "num_hidden_layers": hf["num_hidden_layers"] != len(pattern),
            "mamba_hidden_act": hf.get("mamba_hidden_act", "silu") != "silu",
            "mlp_hidden_act": hf.get("mlp_hidden_act", "relu2") != "relu2",
            "n_group": hf.get("n_group", 1) != 1,
            "topk_group": hf.get("topk_group", 1) != 1,
            "use_bias": bool(hf.get("use_bias")),
            "mlp_bias": bool(hf.get("mlp_bias")),
            "mamba_proj_bias": bool(hf.get("mamba_proj_bias")),
            "attention_bias": bool(hf.get("attention_bias")),
            "use_conv_bias": not hf.get("use_conv_bias", True),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
            "residual_in_fp32": bool(hf.get("residual_in_fp32")),
            "n_shared_experts": hf.get("n_shared_experts", 1) != 1,
            "time_step_limit": (float(limit[0] or 0.0) != 0.0
                                or limit[1] not in (None, math.inf)),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"nemotron_h config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/nemotron_h.py; refusing to serve wrong logits")
        held, experts, first = held_share(hf)
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("intermediate_size",
                                     hf["moe_intermediate_size"]),
            num_layers=len(pattern),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim", hf.get("attention_head_dim")),
            rope_theta=float(hf.get("rope_theta", 10000.0)),  # read by no layer
            rms_eps=hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            dtype=dtype,
            pattern=pattern,
            ssm_heads=hf["mamba_num_heads"],
            ssm_head_dim=hf["mamba_head_dim"],
            ssm_groups=hf["n_groups"],
            ssm_state=hf["ssm_state_size"],
            conv_kernel=hf.get("conv_kernel", 4),
            chunk_size=hf.get("chunk_size", 128),
            time_step_min=float(hf.get("time_step_min", 0.001)),
            time_step_max=float(hf.get("time_step_max", 0.1)),
            time_step_floor=float(hf.get("time_step_floor", 1e-4)),
            num_experts=held,
            router_experts=experts,
            first_expert=first,
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            shared_intermediate_size=hf.get(
                "moe_shared_expert_intermediate_size",
                hf["moe_intermediate_size"]),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        )


# ---------------------------------------------------------------------------
# Params: one stack a kind
# ---------------------------------------------------------------------------

SSM = ("ln_ssm", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
        "ssm_a_log", "ssm_d", "ln_gate", "ssm_out")
ATTN = ("ln_attn", "wq", "wk", "wv", "wo")
_MOE = ("ln_mlp", "router", "router_bias", "we_up", "we_down", "ws_up",
        "ws_down")
_NAMES = {"M": SSM, "*": ATTN, "E": _MOE}


def mixer_shapes(cfg) -> dict[str, tuple[tuple, int]]:
    """`_layer_shapes` of the state-space and the attention layer's leaves
    (`SSM`, `ATTN`), which models/granite_hybrid.py holds under the same
    names: seeded_vector reads them."""
    e, d = cfg.hidden_size, cfg.head_dim_
    di, cd, hs = cfg.d_inner, cfg.conv_dim, cfg.ssm_heads
    return {
        "ln_ssm": ((e,), 0), "ssm_in": ((e, di + cd + hs), e),
        "ssm_conv_w": ((cd, cfg.conv_kernel), 0), "ssm_conv_b": ((cd,), 0),
        "ssm_dt_bias": ((hs,), 0), "ssm_a_log": ((hs,), 0),
        "ssm_d": ((hs,), 0), "ln_gate": ((di,), 0), "ssm_out": ((di, e), di),
        "ln_attn": ((e,), 0), "wq": ((e, cfg.num_heads * d), e),
        "wk": ((e, cfg.num_kv_heads * d), e),
        "wv": ((e, cfg.num_kv_heads * d), e),
        "wo": ((cfg.num_heads * d, e), cfg.num_heads * d),
    }


def _layer_shapes(cfg: NemotronHConfig) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, x, fm, fs = (cfg.hidden_size, cfg.num_experts,
                    cfg.moe_intermediate_size, cfg.shared_intermediate_size)
    return {
        **mixer_shapes(cfg),
        "ln_mlp": ((e,), 0), "router": ((e, cfg.router_experts), e),
        "router_bias": ((cfg.router_experts,), 0),
        # output-major, as the checkpoint has it: ops/pallas_moe says why
        "we_up": ((x, fm, e), e), "we_down": ((x, fm, e), fm),
        "ws_up": ((e, fs), e), "ws_down": ((fs, e), fs),
    }


def _leaves(cfg: NemotronHConfig) -> list[stacks.Leaf]:
    """Every stacked leaf the pattern calls for: one stack a kind."""
    return stacks.stack_leaves(_layer_shapes(cfg), [
        ("", _NAMES[kind], cfg.layers_of(kind)) for kind in KINDS])


def seeded_vector(cfg, name: str, k, shape):
    """A seeded leaf that is no matrix, by the published initialisation of
    the state-space layers (init_params says which; models/granite_hybrid.py
    seeds its own by the same rule): `cfg` names `conv_kernel` and the three
    `time_step_*`."""
    if name in ("ssm_conv_w", "ssm_conv_b"):
        bound = cfg.conv_kernel**-0.5
        return jax.random.uniform(k, shape, F32, -bound, bound
                                  ).astype(cfg.dtype)
    if name == "ssm_a_log":
        return jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
    if name == "ssm_dt_bias":
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, F32, lo, hi)),
                         cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    if name == "router_bias":
        return 0.02 * jax.random.normal(k, shape, F32)
    if name == "ssm_d":
        return jnp.ones(shape, F32)
    return jnp.ones(shape, cfg.dtype)  # the norms


def init_params(cfg: NemotronHConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark) by the family's published initialisation, from the
    config's own keys, so that the decay runs in the range the model runs
    in: matrices normal x fan_in^-0.5; `A_log` the log of a uniform draw in
    [1, 16]; `dt_bias` the inverse softplus of a log-uniform draw in
    [time_step_min, time_step_max] floored at time_step_floor; `D` and the
    norms ones; the convolution uniform within +-kernel^-0.5; the router's
    choice bias a seeded normal of sd 0.02 (deepseek_v3.init_params says
    why it is not zero)."""
    return stacks.init_params(cfg, key, _leaves(cfg), seeded_vector)


def param_logical_axes(cfg: NemotronHConfig) -> dict[str, tuple]:
    """Attention and the experts shard as in the other families; the
    state-space projections replicate (their output is split into parts of
    unlike widths, which a tensor-parallel split would cut across)."""
    layer = {
        **stacks.GQA_AXES, **stacks.EXPERT_AXES,
        "we_up": ("experts", "ffn", "embed"),  # output-major: _layer_shapes
    }
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: NemotronHConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: pages of the attention layers, state of the state-space layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: NemotronHConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: K (V) pages of the attention layers [n_A, P, PS, K, D],
    and per slot the recurrent state [n_M, slots, H, P, N] float32 (the rows
    of xBC the convolution looks back on [n_M, slots, kernel - 1, channels]).
    Page 0 is the trash page; the state has none (a row that does not
    advance is masked). `num_slots` 1 serves a caller with one row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    pages = (cfg.layers_of("*"), num_pages, page_size, cfg.num_kv_heads,
             cfg.head_dim_)
    n_m = cfg.layers_of("M")
    return (
        StatePool(jnp.zeros(pages, dtype), jnp.zeros(
            (n_m, num_slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            F32)),
        StatePool(jnp.zeros(pages, dtype), jnp.zeros(
            (n_m, num_slots, cfg.conv_kernel - 1, cfg.conv_dim), dtype)),
    )


def kv_pages_shardings(cfg: NemotronHConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Pages as llama's; the state replicates (a slot's rows are one
    sequence's, and its heads are not split: param_logical_axes)."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    pages = logical_to_sharding(mesh, rules, "layers", None, "seq",
                                "kv_heads", "head_dim")
    state = logical_to_sharding(mesh, rules, "layers", None, None, None, None)
    conv = logical_to_sharding(mesh, rules, "layers", None, None, None)
    return (StatePool(pages, state), StatePool(pages, conv))


def kv_pool_layers(cfg: NemotronHConfig) -> int:
    """Layers of the page pool: the attention layers alone."""
    return cfg.layers_of("*")


def kv_token_layer_bytes(cfg: NemotronHConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool (K and V of
    every kv head); the state-space layers leave nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return (2 * cfg.num_kv_heads * cfg.head_dim_
            * jnp.dtype(cfg.dtype).itemsize)


def state_slot_bytes(cfg: NemotronHConfig) -> int:
    """HBM bytes one slot holds beside its pages: the recurrent state and
    the convolution's rows of every state-space layer."""
    per_layer = (cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
                 + (cfg.conv_kernel - 1) * cfg.conv_dim
                 * jnp.dtype(cfg.dtype).itemsize)
    return cfg.layers_of("M") * per_layer


def kv_wire_cell(cfg: NemotronHConfig) -> None:
    """Nothing ships: the recurrent state has no KVSH wire form, and pages
    without it are half a sequence. A handoff, resume or park replays its
    tokens instead."""
    return None


# ---------------------------------------------------------------------------
# The three mixers
# ---------------------------------------------------------------------------

def _nope_block(cfg: NemotronHConfig, lp: Params, x, positions, inv_freq,
                attn_fn, lora_idx=None):
    """llama._attn_block without the rotary embedding. Returns (x_out, k,
    v)."""
    del positions, inv_freq
    b, t, _ = x.shape
    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    q, k, v = _qkv(cfg, lp, h, lora_idx)
    attn = attn_fn(q, k, v)
    return x + _proj(lp, "wo", attn.reshape(b, t, -1), lora_idx), k, v


_ATTENTION = GQA_ATTENTION._replace(block=_nope_block)


def ssm_mixer(cfg, residual: float = 1.0):
    """llama.LayerGroup's `mixer` for a state-space (Mamba-2) layer, of any
    family whose configuration names the mixer's sizes as NemotronHConfig
    does (`ssm_heads`, `ssm_head_dim`, `ssm_groups`, `ssm_state`, `d_inner`,
    `conv_dim`, `chunk_size`) and its parameters by `SSM`. `residual`
    scales what the mixer GIVES before it joins x (Granite's
    `residual_multiplier`, models/granite_hybrid.py); at 1 nothing is
    traced for it."""
    heads, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
    di, cd = cfg.d_inner, cfg.conv_dim

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b, t, _ = x.shape
        state, conv = cache_k.state, cache_v.state
        h = rms_norm(x, lp["ln_ssm"], cfg.rms_eps)
        zxbcdt = h @ lp["ssm_in"]
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
                      zxbcdt[..., di + cd:])
        dt = jax.nn.softplus(dt.astype(F32) + lp["ssm_dt_bias"])  # [B, T, H]
        a = -jnp.exp(lp["ssm_a_log"].astype(F32))
        decoding = rows.lens is None  # one token a row
        if decoding:
            at = (layer,) if rows.slots is None else (layer, rows.slots)
            lens = jnp.ones((b,), jnp.int32)
            before = conv[at]
        else:
            at = (layer, jnp.arange(b) if rows.slots is None else rows.slots)
            lens = rows.lens
            fresh = (jnp.ones((b,), bool) if rows.start_pos is None
                     else rows.start_pos == 0)
            before = jnp.where(fresh[:, None, None], 0, conv[at])
        xbc, carried = ssm.causal_conv(xbc, before, lp["ssm_conv_w"],
                                       lp["ssm_conv_b"], lens)
        xs = xbc[..., :di].reshape(b, t, heads, p)
        bm = xbc[..., di:di + g * n].reshape(b, t, g, n)
        cm = xbc[..., di + g * n:].reshape(b, t, g, n)
        if decoding:
            if rows.live is not None:
                carried = jnp.where(rows.live[:, None, None], carried, before)
            y, state = ssm.ssm_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                    lp["ssm_d"], state, layer,
                                    slots=rows.slots, live=rows.live)
            y = y[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0.0, state[at])
            y, s = ssm.ssd_chunked(xs, dt, a, bm, cm, lp["ssm_d"], s0, lens,
                                   chunk=cfg.chunk_size)
            state = state.at[at].set(s)
        conv = conv.at[at].set(carried.astype(conv.dtype))
        # y silu(z), then an RMS norm over each group's channels
        y = (y.reshape(b, t, di).astype(F32)
             * jax.nn.silu(z.astype(F32))).reshape(b, t, g, di // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.rms_eps)
        y = (y.reshape(b, t, di) * lp["ln_gate"].astype(F32)).astype(x.dtype)
        out = y @ lp["ssm_out"]
        if residual != 1.0:
            out = out * residual
        return (x + out, cache_k._replace(state=state),
                cache_v._replace(state=conv))

    return mixer


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _moe_mlp_fn(cfg: NemotronHConfig, live=None):
    """llama's `mlp_fn` for an expert layer: the routed experts this chip
    holds by DeepSeek-V3's rule plus the shared expert, and as aux the
    layer's ops/moe.Routing. `live`: as deepseek_v3._moe_mlp_fn."""
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
            flat, logits, None, lp["we_up"], lp["we_down"],
            layer=lp["layer"], held=held, act=relu2, up_transposed=True,
            route=lambda r: moe.sigmoid_bias_routing(
                r, lp["router_bias"], cfg.experts_per_token,
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob),
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
        )
        shared = relu2(flat @ lp["ws_up"]) @ lp["ws_down"]
        return (routed + shared).reshape(b, t, m), routing

    return fn


def _groups(cfg: NemotronHConfig, live=None) -> list[LayerGroup]:
    """A group a layer, in the pattern's order; a layer's parameters and
    its place in its pool are its kind's next row."""
    kinds = {
        "M": dict(mlp_fn=None, attends=False, mixer=ssm_mixer(cfg)),
        "*": dict(mlp_fn=None),
        "E": dict(mlp_fn=_moe_mlp_fn(cfg, live), attends=False,
                  whole=("we_up", "we_down")),
    }
    seen = dict.fromkeys(KINDS, 0)
    groups = []
    for kind in cfg.pattern:
        groups.append(LayerGroup(names=_NAMES[kind], count=1,
                                 start=seen[kind], pool_layer=seen[kind],
                                 **kinds[kind]))
        seen[kind] += 1
    return groups


def step_counters(cfg: NemotronHConfig) -> dict[str, tuple]:
    """The counters a decode step returns, by name and shape (all int32):
    deepseek_v3's expert load over the HELD experts, the assignments that
    went to experts this chip does not hold, and the rows whose state the
    step advanced."""
    shapes: dict[str, tuple] = {"state_rows": ()}
    if cfg.num_moe_layers:
        shapes.update({
            "experts_touched": (), "expert_assignments": (),
            "expert_load_max": (), "assignments_elsewhere": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)})
    return shapes


def _extra(cfg: NemotronHConfig, aux, shape, routing: bool, advanced,
           scanned=None):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a group;
    the expert layers' are stacked here in their own order. `advanced`:
    rows whose state moved; `scanned` (prefill and extend): their tokens."""
    found = [a[0] if isinstance(a, list) else
             jax.tree.map(lambda v: v[0], a)
             for a, kind in zip(aux, cfg.pattern) if kind == "E"]
    stacked = ([jax.tree.map(lambda *v: jnp.stack(v), *found)]
               if found else [None])
    out = _routed_extra(cfg, stacked, shape, routing)
    if routing:
        return out
    counters = dict(out[0]) if out else {}
    counters["state_rows"] = jnp.asarray(advanced, jnp.int32)
    if found:
        counters["assignments_elsewhere"] = (
            jnp.zeros((), jnp.int32) if stacked[0].elsewhere is None
            else jnp.sum(stacked[0].elsewhere, dtype=jnp.int32))
    if scanned is not None:
        counters["scan_tokens"] = jnp.sum(scanned, dtype=jnp.int32)
        counters["scan_chunks"] = jnp.asarray(
            shape[0] * -(-shape[1] // cfg.chunk_size), jnp.int32)
    return (counters,)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: NemotronHConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False, slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose state the rows write, from zeros."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_ATTENTION,
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, input_ids.shape[0], prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: NemotronHConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False, slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' state is read from their slots,
    scanned on from `start_pos` and written back."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_ATTENTION, slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, input_ids.shape[0], chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: NemotronHConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False,
                      slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged; a row that is not `live` keeps its state."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_ATTENTION, slot_ids=slot_ids)
    advanced = (input_ids.shape[0] if live is None
                else jnp.sum(live, dtype=jnp.int32))
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, (input_ids.shape[0], 1), routing, advanced))


# It verifies no draft: a rejected token would leave the state advanced, and
# there is no snapshot to roll back to. `slot_ids`: the rows' slots (default
# row i in slot i); `num_slots`: the slot count of the pool's state.
FAMILY = Family(
    name="nemotron_h", config_class=NemotronHConfig,
    model_types=("nemotron_h",),
    mechanism_keys=("n_routed_experts", "n_shared_experts",
                    "moe_intermediate_size", "hybrid_override_pattern",
                    "mamba_num_heads", "ssm_state_size", "expert_parallel"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="page pool beside a recurrent state",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "assignments_elsewhere": StepCounter(
            "sum", "moe_assignments_elsewhere_total"),
        "state_rows": StepCounter("sum", "ssm_state_rows_total")},
    step_counters=step_counters, paged_keywords=("routing", "slot_ids"),
    keywords_of={"init_kv_pages": ("num_slots",)})
