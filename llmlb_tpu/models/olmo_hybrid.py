"""Olmo-Hybrid-class decoder (`model_type` `olmo_hybrid`: Olmo-Hybrid-7B): a
stack whose layers mix their tokens by a GATED DELTA RULE (linear attention
with a state per head) or by full attention, in the order the config's
`layer_types` spells, each followed by a dense SwiGLU feed-forward — and
every sub-layer's OUTPUT normed, Olmo's block: `h = x + norm(mix(x))`,
`y = h + norm(mlp(h))`, no norm on an input. docs/linear-attention.md has
the equations.

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them:

- The walk follows `layer_types`: two llama.LayerGroups a layer, its mix
  (its parameters the layer's row of its KIND's stack: `lin_*` [n_L, ...],
  `wq`.. [n_A, ...]) and its feed-forward (one stack over all the layers,
  `post_norm`: the group norms what the feed-forward gives, not what it
  takes).
- `linear_attention`: `[q | k | v] = x W_qkv`, a causal depthwise
  convolution of width 4 over time and SiLU; per head q and k L2-normalised
  (q times K^-1/2); `b = 2 sigmoid(x W_b)` (`linear_allow_neg_eigval`),
  `g = -exp(A_log) softplus(x W_a + dt_bias)`; the rule of ops/delta_rule.py;
  per head an RMS norm over the V channels times a weight, times
  silu(x W_z); `W_o`. What a sequence carries between calls lives per SLOT
  beside the page pool (llama.StatePool): `cache_k.state` [n_L, slots, K,
  H * V] float32 (delta_rule.to_pool says why that layout) and
  `cache_v.state` [n_L, 3, slots, channels], the rows of [q | k | v] before
  the convolution (the slots second to last: three rows would be padded to
  a tile of sixteen). Prefill starts from zeros and writes the state after the
  prompt's last token; extend reads its slot's, goes on, writes back (a
  chunk that starts at 0 starts from zeros); decode advances the `live`
  rows.
- `full_attention`: attention WITHOUT rotary embedding (the config's
  `rope_theta` is null) and without grouping, an RMS norm with weight over
  the whole of q and of k before they split into heads, over the page pool
  of the attention layers alone (`cache_k.pages` [n_A, P, PS, K', D], K'
  the 30 KV heads and two dead ones: OlmoHybridConfig.pool_kv_heads).

Not served, each refused by name: speculative decoding (`verify_step_paged`
is absent: a rejected draft would need the state rolled back), an int8 page
pool, KV on the wire (`kv_wire_cell` None: the state has no wire form), int8
weights and LoRA pools; the engine refuses the prefix cache, the offload
tier and the split role for a family with state per slot (scheduler.py).

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/nemotron_h.py's do: the step's counters.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.models import stacks
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
from llmlb_tpu.ops import delta_rule, ssm
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

LINEAR, FULL = "linear_attention", "full_attention"
# every key of a config.json that says something of the linear layers: one
# the class does not know is refused (from_hf_config)
LINEAR_KEYS = ("linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "linear_allow_neg_eigval")
QK_NORM_EPS = 1e-6  # under the L2 norm of a head's q and k


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    layer_types: tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    lin_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    chunk_size: int = delta_rule.CHUNK

    @property
    def conv_dim(self) -> int:
        """Channels of [q | k | v], what the convolution runs over."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def pool_kv_heads(self) -> int:
        """KV heads a cell of the page pool holds: `num_kv_heads`, and dead
        ones behind them up to a count the chip tiles without a gap (a
        power of two up to 8, else whole tiles of 16 rows). A bf16 [.., 30,
        128] is stored 32 rows deep whatever its shape says, and its view
        as a page's [PS * 30, 128] rows is then no bitcast: the compiler
        copied the whole pool, 1.46 GB, in front of every call of
        paged_flash_decode (the compile for a described v5e said so). With
        the two dead heads stated the same bytes are a pool the kernels
        take as it is stored."""
        k = self.num_kv_heads
        return 1 << (k - 1).bit_length() if k <= 8 else -(-k // 16) * 16

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "OlmoHybridConfig":
        """Build from a published `config.json`. What this family does not
        compute is refused by name: a layer type it does not know, a rotary
        base that is stated (no layer here rotates), key and value heads of
        unlike counts (the grouped rule is not implemented), a `linear_*` key
        it does not read."""
        kinds = tuple(hf["layer_types"])
        rope = hf.get("rope_parameters") or {}
        unsupported = {
            "layer_types": bool(set(kinds) - {LINEAR, FULL}),
            "num_hidden_layers": hf["num_hidden_layers"] != len(kinds),
            "rope_theta": hf.get("rope_theta") is not None,
            "rope_parameters": any(v is not None for v in rope.values()),
            "rope_scaling": hf.get("rope_scaling") is not None,
            "linear_num_value_heads": (hf["linear_num_value_heads"]
                                       != hf["linear_num_key_heads"]),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(hf.get("attention_bias")),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
            **{k: True for k in hf
               if k.startswith("linear_") and k not in LINEAR_KEYS},
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"olmo_hybrid config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/olmo_hybrid.py; refusing to serve wrong logits")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=len(kinds),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads",
                                hf["num_attention_heads"]),
            head_dim=hf.get("head_dim"),
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 65536),
            dtype=dtype,
            layer_types=kinds,
            lin_heads=hf["linear_num_key_heads"],
            lin_key_dim=hf["linear_key_head_dim"],
            lin_value_dim=hf["linear_value_head_dim"],
            conv_kernel=hf.get("linear_conv_kernel_dim", 4),
            allow_neg_eigval=bool(hf.get("linear_allow_neg_eigval", False)),
        )


# ---------------------------------------------------------------------------
# Params: one stack a kind of mix, one of feed-forwards
# ---------------------------------------------------------------------------

_LIN = ("wqkv", "wz", "wab", "conv_w", "a_log", "dt_bias", "gate_norm", "wo",
        "ln_mix")
_LIN_PREFIX = "lin_"
_ATTN = ("wq", "wk", "wv", "q_norm", "k_norm", "wo", "ln_attn")
_MLP = ("wg", "wu", "wd", "ln_mlp")


def _layer_shapes(cfg: OlmoHybridConfig) -> dict[str, tuple[tuple, int]]:
    """name in the pytree -> (shape of one layer's leaf, fan-in; 0 = its
    own rule)."""
    e, f, hd = cfg.hidden_size, cfg.intermediate_size, (
        cfg.num_heads * cfg.head_dim_)
    kd = cfg.num_kv_heads * cfg.head_dim_
    h, vd = cfg.lin_heads, cfg.lin_heads * cfg.lin_value_dim
    return {
        "lin_wqkv": ((e, cfg.conv_dim), e), "lin_wz": ((e, vd), e),
        "lin_wab": ((e, 2 * h), e), "lin_conv_w": ((cfg.conv_dim,
                                                    cfg.conv_kernel), 0),
        "lin_a_log": ((h,), 0), "lin_dt_bias": ((h,), 0),
        "lin_gate_norm": ((cfg.lin_value_dim,), 0), "lin_wo": ((vd, e), vd),
        "lin_ln_mix": ((e,), 0),
        "wq": ((e, hd), e), "wk": ((e, kd), e), "wv": ((e, kd), e),
        "q_norm": ((hd,), 0), "k_norm": ((kd,), 0), "wo": ((hd, e), hd),
        "ln_attn": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "ln_mlp": ((e,), 0),
    }


def _leaves(cfg: OlmoHybridConfig) -> list[stacks.Leaf]:
    """Every stacked leaf, under its name in the pytree."""
    return stacks.stack_leaves(_layer_shapes(cfg), [
        ("", [_LIN_PREFIX + n for n in _LIN], cfg.layers_of(LINEAR)),
        ("", _ATTN, cfg.layers_of(FULL)), ("", _MLP, cfg.num_layers)])


def _own_rule(cfg, name: str, k, shape):
    """A seeded leaf that is no matrix, by the gated-delta-net layer's own
    rule (init_params says which and why)."""
    if name == "lin_conv_w":
        bound = cfg.conv_kernel**-0.5
        return jax.random.uniform(k, shape, F32, -bound, bound
                                  ).astype(cfg.dtype)
    if name == "lin_a_log":
        return jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
    if name == "lin_dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, F32, jnp.log(0.001),
                                        jnp.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    return jnp.ones(shape, cfg.dtype)  # the norms


def init_params(cfg: OlmoHybridConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): matrices normal x fan_in^-0.5, norms ones, the
    convolution uniform within +-kernel^-0.5, and the decay's two
    parameters by the gated-delta-net layer's own rule — `A_log` the log of
    a uniform draw in [1, 16], `dt_bias` the inverse softplus of a
    log-uniform draw in [0.001, 0.1] — so that the decay runs where a
    trained layer's does (0.2 to 1, most of it near 1): a state that decays
    to nothing would hide the rule."""
    return stacks.init_params(cfg, key, _leaves(cfg), _own_rule)


def param_logical_axes(cfg: OlmoHybridConfig) -> dict[str, tuple]:
    """Attention and the feed-forward shard as llama's; the linear layers'
    projections replicate (their heads are not split: the state pool is one
    slot's whole)."""
    layer = {**stacks.GQA_AXES, **stacks.MLP_AXES}
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: OlmoHybridConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: pages of the attention layers, state of the linear layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: OlmoHybridConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: K (V) pages of the attention layers [n_A, P, PS, K', D],
    and per slot the rule's state [n_L, slots, K, H * V] float32 (the rows of
    [q | k | v] the convolution looks back on [n_L, kernel - 1, slots,
    channels]). Page 0 is the trash page; the state has none (a row that
    does not advance is masked). `num_slots` 1 serves a caller with one
    row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    pages = (cfg.layers_of(FULL), num_pages, page_size, cfg.pool_kv_heads,
             cfg.head_dim_)
    n_l = cfg.layers_of(LINEAR)
    return (
        StatePool(jnp.zeros(pages, dtype), jnp.zeros(
            (n_l, num_slots, cfg.lin_key_dim,
             cfg.lin_heads * cfg.lin_value_dim), F32)),
        StatePool(jnp.zeros(pages, dtype), jnp.zeros(
            (n_l, cfg.conv_kernel - 1, num_slots, cfg.conv_dim), dtype)),
    )


def kv_pages_shardings(cfg: OlmoHybridConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Pages as llama's; the state replicates (param_logical_axes)."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    pages = logical_to_sharding(mesh, rules, "layers", None, "seq",
                                "kv_heads", "head_dim")
    state = logical_to_sharding(mesh, rules, "layers", None, None, None)
    return (StatePool(pages, state), StatePool(pages, state))


def kv_pool_layers(cfg: OlmoHybridConfig) -> int:
    """Layers of the page pool: the full-attention layers alone."""
    return cfg.layers_of(FULL)


def kv_token_layer_bytes(cfg: OlmoHybridConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool (K and V of
    every head the pool stores: OlmoHybridConfig.pool_kv_heads); the linear
    layers leave nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return (2 * cfg.pool_kv_heads * cfg.head_dim_
            * jnp.dtype(cfg.dtype).itemsize)


def state_slot_bytes(cfg: OlmoHybridConfig) -> int:
    """HBM bytes one slot holds beside its pages: the rule's state and the
    convolution's rows of every linear layer — what the equations need, a
    padded layout would hold more."""
    per_layer = (cfg.lin_heads * cfg.lin_key_dim * cfg.lin_value_dim * 4
                 + (cfg.conv_kernel - 1) * cfg.conv_dim
                 * jnp.dtype(cfg.dtype).itemsize)
    return cfg.layers_of(LINEAR) * per_layer


def kv_wire_cell(cfg: OlmoHybridConfig) -> None:
    """Nothing ships: the state has no KVSH wire form, and pages without it
    are a quarter of a sequence. A handoff, resume or park replays its
    tokens instead."""
    return None


# ---------------------------------------------------------------------------
# The two mixes
# ---------------------------------------------------------------------------

def _attn_block(cfg: OlmoHybridConfig, lp: Params, x, positions, inv_freq,
                attn_fn, lora_idx=None):
    """llama._attn_block for Olmo's attention: no norm on the input, none
    rotated, q and k normed over their whole width before the heads split,
    the output normed before it joins the residual. Returns (x_out, k, v)."""
    del positions, inv_freq
    b, t, _ = x.shape
    d = cfg.head_dim_
    q = rms_norm(_proj_heads(lp, "wq", x, lora_idx), lp["q_norm"], cfg.rms_eps)
    k = rms_norm(_proj_heads(lp, "wk", x, lora_idx), lp["k_norm"], cfg.rms_eps)
    v = _proj_heads(lp, "wv", x, lora_idx)
    dead = cfg.pool_kv_heads - cfg.num_kv_heads  # behind the real ones
    group = cfg.num_heads // cfg.num_kv_heads

    def heads(x, count, pad):
        x = x.reshape(b, t, count, d)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    q = heads(q, cfg.num_heads, dead * group)
    k = heads(k, cfg.num_kv_heads, dead)
    v = heads(v, cfg.num_kv_heads, dead)
    attn = attn_fn(q, k, v)[:, :, :cfg.num_heads]
    out = _proj(lp, "wo", attn.reshape(b, t, -1), lora_idx)
    return x + rms_norm(out, lp["ln_attn"], cfg.rms_eps), k, v


_ATTENTION = GQA_ATTENTION._replace(block=_attn_block)


def _conv_step(x, before, w):
    """ops/ssm.causal_conv for one token a row, on the pool's own layout:
    x [B, C], before [W - 1, B, C] the rows in front of it, w [C, W] with
    w[:, W - 1] on the current row. Returns (silu(conv) [B, 1, C] in x's
    type, the rows to carry on [W - 1, B, C])."""
    rows = jnp.concatenate([before.astype(x.dtype), x[None]], axis=0)
    out = sum(rows[j].astype(F32) * w[:, j].astype(F32)
              for j in range(w.shape[-1]))
    return jax.nn.silu(out).astype(x.dtype)[:, None], rows[1:]


def _unit(x, scale: float = 1.0):
    """A head's vector over its own length, times `scale`, in float32."""
    x = x.astype(F32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + QK_NORM_EPS) * scale)


def _delta_mixer(cfg: OlmoHybridConfig):
    """llama.LayerGroup's `mixer` for a linear-attention layer."""
    h, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b, t, _ = x.shape
        state, conv = cache_k.state, cache_v.state
        qkv = _proj_heads(lp, "wqkv", x)
        z = _proj_heads(lp, "wz", x)
        ab = _proj(lp, "wab", x).astype(F32)
        g = (-jnp.exp(lp["a_log"].astype(F32))
             * jax.nn.softplus(ab[..., :h] + lp["dt_bias"].astype(F32)))
        beta = jax.nn.sigmoid(ab[..., h:]) * (2.0 if cfg.allow_neg_eigval
                                              else 1.0)
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
        q = _unit(qkv[..., :h * dk].reshape(b, t, h, dk), dk**-0.5)
        k = _unit(qkv[..., h * dk:2 * h * dk].reshape(b, t, h, dk))
        v = qkv[..., 2 * h * dk:].reshape(b, t, h, dv)
        if decoding:
            o, state = delta_rule.delta_rule_step(
                state, layer, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                beta[:, 0], slots=rows.slots, live=rows.live)
            o = o[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           delta_rule.from_pool(state[layer, slots], h))
            o, s = delta_rule.delta_rule_chunked(q, k, v, g, beta, s0,
                                                 rows.lens,
                                                 chunk=cfg.chunk_size)
            state = state.at[layer, slots].set(delta_rule.to_pool(s))
        carried = carried.astype(conv.dtype)
        conv = conv.at[layer].set(
            carried if whole else held.at[:, slots].set(carried))
        # per head an RMS norm over the V channels, times silu(z)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_eps)
        o = (o * lp["gate_norm"].astype(F32)
             * jax.nn.silu(z.astype(F32)).reshape(b, t, h, dv))
        out = _proj(lp, "wo", o.reshape(b, t, h * dv).astype(x.dtype))
        return (x + rms_norm(out, lp["ln_mix"], cfg.rms_eps),
                cache_k._replace(state=state), cache_v._replace(state=conv))

    return mixer


def _groups(cfg: OlmoHybridConfig) -> list[LayerGroup]:
    """Two groups a layer, in `layer_types`' order: the layer's mix (its
    parameters and its place in its pool its kind's next row), then its
    feed-forward."""
    mixes = {
        LINEAR: dict(names=_LIN, prefix=_LIN_PREFIX, attends=False,
                     mixer=_delta_mixer(cfg)),
        FULL: dict(names=_ATTN),
    }
    seen = {LINEAR: 0, FULL: 0}
    groups = []
    for at, kind in enumerate(cfg.layer_types):
        groups.append(LayerGroup(mlp_fn=None, count=1, start=seen[kind],
                                 pool_layer=seen[kind], scope=kind,
                                 **mixes[kind]))
        groups.append(LayerGroup(_MLP, _default_mlp_fn, 1, start=at,
                                 attends=False, post_norm=True,
                                 scope="feed_forward"))
        seen[kind] += 1
    return groups


def step_counters(cfg: OlmoHybridConfig) -> dict[str, tuple]:
    """The counters a decode step returns, by name and shape (all int32):
    the rows whose state the step advanced, and the cells its full
    attentions read (a live row's whole length in every such layer)."""
    return {"state_rows": (), "global_kv_tokens": ()}


def _extra(cfg: OlmoHybridConfig, advanced, kv_lens):
    """What follows (logits, cache_k, cache_v): the step's counters.
    `advanced`: rows whose state moved; `kv_lens` [B]: the cells each row's
    context holds once the call is done, 0 for a row not live."""
    return ({"state_rows": jnp.asarray(advanced, jnp.int32),
             "global_kv_tokens": cfg.layers_of(FULL) * jnp.sum(
                 kv_lens, dtype=jnp.int32)},)


_STATIC = ("cfg", "mesh")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: OlmoHybridConfig, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose state the rows write, from zeros."""
    logits, cache_k, cache_v, _ = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_ATTENTION,
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_chunk_extra(
        cfg, input_ids, prompt_lens, prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: OlmoHybridConfig, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' state is read from their slots,
    taken on from `start_pos` and written back."""
    logits, cache_k, cache_v, _ = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_ATTENTION, slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_chunk_extra(
        cfg, input_ids, chunk_lens, start_pos + chunk_lens))


def _chunk_extra(cfg, input_ids, lens, kv_lens):
    """A prefill's or an extend's counters: every row's state moved, `lens`
    tokens a row through the chunked form in chunks of `chunk_size`."""
    b, t = input_ids.shape
    (counters,) = _extra(cfg, b, kv_lens)
    counters["scan_tokens"] = jnp.sum(lens, dtype=jnp.int32)
    counters["scan_chunks"] = jnp.asarray(b * -(-t // cfg.chunk_size),
                                          jnp.int32)
    return (counters,)


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: OlmoHybridConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged; a row that is not `live` keeps its state."""
    logits, cache_k, cache_v, _ = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live, groups=_groups(cfg),
        attention=_ATTENTION, slot_ids=slot_ids)
    kv_lens = seq_lens + 1
    advanced = input_ids.shape[0]
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
        advanced = jnp.sum(live, dtype=jnp.int32)
    return (logits, cache_k, cache_v, *_extra(cfg, advanced, kv_lens))


# It verifies no draft: a rejected token would leave the state advanced, and
# there is no snapshot to roll back to. `slot_ids`: the rows' slots (default
# row i in slot i); `num_slots`: the slot count of the pool's state.
FAMILY = Family(
    name="olmo_hybrid", config_class=OlmoHybridConfig,
    model_types=("olmo_hybrid",),
    mechanism_keys=("layer_types", *LINEAR_KEYS),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="page pool beside a delta-rule state",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        "state_rows": StepCounter("sum", "ssm_state_rows_total"),
        "global_kv_tokens": StepCounter("sum", "global_kv_tokens_total")},
    step_counters=step_counters, paged_keywords=("slot_ids",),
    keywords_of={"init_kv_pages": ("num_slots",)})
