"""MiMo-V2-class decoder (`model_type` `mimo_v2`: XiaomiMiMo/MiMo-V2.5's
language model): a stack in which WINDOW attention layers and GLOBAL ones
alternate by `hybrid_layer_pattern` (1 window, 0 global; five to one as
published), each with a cache of its own kind, under a dense feed-forward
or a sigmoid-routed mixture by `moe_layer_freq` (docs/window-attention.md).

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them as `LayerGroup`s, two a layer (its attention,
then its feed-forward: a layer's place in its attention's stack and in its
feed-forward's stack differ), each reading its KIND's stack:

- Both attentions: 64 query heads of 192 on K KV heads, keys 192 wide and
  VALUES 128 (`v_head_dim`), `v <- attention_value_scale x v`; rotary on
  the FIRST 64 numbers of a head (`partial_rotary_factor` 0.334 x 192,
  split-half pairs within them, ops/rope.apply_partial_rope), scores
  q.k / sqrt(192).
- GLOBAL (`g_` stacks): K = `num_key_value_heads` (4), base `rope_theta`,
  the whole context. It is the bodies' `Attention` over the PAGE pool of
  the global layers alone, a cell one row of its KV heads side by side:
  `cache_k.pages` [n_G, P, PS, 4 x 192] and `cache_v.pages` [n_G, P, PS,
  4 x 128]; decode is pallas_attention.paged_flat_decode, which says why
  the pool has no head axis (4 heads of 192 tile badly).
- WINDOW (`w_` stacks): K = `swa_num_key_value_heads` (8), base
  `swa_rope_theta`, the last `sliding_window` positions (a position sees
  itself and the 127 before it), and a learnt SINK a head that enters the
  softmax's denominator and takes no value. It is a group's `mixer` whose
  state is a RING a slot beside the pages (llama.StatePool):
  `cache_k.state` [n_W, slots + 1, W, 8, 192], `cache_v.state` the same at
  128. Position p lives in cell p mod W (keys are rotated before they are
  written, so the order of the cells means nothing to the softmax); the
  last slot is the trash ring, where rows that are not `live` write.
  Decode writes its cell and attends over the row's min(len, W) cells in
  ONE call of paged_flash_decode — a ring is a page of the kernel's own
  shape, the table [B, 1] the rows' slots — whatever the context. Prefill
  leaves a prompt's last min(n, W) positions in the ring; an extend chunk
  attends over the ring as it stood and over its own keys under the window
  mask, then writes what of old and new is the last W (a chunk longer than
  the ring is exact).
- Prefill and extend of both kinds are einsums, a block of keys at a time
  with an online softmax (`_attend`): nothing the size of queries x context
  is ever held. Kernels for them are ROADMAP work (flash_prefill refuses
  heads of 192 on the chip).
- The mixture: ops/moe.py's routed layer with DeepSeek-V3's rule
  (`sigmoid_bias_routing`), three-matrix SwiGLU experts, no shared expert.
  A chip may hold a SHARE of the experts (`expert_parallel` in the config,
  `held_experts`): the router scores all of them, the assignments of the
  others are another chip's.

Not served, each refused by name: speculative decoding (a rejected draft's
cell has overwritten the position W before it), an int8 pool, KV on the wire
(`kv_wire_cell` None: a ring has no wire form), int8 weights and LoRA
pools; the engine refuses the prefix cache, the offload tier and the split
role for a family with state per slot (scheduler.py) — a hit would extend
behind pages whose window cells are gone. Outside this module: the
multi-token-prediction layers and the vision and audio encoders of
MiMo-V2.5; `attention_chunk_size` is read by no layer here.

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/deepseek_v3.py's do: the step's counters, or under the
static `routing=True` what the routers decided.
"""

from __future__ import annotations

import dataclasses
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
    Attention,
    LayerGroup,
    LlamaConfig,
    StatePool,
    StateRows,
    _decode_paged_impl,
    _default_mlp_fn,
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj_heads,
    shard_rules_for,
)
from llmlb_tpu.ops import moe
from llmlb_tpu.ops.attention import (
    _pallas_enabled,
    _traced,
    _window_pages,
    band_positions,
    gather_kv_pages,
    note_decode_group,
    paged_decode_work,
)
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.rope import (
    apply_partial_rope,
    rope_frequencies,
    rotary_dim,
)
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32
_NEG_INF = -1e30  # finite, as ops/attention.py's

GLOBAL, WINDOW = 0, 1  # `hybrid_layer_pattern`'s two kinds
WINDOW_DECODE = "paged_window_decode"  # the ring's decode call in a trace
_KEY_BLOCK = 128  # keys a step of a chunk's attention holds scores for


@dataclasses.dataclass(frozen=True)
class MimoV2Config(LlamaConfig):
    # `num_kv_heads`, `rope_theta`: the GLOBAL layers'; `head_dim` the keys'
    pattern: tuple = (GLOBAL, WINDOW)  # hybrid_layer_pattern, a kind a layer
    moe_pattern: tuple = (0, 1)  # moe_layer_freq: 1 = a mixture, 0 = dense
    v_head_dim: int = 128
    window_kv_heads: int = 8  # swa_num_key_value_heads
    window_rope_theta: float = 10000.0  # swa_rope_theta
    sliding_window: int = 128  # cells of a ring: a position and the W-1 before
    partial_rotary_factor: float = 1.0
    value_scale: float = 1.0  # attention_value_scale
    window_sink: bool = True  # add_swa_attention_sink_bias
    # the routed experts THIS CHIP holds (the weights' expert axis): all
    # the router scores, or a share of them [first_expert, + num_experts)
    num_experts: int = 256
    experts_per_token: int = 8
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    router_experts: int = 256  # what the router scores
    first_expert: int = 0

    @property
    def rotary_dim(self) -> int:
        return rotary_dim(self.head_dim_, self.partial_rotary_factor)

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the router's experts this chip holds."""
        return self.first_expert, self.num_experts

    def layers_of(self, kind: int) -> int:
        return self.pattern.count(kind)

    @property
    def num_moe_layers(self) -> int:
        return sum(self.moe_pattern)

    def kv_heads_of(self, kind: int) -> int:
        return self.window_kv_heads if kind == WINDOW else self.num_kv_heads

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "MimoV2Config":
        """Build from a published `config.json`. What this family does not
        compute is refused by name. `expert_parallel` ({"chips", "chip",
        "experts"}) is the deployment's, not the checkpoint's: this chip
        holds `n_routed_experts` of the router's `experts`, the `chip`-th
        such share. `attention_projection_layout` is how a checkpoint stores
        q, k and v, not another function; `attention_chunk_size` is read by
        no layer of the language model."""
        layers = hf["num_hidden_layers"]
        pattern = tuple(hf["hybrid_layer_pattern"])
        moe_pattern = tuple(hf["moe_layer_freq"])
        window = hf.get("sliding_window")
        rs = hf.get("rope_scaling") or {}
        heads, d = hf["num_attention_heads"], hf["head_dim"]
        unsupported = {
            "hybrid_layer_pattern": (len(pattern) != layers
                                     or bool(set(pattern) - {GLOBAL, WINDOW})),
            "moe_layer_freq": (len(moe_pattern) != layers
                               or bool(set(moe_pattern) - {0, 1})),
            "sliding_window": not window,
            "sliding_window_size": hf.get("sliding_window_size",
                                          window) != window,
            "add_full_attention_sink_bias": bool(
                hf.get("add_full_attention_sink_bias")),
            "swa_num_attention_heads": hf.get("swa_num_attention_heads",
                                              heads) != heads,
            "swa_head_dim": hf.get("swa_head_dim", d) != d,
            "swa_v_head_dim": hf.get("swa_v_head_dim",
                                     hf["v_head_dim"]) != hf["v_head_dim"],
            "rope_scaling": rs.get("rope_type", rs.get("type", "default"))
            != "default",
            "attention_bias": bool(hf.get("attention_bias")),
            "n_group": hf.get("n_group", 1) != 1,
            "topk_group": hf.get("topk_group", 1) != 1,
            "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
            "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "n_shared_experts": bool(hf.get("n_shared_experts")),
            "tie_word_embeddings": bool(hf.get("tie_word_embeddings")),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"mimo_v2 config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/mimo_v2.py; refusing to serve wrong logits")
        held, experts, first = held_share(hf)
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=d,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("layernorm_epsilon", hf.get("rms_norm_eps", 1e-5)),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            dtype=dtype,
            pattern=pattern,
            moe_pattern=moe_pattern,
            v_head_dim=hf["v_head_dim"],
            window_kv_heads=hf.get("swa_num_key_value_heads",
                                   hf["num_key_value_heads"]),
            window_rope_theta=float(hf.get("swa_rope_theta",
                                           hf.get("rope_theta", 10000.0))),
            sliding_window=int(window),
            partial_rotary_factor=float(hf.get("partial_rotary_factor", 1.0)),
            value_scale=float(hf.get("attention_value_scale") or 1.0),
            window_sink=bool(hf.get("add_swa_attention_sink_bias")),
            num_experts=held,
            router_experts=experts,
            first_expert=first,
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            routed_scaling_factor=float(hf.get("routed_scaling_factor")
                                        or 1.0),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        )


# ---------------------------------------------------------------------------
# Params: one stack a kind of attention and a kind of feed-forward
# ---------------------------------------------------------------------------

G, W, DENSE = "g_", "w_", "dense_"  # the stacks' prefixes; the mixtures': ""
_ATTN = ("ln_attn", "wq", "wk", "wv", "wo")
_DENSE_MLP = ("ln_mlp", "wg", "wu", "wd")
_MOE_MLP = ("ln_mlp", "router", "router_bias", "we_gate", "we_up", "we_down")
_EXPERTS = ("we_gate", "we_up", "we_down")


def _window_names(cfg: MimoV2Config) -> tuple:
    return _ATTN + (("sink",) if cfg.window_sink else ())


def _layer_shapes(cfg: MimoV2Config, kv_heads: int
                  ) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, h, d, dv = (cfg.hidden_size, cfg.num_heads, cfg.head_dim_,
                   cfg.v_head_dim)
    f, x, fm = (cfg.intermediate_size, cfg.num_experts,
                cfg.moe_intermediate_size)
    return {
        "ln_attn": ((e,), 0), "wq": ((e, h * d), e),
        "wk": ((e, kv_heads * d), e), "wv": ((e, kv_heads * dv), e),
        "wo": ((h * dv, e), h * dv), "sink": ((h,), 0),
        "ln_mlp": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, cfg.router_experts), e),
        "router_bias": ((cfg.router_experts,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
    }


def _leaves(cfg: MimoV2Config) -> list[stacks.Leaf]:
    """Every stacked leaf the patterns call for: a stack a kind of
    attention, at its own KV heads, and a kind of feed-forward."""
    dense = len(cfg.moe_pattern) - cfg.num_moe_layers
    stacks_ = [(G, _ATTN, cfg.layers_of(GLOBAL), cfg.num_kv_heads),
               (W, _window_names(cfg), cfg.layers_of(WINDOW),
                cfg.window_kv_heads),
               (DENSE, _DENSE_MLP, dense, 0),
               ("", _MOE_MLP, cfg.num_moe_layers, 0)]
    return [leaf for *stack, kv_heads in stacks_ for leaf in
            stacks.stack_leaves(_layer_shapes(cfg, kv_heads), [stack])]


def _own_rule(cfg, name: str, k, shape):
    """The window layers' SINKS a seeded normal of sd 1 in float32, the rest
    as the other mixtures' (init_params says why neither is zero)."""
    if name == "sink":
        return jax.random.normal(k, shape, F32)
    return stacks.seeded_bias(0.02)(cfg, name, k, shape)


def init_params(cfg: MimoV2Config, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): matrices normal x fan_in^-0.5, norms ones, the router's
    choice bias a seeded normal of sd 0.02 (deepseek_v3.init_params says why
    it is not zero), and the window layers' SINKS a seeded normal of sd 1 in
    float32, not zero: a program that leaves the sink out, or gives every
    head the same, then differs from one that follows the rule."""
    return stacks.init_params(cfg, key, _leaves(cfg), _own_rule)


def param_logical_axes(cfg: MimoV2Config) -> dict[str, tuple]:
    layer = {**stacks.GQA_AXES, **stacks.MLP_AXES, **stacks.EXPERT_AXES}
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: MimoV2Config, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: pages of the global layers, a ring a slot of the window layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: MimoV2Config, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool: pages of the GLOBAL layers [n_G, P, PS, K x 192]
    (values K x 128), a cell's KV heads side by side in one row, and per
    slot the WINDOW layers' ring [n_W, slots + 1, W, K_w, 192] (128). Page
    0 is the trash page and the last ring the trash ring: a decode row that
    is not live writes there. `num_slots` 1 serves a caller with one row."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype

    def pool(width):
        return StatePool(
            jnp.zeros((cfg.layers_of(GLOBAL), num_pages, page_size,
                       cfg.num_kv_heads * width), dtype),
            jnp.zeros((cfg.layers_of(WINDOW), num_slots + 1,
                       cfg.sliding_window, cfg.window_kv_heads, width),
                      dtype))

    return pool(cfg.head_dim_), pool(cfg.v_head_dim)


def kv_pages_shardings(cfg: MimoV2Config, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """The pages replicate over tp (a cell's heads are one row); a ring
    shards over its heads as llama's pages do."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    pages = logical_to_sharding(mesh, rules, "layers", None, "seq", None)
    ring = logical_to_sharding(mesh, rules, "layers", None, None,
                               "kv_heads", "head_dim")
    return (StatePool(pages, ring), StatePool(pages, ring))


def kv_pool_layers(cfg: MimoV2Config) -> int:
    """Layers of the page pool: the global layers alone."""
    return cfg.layers_of(GLOBAL)


def kv_token_layer_bytes(cfg: MimoV2Config, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool: a key of
    192 and a value of 128 on every global KV head; a window layer leaves
    nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return (cfg.num_kv_heads * (cfg.head_dim_ + cfg.v_head_dim)
            * jnp.dtype(cfg.dtype).itemsize)


def state_slot_bytes(cfg: MimoV2Config) -> int:
    """HBM bytes one slot holds beside its pages: W cells of keys and
    values on every window KV head, in every window layer."""
    return (cfg.layers_of(WINDOW) * cfg.sliding_window * cfg.window_kv_heads
            * (cfg.head_dim_ + cfg.v_head_dim)
            * jnp.dtype(cfg.dtype).itemsize)


def kv_wire_cell(cfg: MimoV2Config) -> None:
    """Nothing ships: a ring has no KVSH wire form, and pages without it
    are the global layers' half of a sequence. A handoff, resume or park
    replays its tokens instead."""
    return None


# ---------------------------------------------------------------------------
# Attention of a chunk: a block of keys at a time, online softmax
# ---------------------------------------------------------------------------

def _attend(q, q_pos, sources, *, kv_heads: int, v_dim: int, window=None,
            sink=None):
    """q [B, T, H, D] at positions `q_pos` [B, T] over `sources`, each
    `(blocks, fetch)`: `fetch(j) -> (k [B, S, K, D], v [B, S, K, Dv], k_pos
    [B, S])` gives block j < `blocks` (a run-time count) of its keys, their
    positions beside them, below 0 where a cell is void. Query i sees key j
    iff 0 <= pos_j <= pos_i and, under `window`, pos_i - pos_j < window.
    `sink` [H] enters the denominator and takes no value. Softmax online
    over the blocks in float32 (pallas_attention._online_update's rule):
    the largest value held is one block's scores. Returns [B, T, H, Dv]."""
    b, t, h, d = q.shape
    kh, dv = kv_heads, v_dim
    g = h // kh
    qg = q.reshape(b, t, kh, g, d)
    scale = d**-0.5
    if sink is None:
        m = jnp.full((b, kh, g, t, 1), _NEG_INF, F32)
        l = jnp.zeros((b, kh, g, t, 1), F32)
    else:
        m = jnp.broadcast_to(sink.astype(F32).reshape(1, kh, g, 1, 1),
                             (b, kh, g, t, 1))
        l = jnp.ones((b, kh, g, t, 1), F32)
    acc = jnp.zeros((b, kh, g, t, dv), F32)

    def step(fetch, j, carry):
        m, l, acc = carry
        k, v, k_pos = fetch(j)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                            preferred_element_type=F32) * scale
        seen = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :]
                                           <= q_pos[:, :, None])
        if window is not None:
            seen &= q_pos[:, :, None] - k_pos[:, None, :] < window
        scores = jnp.where(seen[:, None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        fix = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l = l * fix + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * fix + jnp.einsum("bkgts,bskd->bkgtd", p.astype(v.dtype),
                                     v, preferred_element_type=F32)
        return m_new, l, acc

    carry = (m, l, acc)
    for blocks, fetch in sources:
        if isinstance(blocks, int) and blocks == 1:
            carry = step(fetch, 0, carry)
        else:
            carry = lax.fori_loop(0, blocks, partial(step, fetch), carry)
    _, l, acc = carry
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, dv).astype(q.dtype)


def _own_blocks(k, v, lens, start=None):
    """A chunk's own keys [B, T, K, D] as a source of _attend: blocks of
    _KEY_BLOCK positions (the whole chunk where that does not divide it)
    from `start` ([B], default 0), void from `lens` on."""
    b, t = k.shape[:2]
    size = _KEY_BLOCK if t % _KEY_BLOCK == 0 else t
    first = jnp.zeros((b,), jnp.int32) if start is None else start

    def fetch(j):
        at = j * size
        idx = at + jnp.arange(size, dtype=jnp.int32)[None, :]
        pos = jnp.where(idx < lens[:, None], first[:, None] + idx, -1)
        return (lax.dynamic_slice_in_dim(k, at, size, axis=1),
                lax.dynamic_slice_in_dim(v, at, size, axis=1), pos)

    return t // size, fetch


def _page_blocks(k_pages, v_pages, layer, tables, kv_lens):
    """A row's pages of one layer of the pool as a source of _attend: page
    j of every row's table, cells at or past `kv_lens` void; as many blocks
    as the longest row holds."""
    ps = k_pages.shape[2]

    def fetch(j):
        page = tables[:, j]
        idx = j * ps + jnp.arange(ps, dtype=jnp.int32)[None, :]
        return (k_pages[layer, page], v_pages[layer, page],
                jnp.where(idx < kv_lens[:, None], idx, -1))

    blocks = jnp.minimum(-(-jnp.max(kv_lens) // ps), tables.shape[1])
    return blocks, fetch


# ---------------------------------------------------------------------------
# The two attentions
# ---------------------------------------------------------------------------

def _qkv(cfg: MimoV2Config, lp: Params, x, positions, kind: int):
    """(q [B, T, H, 192] and k [B, T, K, 192] rotated on their first
    `rotary_dim` numbers at the kind's base, v [B, T, K, 128] scaled) of
    the normed input."""
    b, t, _ = x.shape
    kh = cfg.kv_heads_of(kind)
    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)

    q = _proj_heads(lp, "wq", h).reshape(b, t, cfg.num_heads, cfg.head_dim_)
    k = _proj_heads(lp, "wk", h).reshape(b, t, kh, cfg.head_dim_)
    v = _proj_heads(lp, "wv", h).reshape(b, t, kh, cfg.v_head_dim)
    if cfg.value_scale != 1.0:
        v = (v.astype(F32) * cfg.value_scale).astype(v.dtype)
    inv_freq = rope_frequencies(
        cfg.rotary_dim,
        cfg.window_rope_theta if kind == WINDOW else cfg.rope_theta)
    return (apply_partial_rope(q, positions, inv_freq),
            apply_partial_rope(k, positions, inv_freq), v)


def _global_block(cfg: MimoV2Config, lp: Params, x, positions, inv_freq,
                  attn_fn, lora_idx=None):
    """llama._attn_block for a global layer: partial rotary at the global
    base (the bodies' `inv_freq` is of the whole head: not used), values
    scaled and narrower than keys. Returns (x_out, k, v)."""
    del inv_freq, lora_idx
    b, t, _ = x.shape
    q, k, v = _qkv(cfg, lp, x, positions, GLOBAL)
    # a token leaves its KV heads side by side in one row of the pool
    k, v = k.reshape(b, t, -1), v.reshape(b, t, -1)
    attn = attn_fn(q, k, v)
    return x + attn.reshape(b, t, -1) @ lp["wo"], k, v


def _shape_kw(k, v) -> dict:
    return {"kv_heads": k.shape[-2], "v_dim": v.shape[-1]}


def _global_attention(cfg: MimoV2Config) -> Attention:
    """The global layers' llama.Attention over their flat page pool."""
    kh, d, dv = cfg.num_kv_heads, cfg.head_dim_, cfg.v_head_dim
    shape = {"kv_heads": kh, "v_dim": dv}

    def heads(k, v):  # [B, S, K*D], [B, S, K*Dv] -> [B, S, K, .]
        return (k.reshape(*k.shape[:2], kh, d), v.reshape(*v.shape[:2], kh, dv))

    def prefill(q, k, v, prompt_lens):
        b, t = q.shape[:2]
        _traced["global_prefill"] = "xla"
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None],
                                     (b, t))
        return _attend(q, positions, [_own_blocks(*heads(k, v), prompt_lens)],
                       **shape)

    def extend(q, k_pages, v_pages, layer, tables, positions, chunk_lens):
        _traced["global_extend"] = "xla"
        blocks, fetch = _page_blocks(k_pages, v_pages, layer, tables,
                                     positions[:, 0] + chunk_lens)

        def by_head(j):
            k, v, pos = fetch(j)
            return (*heads(k, v), pos)

        return _attend(q, positions, [(blocks, by_head)], **shape)

    def decode(q, k_pages, v_pages, layer, tables, kv_lens, window=None,
               work=None):
        ps = k_pages.shape[2]
        pages = _window_pages(tables, ps, window)
        if _pallas_enabled():
            from llmlb_tpu.ops.pallas_attention import paged_flat_decode

            if work is None:
                work = paged_decode_work(k_pages, v_pages, tables, kv_lens,
                                         window)
            _traced["global_decode"] = "pallas:paged_flat_decode"
            note_decode_group("paged_flat_decode", work)
            return paged_flat_decode(
                q[:, 0], k_pages, v_pages, layer, tables, kv_lens,
                num_kv=kh, pages=pages, work=work)[:, None]
        _traced["global_decode"] = "xla"
        tables = tables[:, :pages]
        return _decode_einsum(q, *heads(
            gather_kv_pages(k_pages, tables, layer=layer),
            gather_kv_pages(v_pages, tables, layer=layer)), kv_lens, None)

    return Attention(_global_block, prefill, extend, decode,
                     paged_decode_work)


def _decode_einsum(q, k, v, kv_lens, sink):
    """One query a row [B, 1, H, D] over its first `kv_lens` of the cells
    k [B, S, K, D], v [B, S, K, Dv]: the XLA route of both decodes."""
    idx = jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    pos = jnp.where(idx < kv_lens[:, None], 0, -1)  # seen, or void
    zeros = jnp.zeros((q.shape[0], 1), jnp.int32)
    return _attend(q, zeros, [(1, lambda j: (k, v, pos))], sink=sink,
                   **_shape_kw(k, v))


def _window_mixer(cfg: MimoV2Config):
    """llama.LayerGroup's `mixer` for a window layer: attention over the
    row's ring (`cache_k.state`, `cache_v.state`), which it keeps."""
    w = cfg.sliding_window
    shared: dict = {}  # a decode step's ring work-list, built by its first
    # window layer for all of them (the step is unrolled: one trace)

    def mixer(lp, x, cache_k, cache_v, layer, rows: StateRows):
        b, t, _ = x.shape
        ring_k, ring_v = cache_k.state, cache_v.state
        slots = (jnp.arange(b, dtype=jnp.int32) if rows.slots is None
                 else rows.slots)
        sink = lp.get("sink")
        if rows.lens is None:  # decode: one token a row
            pos = rows.start_pos
            q, k, v = _qkv(cfg, lp, x, pos[:, None], WINDOW)
            kv_lens = jnp.minimum(pos + 1, w)
            into = slots
            if rows.live is not None:
                kv_lens = jnp.where(rows.live, kv_lens, 0)
                into = jnp.where(rows.live, slots, ring_k.shape[1] - 1)
            ring_k = ring_k.at[layer, into, pos % w].set(k[:, 0])
            ring_v = ring_v.at[layer, into, pos % w].set(v[:, 0])
            attn = _ring_decode(q, ring_k, ring_v, layer, slots, kv_lens,
                                sink, shared)
        else:
            start = (jnp.zeros((b,), jnp.int32) if rows.start_pos is None
                     else rows.start_pos)
            positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            q, k, v = _qkv(cfg, lp, x, positions, WINDOW)
            sources = [_own_blocks(k, v, rows.lens, start)]
            at = (layer, slots)
            # the ring once the chunk is in: a cell's position is the
            # chunk's where that is at or past `start`, else what it held
            held = band_positions(start + rows.lens, w)  # [B, W]
            pick = jnp.clip(held - start[:, None], 0, t - 1)[:, :, None, None]
            new_k = jnp.take_along_axis(k, pick, axis=1)
            new_v = jnp.take_along_axis(v, pick, axis=1)
            if rows.start_pos is not None:  # the ring as it stood
                old_k, old_v = ring_k[at], ring_v[at]  # [B, W, K, .]
                before = band_positions(start, w)
                sources.insert(0, (1, lambda j: (old_k, old_v, before)))
                new = (held >= start[:, None])[:, :, None, None]
                new_k = jnp.where(new, new_k, old_k)
                new_v = jnp.where(new, new_v, old_v)
            _traced["window_chunk"] = "xla"
            attn = _attend(q, positions, sources, window=w, sink=sink,
                           **_shape_kw(k, v))
            ring_k = ring_k.at[at].set(new_k)
            ring_v = ring_v.at[at].set(new_v)
        return (x + attn.reshape(b, t, -1) @ lp["wo"],
                cache_k._replace(state=ring_k), cache_v._replace(state=ring_v))

    return mixer


def _ring_decode(q, ring_k, ring_v, layer, slots, kv_lens, sink, shared):
    """One token a row [B, 1, H, D] over its slot's ring: ONE call of
    paged_flash_decode over the live rows, a ring a page (its table the
    rows' slots), min(len, W) cells of it whatever the context."""
    if _pallas_enabled():
        from llmlb_tpu.ops.pallas_attention import (
            decode_work_list,
            paged_flash_decode,
        )

        _traced["window_decode"] = "pallas:" + WINDOW_DECODE
        table = slots[:, None]
        if "work" not in shared:
            shared["work"] = decode_work_list(
                table, kv_lens, page_size=ring_k.shape[2], pages=1)
        note_decode_group(WINDOW_DECODE, shared["work"])
        return paged_flash_decode(
            q[:, 0], ring_k, ring_v, layer, table, kv_lens, pages=1,
            work=shared["work"], sink=sink, name=WINDOW_DECODE)[:, None]
    _traced["window_decode"] = "xla"
    return _decode_einsum(q, ring_k[layer, slots], ring_v[layer, slots],
                          kv_lens, sink)


def _moe_mlp_fn(cfg: MimoV2Config, live=None):
    """llama's `mlp_fn` for a mixture layer: the routed experts this chip
    holds by DeepSeek-V3's rule, and as aux the layer's ops/moe.Routing.
    `live`: as deepseek_v3._moe_mlp_fn."""
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
        return routed.reshape(b, t, m), routing

    return fn


def _groups(cfg: MimoV2Config, live=None) -> list[LayerGroup]:
    """Two groups a layer, in the patterns' order: its attention (a global
    layer attends over the page pool, a window layer is a mixer over its
    ring), then its feed-forward. A group's parameters and its place in its
    pool are its kind's next row."""
    mixer, moe_fn = _window_mixer(cfg), _moe_mlp_fn(cfg, live)
    seen = dict.fromkeys((GLOBAL, WINDOW, "dense", "moe"), 0)

    def take(kind):
        seen[kind] += 1
        return seen[kind] - 1

    groups = []
    for kind, routed in zip(cfg.pattern, cfg.moe_pattern):
        at = take(kind)
        if kind == WINDOW:
            groups.append(LayerGroup(
                _window_names(cfg), None, 1, W, start=at, pool_layer=at,
                attends=False, mixer=mixer, scope="window_attention"))
        else:
            groups.append(LayerGroup(
                _ATTN, None, 1, G, start=at, pool_layer=at,
                scope="global_attention"))
        if routed:
            groups.append(LayerGroup(
                _MOE_MLP, moe_fn, 1, whole=_EXPERTS, start=take("moe"),
                attends=False, scope="expert_mixture"))
        else:
            groups.append(LayerGroup(
                _DENSE_MLP, _default_mlp_fn, 1, DENSE, start=take("dense"),
                attends=False, scope="dense_feed_forward"))
    return groups


def step_counters(cfg: MimoV2Config) -> dict[str, tuple]:
    """The counters a decode step returns, by name and shape (all int32):
    the cells the step's attentions read — a live row's min(len, W) in
    every window layer, its whole length in every global one — and
    deepseek_v3's expert load over the HELD experts beside the assignments
    that went to experts this chip does not hold."""
    shapes: dict[str, tuple] = {"window_kv_tokens": (),
                                "global_kv_tokens": ()}
    if cfg.num_moe_layers:
        shapes.update({
            "experts_touched": (), "expert_assignments": (),
            "expert_load_max": (), "assignments_elsewhere": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)})
    return shapes


def _extra(cfg: MimoV2Config, aux, shape, routing: bool, kv_lens):
    """What follows (logits, cache_k, cache_v): the step's counters, or
    under `routing` what the routers decided. `aux` has an entry a group;
    the mixtures' are stacked here in layer order. `kv_lens` [B]: the cells
    each row's context holds once the call is done, 0 for a row not live."""
    # two groups a layer (_groups): its attention, then its feed-forward
    routed = [flag for r in cfg.moe_pattern for flag in (False, bool(r))]
    found = [a[0] if isinstance(a, list) else
             jax.tree.map(lambda v: v[0], a)
             for a, is_moe in zip(aux, routed) if is_moe]
    stacked = ([jax.tree.map(lambda *v: jnp.stack(v), *found)]
               if found else [None])
    out = _routed_extra(cfg, stacked, shape, routing)
    if routing:
        return out
    counters = dict(out[0]) if out else {}
    counters["window_kv_tokens"] = cfg.layers_of(WINDOW) * jnp.sum(
        jnp.minimum(kv_lens, cfg.sliding_window), dtype=jnp.int32)
    counters["global_kv_tokens"] = cfg.layers_of(GLOBAL) * jnp.sum(
        kv_lens, dtype=jnp.int32)
    if found:
        counters["assignments_elsewhere"] = (
            jnp.zeros((), jnp.int32) if stacked[0].elsewhere is None
            else jnp.sum(stacked[0].elsewhere, dtype=jnp.int32))
    return (counters,)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: MimoV2Config, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       routing: bool = False, slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose rings the rows write: whatever a ring held is void."""
    logits, cache_k, cache_v, aux = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_global_attention(cfg),
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: MimoV2Config, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
                         routing: bool = False, slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' rings are read as they stood,
    attended over with the chunk's own keys, and left holding the last W
    positions of `start_pos + chunk_lens`."""
    logits, cache_k, cache_v, aux = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_global_attention(cfg), slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, input_ids.shape, routing, start_pos + chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: MimoV2Config, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, routing: bool = False,
                      slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged (`window`, static, is the engine's context
    bucket for the GLOBAL layers' sweep, not the model's sliding window); a
    row that is not `live` writes the trash ring and reads no cell."""
    logits, cache_k, cache_v, aux = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live,
        groups=_groups(cfg, live), attention=_global_attention(cfg), slot_ids=slot_ids)
    kv_lens = seq_lens + 1
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
    return (logits, cache_k, cache_v, *_extra(
        cfg, aux, (input_ids.shape[0], 1), routing, kv_lens))


# It verifies no draft: a rejected token's cell has overwritten the position
# W before it, and there is no snapshot to roll back to. `slot_ids`: the
# rows' slots (default row i in slot i); `num_slots`: the rings of the pool.
FAMILY = Family(
    name="mimo_v2", config_class=MimoV2Config, model_types=("mimo_v2",),
    mechanism_keys=("sliding_window", "partial_rotary_factor",
                    "hybrid_layer_pattern", "moe_layer_freq",
                    "n_routed_experts", "moe_intermediate_size",
                    "swa_num_key_value_heads", "expert_parallel"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="page pool beside a ring a slot",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        **EXPERT_LOAD_COUNTERS,
        "assignments_elsewhere": StepCounter(
            "sum", "moe_assignments_elsewhere_total"),
        "window_kv_tokens": StepCounter("sum", "window_kv_tokens_total"),
        "global_kv_tokens": StepCounter("sum", "global_kv_tokens_total")},
    step_counters=step_counters, paged_keywords=("routing", "slot_ids"),
    keywords_of={"init_kv_pages": ("num_slots",)})
