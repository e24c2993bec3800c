"""DeepSeek-V3-class decoder (`model_type` `deepseek_v3`: DeepSeek-V3/R1,
Kanana-2-30B-A3B): multi-head LATENT attention and a sigmoid-routed mixture
of experts with shared experts behind leading dense layers.

Same serving contract and the same three shared bodies as models/llama.py
(docs/deepseek-v3.md); what differs is handed to them:

- A stack of two kinds of layer (llama.LayerGroup): `first_k_dense` layers
  with a dense SwiGLU, then expert layers. Each group owns its stacked
  parameters — the dense group under a `dense_` prefix — so a prefill scans
  each group over its own arrays and decode unrolls both.
- Latent attention (llama.Attention). A token leaves in the page pool its
  normalised latent `c` (kv_lora_rank numbers) and the rotated key all heads
  share (qk_rope_head_dim numbers): two pools under the same page ids,
  `cache_k` = c [L, P, PS, C] and `cache_v` = k_rope [L, P, PS, 128], no
  head axis, no second copy (the rope pool's row is one whole 128-lane tile:
  ops/pallas_attention.paged_latent_decode says why). PREFILL materialises
  per-head keys and values from the chunk's own latent (T x T attention at
  width 192/128 is cheaper than at 576/512); EXTEND, VERIFY and DECODE use
  the ABSORBED form over the pool: q_abs = W^K_h q_nope attends the latent
  itself and W^V_h carries the mix back out, so a cached prefix is never
  up-projected. `wk_b` / `wv_b` are W_kv_b split per head once, at load.
  The block also computes a low-rank query and scaled latents for a family
  whose configuration has them (`q_lora_rank`, `*_lora_scale`); this
  family's own `from_hf_config` still refuses `q_lora_rank`, because its
  checkpoint loader (engine/weights.py) maps no `q_a_proj`.
- The routed layer is ops/moe.py's, with DeepSeek-V3's rule
  (sigmoid_bias_routing) and the shared experts added outside the routing.

The paged serving functions return one value after (logits, cache_k,
cache_v): the step's expert-load counters (step_counters), which the
scheduler carries out with the fetch it already makes. One static switch,
off in serving: under `routing=True` that value is instead (chosen
[Lm, B, T, k], scores [Lm, B, T, X] f32 = sigmoid(h W_r) + b, kept
[Lm, B, T, k] all true) over the Lm expert layers (benchmark/routing.py's
contract).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.models import stacks
from llmlb_tpu.models.family import Family, StepCounter
from llmlb_tpu.models.llama import (
    Attention,
    LayerGroup,
    LlamaConfig,
    _decode_paged_impl,
    _default_mlp_fn,
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj,
    _proj_heads,
    shard_rules_for,
)
from llmlb_tpu.ops import moe
from llmlb_tpu.ops.attention import (
    latent_attention_prefill,
    paged_decode_work,
    paged_latent_decode,
    paged_latent_extend,
)
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.rope import apply_partial_rope, apply_rope
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]

ROPE_CELL = 128  # lanes of the rope pool's row: the rope key, then zeros
# Upper bounds of the expert-load histogram's buckets (assignments one
# expert took in one step); the last bucket is open.
LOAD_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(LlamaConfig):
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 128  # n_routed_experts
    experts_per_token: int = 6
    moe_intermediate_size: int = 768
    num_shared_experts: int = 2
    first_k_dense: int = 1  # first_k_dense_replace
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    rope_interleave: bool = True
    # The low-rank query (down, RMS norm, up) of a family that has one, and
    # the factors by which its two latents are scaled behind their norms
    # (`mla_scale_q_lora` / `mla_scale_kv_lora`: models/longcat_flash.py).
    # None and 1: the block below is the program it always was.
    q_lora_rank: int | None = None
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0
    # `mla_use_nope` (models/kimi_linear.py): the block rotates nothing. The
    # shared key's numbers and the queries' last ones are projected, cached
    # in the rope cell and scored as they are.
    mla_nope: bool = False
    # models/dots3_note.py: a sigmoid GATE a head on the attention's output
    # (`w_gate` [E, H], from the layer's normed input), and a learned INDEXER
    # (`_index_block`) whose `index_topk` highest-scored cells are all a
    # query attends over (0: none, the layer attends over its whole context).
    attn_gate: bool = False
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "DeepseekV3Config":
        """Build from a published `config.json`. What this family does not
        compute is refused by name: served wrong is worse than not served."""
        unsupported = {
            "q_lora_rank": hf.get("q_lora_rank") is not None,
            "rope_scaling": hf.get("rope_scaling") is not None,
            "attention_bias": bool(hf.get("attention_bias")),
            "n_group": hf.get("n_group", 1) != 1,
            "topk_group": hf.get("topk_group", 1) != 1,
            "moe_layer_freq": hf.get("moe_layer_freq", 1) != 1,
            "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
            "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"deepseek_v3 config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/deepseek_v3.py; refusing to serve wrong logits"
                + (" (a low-rank query is computed by _mla_block and served "
                   "for the model_types longcat_flash and dots3_note, whose "
                   "checkpoints are not this family's)"
                   if "q_lora_rank" in bad else ""))
        rope = hf.get("qk_rope_head_dim", 64)
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=rope,  # what RoPE turns: the bodies' rope_frequencies
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            dtype=dtype,
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
            num_experts=hf["n_routed_experts"],
            experts_per_token=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_shared_experts=hf.get("n_shared_experts", 0),
            first_k_dense=hf.get("first_k_dense_replace", 0),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            rope_interleave=bool(hf.get("rope_interleave", True)),
        )


def held_share(hf: dict, key: str = "n_routed_experts"
               ) -> tuple[int, int, int]:
    """(experts held here, experts the router scores, the first held) of a
    config whose `expert_parallel` ({"chips", "chip", "experts"}) says that
    this chip holds `n_routed_experts` (`key`: the family's own name for
    the count) of a deployment's `experts`, the `chip`-th such share; the
    deployment's key, not a checkpoint's. Without it every expert is
    held."""
    held = hf[key]
    share = hf.get("expert_parallel") or {}
    experts = int(share.get("experts", held))
    chips, chip = int(share.get("chips", 1)), int(share.get("chip", 0))
    if held * chips != experts or not 0 <= chip < chips:
        raise ValueError(
            f"expert_parallel {share} does not split {experts} experts "
            f"into shares of {key} = {held}")
    return held, experts, chip * held


# ---------------------------------------------------------------------------
# Params: two groups, each with its own stacked arrays
# ---------------------------------------------------------------------------

DENSE = "dense_"
_ATTN = ("wq", "wkv_a", "ln_kv", "wk_b", "wv_b", "wo", "ln_attn", "ln_mlp")
_DENSE_MLP = ("wg", "wu", "wd")
_MOE_MLP = ("router", "router_bias", "we_gate", "we_up", "we_down",
            "ws_gate", "ws_up", "ws_down")


def _layer_shapes(cfg: DeepseekV3Config) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, h, c = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f, x, fm = cfg.intermediate_size, cfg.num_experts, cfg.moe_intermediate_size
    fs = fm * cfg.num_shared_experts
    return {
        "wq": ((e, h * (dn + dr)), e),
        "wkv_a": ((e, c + dr), e),
        "ln_kv": ((c,), 0),
        "wk_b": ((h, c, dn), c),  # W_kv_b's key half, per head
        "wv_b": ((h, c, dv), c),  # and its value half
        "wo": ((h * dv, e), h * dv),
        "ln_attn": ((e,), 0),
        "ln_mlp": ((e,), 0),
        "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f),
        "router": ((e, x), e), "router_bias": ((x,), 0),
        "we_gate": ((x, e, fm), e), "we_up": ((x, e, fm), e),
        "we_down": ((x, fm, e), fm),
        "ws_gate": ((e, fs), e), "ws_up": ((e, fs), e), "ws_down": ((fs, e), fs),
    }


def _group_leaves(cfg: DeepseekV3Config) -> list[stacks.Leaf]:
    """Every stacked leaf: the dense group's under `DENSE`, the mixture
    group's under their names."""
    return stacks.stack_leaves(_layer_shapes(cfg), [
        (DENSE, _ATTN + _DENSE_MLP, cfg.first_k_dense),
        ("", _ATTN + _MOE_MLP, cfg.num_moe_layers)])


def init_params(cfg: DeepseekV3Config, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): fan-in scaled normal, norms ones, and the router's
    choice bias a seeded draw that is NOT zero — a program that weighs by
    score + bias, or chooses by score alone, then differs from one that
    follows the rule. The draw is left as it is: such a router spreads a
    decode step of 64 rows over 118 of 128 experts, where uniform routing
    gives 122 and a bias balanced over seeded tokens by the architecture's
    own rule gave 114 (PERF.md section 6, PR 31)."""
    return stacks.init_params(cfg, key, _group_leaves(cfg),
                              stacks.seeded_bias(0.02), head_last=True)


def param_logical_axes(cfg: DeepseekV3Config) -> dict[str, tuple]:
    layer = {
        **stacks.MLP_AXES, **stacks.EXPERT_AXES,
        "wq": ("embed", "heads"), "wkv_a": ("embed", None),
        "wk_b": ("heads", None, None), "wv_b": ("heads", None, None),
        "wo": ("heads", "embed"), "ln_attn": ("embed",), "ln_mlp": ("embed",),
        "router": ("embed", None),
    }
    return stacks.param_logical_axes(cfg, _group_leaves(cfg), layer)


def param_shardings(cfg: DeepseekV3Config, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The latent page pool
# ---------------------------------------------------------------------------

def kv_token_layer_bytes(cfg: DeepseekV3Config, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the pool: the latent and
    the rope pool's tile-wide row."""
    FAMILY.refuse(int8_kv=quantized)
    return (cfg.kv_lora_rank + ROPE_CELL) * jnp.dtype(cfg.dtype).itemsize


def kv_wire_cell(cfg: DeepseekV3Config) -> None:
    """The latent pool has no KVSH wire form yet (engine/kv_transfer.py
    ships K and V pages of one shape; the latent and the rope key are two
    shapes): an engine of this family ships and adopts nothing, and a
    handoff, resume or park replays its tokens instead."""
    return None


def init_kv_pages(cfg: DeepseekV3Config, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False):
    """The latent page pool, as the (cache_k, cache_v) pair of the serving
    contract: c [L, P, PS, kv_lora_rank] and k_rope [L, P, PS, 128] (the
    qk_rope_head_dim numbers, then zeros). Page 0 is the trash page."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    lead = (cfg.num_layers, num_pages, page_size)
    return (jnp.zeros((*lead, cfg.kv_lora_rank), dtype),
            jnp.zeros((*lead, ROPE_CELL), dtype))


def kv_pages_shardings(cfg: DeepseekV3Config, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Every head reads the whole latent: the pool replicates (pages cannot
    split over dp, and there is no head axis for tp)."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    sharding = logical_to_sharding(mesh, rules, "layers", None, "seq", None)
    return (sharding, sharding)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

class LatentQuery(NamedTuple):
    """What the block hands the three attention ops as `q`: the two parts
    of the queries and the layer's per-head halves of W_kv_b."""

    nope: jnp.ndarray  # [B, T, H, Dn]
    rope: jnp.ndarray  # [B, T, H, Dr], rotated
    wk_b: jnp.ndarray  # [H, C, Dn]
    wv_b: jnp.ndarray  # [H, C, Dv]


class IndexedQuery(NamedTuple):
    """LatentQuery of a layer with a learned indexer: beside it the index
    queries and the weights of their heads (`_index_block`)."""

    nope: jnp.ndarray
    rope: jnp.ndarray
    wk_b: jnp.ndarray
    wv_b: jnp.ndarray
    index_q: jnp.ndarray  # [B, T, Hi, Di], rotated
    index_w: jnp.ndarray  # [B, T, Hi] f32, scaled


def _index_block(cfg: DeepseekV3Config, lp: Params, h, c_q, positions,
                 inv_freq):
    """The learned indexer of DeepSeek-V3.2's sparse attention, from the
    layer's normed input `h` and the queries' scaled latent `c_q`: index
    queries q^I = c_q W^I_q [B, T, Hi, Di], ONE index key a token k^I =
    LayerNorm(h W^I_k) [B, T, Di] (weight and bias, eps 1e-6), both rotated
    on their first `qk_rope_head_dim` numbers in split halves at the layer's
    base, and the heads' weights w = h W^I_w Hi^-1/2 Di^-1/2 [B, T, Hi] in
    float32. A cell's score is sum_j w_j ReLU(q^I_j . k^I)
    (ops/attention.index_scores)."""
    b, t, _ = h.shape
    hi, di = cfg.index_heads, cfg.index_head_dim
    q_i = _proj_heads(lp, "wi_q", c_q).reshape(b, t, hi, di)
    k_i = _proj(lp, "wi_k", h).astype(jnp.float32)
    k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
    k_i = k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True)
                              + 1e-6)
    k_i = (k_i * lp["ln_ik"].astype(jnp.float32)
           + lp["ln_ik_bias"].astype(jnp.float32)).astype(h.dtype)
    w = _proj(lp, "wi_w", h).astype(jnp.float32) * (hi * di) ** -0.5
    return (apply_partial_rope(q_i, positions, inv_freq),
            apply_partial_rope(k_i[:, :, None], positions, inv_freq)[:, :, 0],
            w)


def _scale(cfg: DeepseekV3Config) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _mla_block(cfg: DeepseekV3Config, lp: Params, x, positions, inv_freq,
               attn_fn, lora_idx=None):
    """Pre-norm latent attention sub-block. Returns (x_out, c, k_rope): the
    two values the token leaves in the pool.

    With `cfg.q_lora_rank` the queries come through a latent of their own,
    `W_qb RMSNorm(W_qa h)`. A latent's scale (`q_lora_scale`,
    `kv_lora_scale`) multiplies its norm's weight, in float32 inside the
    norm: what follows the norm is linear in it, so the scaled latent — and
    for keys and values the latent the POOL keeps — is rounded once. The
    rope key is not behind the norm and is not scaled. Under `cfg.mla_nope`
    neither it nor the queries' last numbers are rotated. Under
    `cfg.index_topk` the query carries the indexer's queries and weights
    (IndexedQuery) and the rope cell the token's index key behind its 128
    lanes: the third value a token leaves, in the same row. Under
    `cfg.attn_gate` a head's output is multiplied by sigmoid(h W_g)_h."""
    b, t, _ = x.shape
    heads, c_dim = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim

    def latent_norm(v, name, scale):
        w = lp[name]
        if scale != 1.0:
            w = w.astype(jnp.float32) * scale
        return rms_norm(v, w, cfg.rms_eps)

    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    if cfg.q_lora_rank:
        c_q = latent_norm(_proj(lp, "wq_a", h, lora_idx), "ln_q",
                          cfg.q_lora_scale)
        q = _proj_heads(lp, "wq_b", c_q, lora_idx)
    else:
        q = _proj_heads(lp, "wq", h, lora_idx)
    q = q.reshape(b, t, heads, dn + dr)
    kv = _proj(lp, "wkv_a", h, lora_idx)  # [B, T, C + Dr]
    c = latent_norm(kv[..., :c_dim], "ln_kv", cfg.kv_lora_scale)

    def rotated(v):
        return v if cfg.mla_nope else apply_rope(v, positions, inv_freq,
                                                 cfg.rope_interleave)

    k_rope = rotated(kv[:, :, None, c_dim:])[:, :, 0]  # one head for all
    k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, ROPE_CELL - dr)))
    query = LatentQuery(q[..., :dn], rotated(q[..., dn:]), lp["wk_b"],
                        lp["wv_b"])
    if cfg.index_topk:
        index_q, index_k, index_w = _index_block(cfg, lp, h, c_q, positions,
                                                 inv_freq)
        query = IndexedQuery(*query, index_q, index_w)
        k_rope = jnp.concatenate([k_rope, index_k], axis=-1)
    out = attn_fn(query, c, k_rope)  # [B, T, H, Dv]
    if cfg.attn_gate:
        gate = jax.nn.sigmoid(_proj(lp, "w_gate", h).astype(jnp.float32))
        out = (out.astype(jnp.float32) * gate[..., None]).astype(out.dtype)
    return x + _proj(lp, "wo", out.reshape(b, t, -1), lora_idx), c, k_rope


def absorb(q: LatentQuery):
    """The queries carried into the latent space, q_abs = W^K_h q_nope
    [B, T, H, C]."""
    return jnp.einsum("bthd,hcd->bthc", q.nope, q.wk_b,
                      preferred_element_type=jnp.float32
                      ).astype(q.nope.dtype)


def carry_out(mix, q: LatentQuery):
    """A mix of latents carried back out through W^V_h [B, T, H, Dv]."""
    return jnp.einsum("bthc,hcd->bthd", mix, q.wv_b,
                      preferred_element_type=jnp.float32
                      ).astype(mix.dtype)


def _attention(cfg: DeepseekV3Config) -> Attention:
    dr = cfg.qk_rope_head_dim

    def prefill(q: LatentQuery, c, k_rope, prompt_lens):
        def up(w):  # per-head keys or values of the chunk, from its latent
            return jnp.einsum("btc,hcd->bthd", c, w,
                              preferred_element_type=jnp.float32
                              ).astype(c.dtype)

        shared = jnp.broadcast_to(k_rope[:, :, None, :dr],
                                  (*q.rope.shape[:3], dr))
        return latent_attention_prefill(
            jnp.concatenate([q.nope, q.rope], axis=-1),
            jnp.concatenate([up(q.wk_b), shared], axis=-1),
            up(q.wv_b), prompt_lens)

    def extend(q: LatentQuery, c_pool, r_pool, layer, tables, positions,
               chunk_lens):
        del chunk_lens  # padding queries attend like real ones; discarded
        return carry_out(paged_latent_extend(
            absorb(q), q.rope, c_pool, r_pool, layer, tables, positions,
            scale=_scale(cfg)), q)

    def decode(q: LatentQuery, c_pool, r_pool, layer, tables, kv_lens, *,
               window=None, work=None):
        return carry_out(paged_latent_decode(
            absorb(q), q.rope, c_pool, r_pool, layer, tables, kv_lens,
            scale=_scale(cfg), window=window, work=work), q)

    return Attention(_mla_block, prefill, extend, decode, paged_decode_work)


# ---------------------------------------------------------------------------
# Feed-forward and the stack
# ---------------------------------------------------------------------------

def _moe_mlp_fn(cfg: DeepseekV3Config, live=None):
    """llama's `mlp_fn(lp, h, token_valid, lora_idx)` for an expert layer:
    the routed experts by DeepSeek-V3's rule plus the shared experts, and
    as aux the layer's ops/moe.Routing. `live` ([B] bool) stands in for
    `token_valid` where the body has none (decode): rows that do not decode
    are routed nowhere and counted in no expert's load."""

    def fn(lp, h, token_valid, lora_idx=None):
        b, t, m = h.shape
        flat = h.reshape(b * t, m)
        if token_valid is None and live is not None:
            token_valid = jnp.broadcast_to(live[:, None], (b, t))
        logits = jnp.einsum("sm,mx->sx", flat, lp["router"],
                            preferred_element_type=jnp.float32)
        routed, routing = moe.moe_routed(
            flat, logits, lp["we_gate"], lp["we_up"], lp["we_down"],
            layer=lp["layer"],  # the stacks whole: llama.LayerGroup.whole
            route=lambda r: moe.sigmoid_bias_routing(
                r, lp["router_bias"], cfg.experts_per_token,
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob),
            token_valid=(None if token_valid is None
                         else token_valid.reshape(b * t)),
        )
        shared = (jax.nn.silu(flat @ lp["ws_gate"]) * (flat @ lp["ws_up"])
                  ) @ lp["ws_down"]
        return (routed + shared).reshape(b, t, m), routing

    return fn


def _groups(cfg: DeepseekV3Config, live=None) -> list[LayerGroup]:
    groups = [
        LayerGroup(_ATTN + _DENSE_MLP, _default_mlp_fn, cfg.first_k_dense,
                   DENSE),
        LayerGroup(_ATTN + _MOE_MLP, _moe_mlp_fn(cfg, live),
                   cfg.num_moe_layers, whole=("we_gate", "we_up", "we_down")),
    ]
    return [g for g in groups if g.count > 0]


EXPERT_LOAD_COUNTERS = {
    "experts_touched": StepCounter("sum", "moe_experts_touched_total"),
    "expert_assignments": StepCounter("sum", "moe_expert_assignments_total"),
    "expert_load_max": StepCounter("max", "moe_expert_load_max"),
    "expert_load_hist": StepCounter("sum", "moe_expert_load_hist"),
}


def step_counters(cfg: DeepseekV3Config) -> dict[str, tuple]:
    """The counters a paged serving call returns, by name and shape (all
    int32; how each is reduced and exported: EXPERT_LOAD_COUNTERS)."""
    if cfg.num_moe_layers <= 0:
        return {}
    return {"experts_touched": (), "expert_assignments": (),
            "expert_load_max": (),
            "expert_load_hist": (cfg.num_moe_layers, len(LOAD_BUCKETS) + 1)}


def _moe_aux(cfg: DeepseekV3Config, aux) -> moe.Routing | None:
    """The expert group's Routing stacked over its layers [Lm, ...]."""
    if cfg.num_moe_layers <= 0:
        return None
    stacked = aux[-1]
    if isinstance(stacked, list):  # decode: one per unrolled layer
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *stacked)
    return stacked


def _extra(cfg: DeepseekV3Config, aux, shape, routing: bool):
    """What follows (logits, cache_k, cache_v) in a call's result: the
    step's counters, or under `routing` what the routers decided."""
    r = _moe_aux(cfg, aux)
    if r is None:
        return ()
    if routing:
        lm = cfg.num_moe_layers
        chosen = r.chosen.reshape(lm, *shape, -1)
        return ((chosen, r.scores.reshape(lm, *shape, -1),
                 jnp.ones(chosen.shape, bool)),)
    load = r.load  # [Lm, X]
    bounds = jnp.asarray(LOAD_BUCKETS, jnp.int32)
    bucket = jnp.sum(load[..., None] > bounds, axis=-1)  # [Lm, X]
    hist = jnp.sum(
        bucket[..., None] == jnp.arange(len(LOAD_BUCKETS) + 1),
        axis=1, dtype=jnp.int32)
    return ({
        "experts_touched": jnp.sum(load > 0, dtype=jnp.int32),
        "expert_assignments": jnp.sum(load, dtype=jnp.int32),
        "expert_load_max": jnp.max(load).astype(jnp.int32),
        "expert_load_hist": hist,
    },)


_STATIC = ("cfg", "mesh", "routing")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: DeepseekV3Config, input_ids, prompt_lens,
                       block_tables, cache_k, cache_v,
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
def prefill_extend_pages(params, cfg: DeepseekV3Config, input_ids, chunk_lens,
                         start_pos, block_tables, cache_k, cache_v,
                         mesh: Mesh | None = None, lora_idx=None,
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
def verify_step_paged(params, cfg: DeepseekV3Config, input_ids, chunk_lens,
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
def decode_step_paged(params, cfg: DeepseekV3Config, input_ids, seq_lens,
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


# Refused: int8 weights (the quant names cover some of its projections and
# not wkv_a, wk_b, wv_b or the shared experts), LoRA pools, an int8 pool.
FAMILY = Family(
    name="deepseek_v3", config_class=DeepseekV3Config,
    model_types=("deepseek_v3",),
    mechanism_keys=("kv_lora_rank", "n_routed_experts", "n_shared_experts",
                    "first_k_dense_replace", "moe_intermediate_size"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    pool="latent page pool",
    int8_weights=False, int8_kv=False, lora=False,
    counters=EXPERT_LOAD_COUNTERS, step_counters=step_counters,
    paged_keywords=("routing",))
