"""Llama-family decoder (Llama-2/3, Qwen-2/2.5, Mistral) — functional JAX.

TPU-first design decisions:
- Pure functions over a flat param pytree; no Module framework. Everything jits.
- All layers are *stacked* along a leading axis. Prefill/extend iterate them
  with `lax.scan` (one layer compiles once — prefill compile time stays flat
  even for 80-layer configs); decode UNROLLS the loop so each layer updates
  the donated KV pool in place at a static index — scanning the pool
  materialized full-pool copies per layer under the engine's burst scan
  (see _decode_paged_impl).
- Serving-shaped entry points over one KV layout, a global page pool
  addressed through per-row block tables: `prefill_into_pages` (bucketed
  [B, T] prompts), `prefill_extend_pages` (chunked append),
  `verify_step_paged` (speculative verify) and `decode_step_paged` ([B] one
  token per row). All have fully static shapes; raggedness is carried by
  `prompt_lens` / `seq_lens` masks.
- A model is a list of `LayerGroup`s handed to three shared bodies
  (prefill, extend, decode). Two notions are defined there: a layer whose
  halves may be absent or another mixer (a state per slot: `StatePool`),
  and a DEFERRED BRANCH — a residual branch one layer computes and a later
  layer adds, carried by the bodies between the two (`LayerGroup`'s
  docstring; models/longcat_flash.py is its user).
- Sharding is expressed once in `param_shardings` / `kv_pages_shardings` using
  logical axes (parallel/sharding.py) — Megatron-style tp over heads/ffn/vocab,
  dp over the batch axis (the pool replicates over dp).

The reference does no inference in-process (SURVEY.md L0: external runtimes over
HTTP); this model family is the in-tree `tpu://` engine's compute core per the
BASELINE.json north star. HF-format checkpoints load via engine/weights.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmlb_tpu.models import stacks
from llmlb_tpu.models.family import Family
from llmlb_tpu.models.stacks import shard_rules_for
from llmlb_tpu.ops.attention import (
    gqa_attention_prefill,
    paged_attention_decode,
    paged_attention_extend,
    paged_decode_work,
)
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.ops.rope import RopeScaling, apply_rope, rope_frequencies
from llmlb_tpu.parallel.sharding import ShardingRules, logical_to_sharding
from llmlb_tpu.quant import quantize_kv

Params = dict[str, Any]

# Int8-quantized projection weights ride the pytree as `<name>` (int8) +
# `<name>_scale` (f32 per output channel) pairs — llmlb_tpu/quant.
_SCALE = "_scale"

# Multi-LoRA adapter pools (llmlb_tpu/lora, docs/lora.md) ride the pytree as
# `<name>_lora_a` [L, N, IN, R] / `<name>_lora_b` [L, N, R, OUT] pairs —
# N stacked adapter slots over the base projection `<name>`, slot 0 all-zero
# (the no-adapter identity row). Like the quant scales they are companions:
# absent on LoRA-free engines, in which case every branch below compiles the
# original program bit for bit.
_LORA_A = "_lora_a"
_LORA_B = "_lora_b"
# Projections that can carry adapter deltas (attention always; the dense
# SwiGLU MLP optionally — MoE expert FFNs are out of scope, so mixtral
# engines serve attention-only adapters).
LORA_TARGETS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int | None = None  # default hidden_size // num_heads
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    rms_eps: float = 1e-5
    attention_bias: bool = False  # Qwen-2/2.5 use qkv bias
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 8192
    dtype: Any = jnp.bfloat16
    # Granite's scalars on the way in and out (models/granite_hybrid.py):
    # x0 = embed[ids] * embedding_multiplier, logits / logits_scaling. At 1
    # the bodies below trace what they always did.
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16) -> "LlamaConfig":
        """Build from a HF `config.json` dict (llama / qwen2 / mistral archs)."""
        scaling = None
        rs = hf.get("rope_scaling")
        rope_type = rs.get("rope_type", rs.get("type")) if rs else None
        if rope_type not in (None, "default", "llama3"):
            raise NotImplementedError(
                f"rope_scaling type {rope_type!r} is not supported yet; "
                "refusing to load a checkpoint that would generate silently "
                "wrong logits beyond its original context window"
            )
        if rope_type == "llama3":
            scaling = RopeScaling(
                factor=rs.get("factor", 8.0),
                low_freq_factor=rs.get("low_freq_factor", 1.0),
                high_freq_factor=rs.get("high_freq_factor", 4.0),
                original_max_position=rs.get("original_max_position_embeddings", 8192),
            )
        model_type = hf.get("model_type", "llama")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling=scaling,
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            attention_bias=hf.get(
                "attention_bias", model_type in ("qwen2", "qwen2_moe")
            ),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            dtype=dtype,
        )


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests/benches)."""
    d = cfg.head_dim_
    h, k_, e, f, l_ = cfg.num_heads, cfg.num_kv_heads, cfg.hidden_size, (
        cfg.intermediate_size
    ), cfg.num_layers
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
        "wg": w(next(keys), (l_, e, f), e),
        "wu": w(next(keys), (l_, e, f), e),
        "wd": w(next(keys), (l_, f, e), f),
        "ln_attn": jnp.ones((l_, e), cfg.dtype),
        "ln_mlp": jnp.ones((l_, e), cfg.dtype),
        "ln_final": jnp.ones((e,), cfg.dtype),
    }
    if cfg.attention_bias:
        params["bq"] = jnp.zeros((l_, h * d), cfg.dtype)
        params["bk"] = jnp.zeros((l_, k_ * d), cfg.dtype)
        params["bv"] = jnp.zeros((l_, k_ * d), cfg.dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (e, cfg.vocab_size), e)
    return params


def param_logical_axes(cfg: LlamaConfig) -> dict[str, tuple]:
    """Logical sharding axes per param leaf (see parallel/sharding.py)."""
    axes = {
        "embed": ("vocab", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "wg": ("layers", "embed", "ffn"),
        "wu": ("layers", "embed", "ffn"),
        "wd": ("layers", "ffn", "embed"),
        "ln_attn": ("layers", "embed"),
        "ln_mlp": ("layers", "embed"),
        "ln_final": ("embed",),
    }
    if cfg.attention_bias:
        axes["bq"] = ("layers", "heads")
        axes["bk"] = ("layers", "kv_heads")
        axes["bv"] = ("layers", "kv_heads")
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    # Per-output-channel int8 scales (present only on quantized pytrees;
    # extra sharding entries for absent leaves are never consulted). A
    # scale's axes are its weight's with the input (contraction) axis
    # dropped — the scale is per OUTPUT channel.
    for name in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        axes[name + _SCALE] = (axes[name][0], axes[name][2])
    # LoRA adapter pools (present only on LoRA-enabled engines): A keeps the
    # weight's input axis (rank axis replicated — ranks are tiny), B keeps
    # the output axis so the delta lands sharded exactly like the base
    # projection's output under tp.
    for name in LORA_TARGETS:
        w_axes = axes[name]
        axes[name + _LORA_A] = (w_axes[0], None, w_axes[1], None)
        axes[name + _LORA_B] = (w_axes[0], None, None, w_axes[2])
    return axes


def param_shardings(cfg: LlamaConfig, mesh: Mesh, rules: ShardingRules | None = None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# Paged KV cache (global pool: [L, P_pages, page_size, K, D] + block tables)
# ---------------------------------------------------------------------------

def init_kv_pages(
    cfg: LlamaConfig, num_pages: int, page_size: int, dtype=None,
    quantized: bool = False,
):
    """Global page pool shared by every slot: a slot's logical row is the
    concatenation of the pool pages its block table names. Page 0 is the
    engine's trash page (see engine/paging.py).

    `quantized` swaps each pool for an int8 layout: values [L, P, PS, K, D]
    int8 plus per-vector scales [L, P, PS, K] f32 riding the same page ids
    (one absmax scale per written (token, head) K/V vector). The pair
    travels as a {"q", "s"} pytree through the same serving signatures —
    every alloc/free/refcount/block-table decision stays byte-identical."""
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
             cfg.head_dim_)
    if quantized:
        def pool():
            return {"q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:-1], jnp.float32)}

        return pool(), pool()
    dtype = dtype or cfg.dtype
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def kv_pages_shardings(cfg: LlamaConfig, mesh: Mesh,
                       rules: ShardingRules | None = None,
                       quantized: bool = False):
    """Pages are shared across slots, so the page axis cannot shard over dp
    (one sequence's pages must stay co-resident); only the kv-head axis
    splits (tp), pages replicate over dp. Quantized
    pools shard their scale arrays along the same axes minus head_dim."""
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    sharding = logical_to_sharding(
        mesh, rules, "layers", None, "seq", "kv_heads", "head_dim"
    )
    if quantized:
        scale_sh = logical_to_sharding(
            mesh, rules, "layers", None, "seq", "kv_heads"
        )
        pool_sh = {"q": sharding, "s": scale_sh}
        return (pool_sh, dict(pool_sh))
    return (sharding, sharding)


def kv_token_layer_bytes(cfg: LlamaConfig, quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the pool: K and V of
    every kv head (the int8 cell is D·1 plus one f32 scale)."""
    from llmlb_tpu.quant import kv_cell_bytes

    itemsize = jnp.dtype(cfg.dtype).itemsize
    return cfg.num_kv_heads * 2 * kv_cell_bytes(cfg.head_dim_, quantized,
                                                itemsize)


def kv_wire_cell(cfg: LlamaConfig) -> tuple[int, int]:
    """(kv heads, head dim) of the K and V pages a KVSH payload carries
    (engine/kv_transfer.py)."""
    return cfg.num_kv_heads, cfg.head_dim_


class StatePool(NamedTuple):
    """One of the (cache_k, cache_v) pair of a family whose layers do not
    all attend: the page pool of its attention layers, and beside it what
    its other layers carry per SLOT, not per page (a state-space layer's
    recurrent state: models/nemotron_h.py). It travels, is donated and is
    returned as the pages alone do; the bodies below write and read the
    pages through it (_pages, _write_pool) and hand the whole to a group's
    `mixer`."""

    pages: Any  # [L_attn, P, PS, K, D], or a quantized {"q", "s"} pair
    state: jnp.ndarray  # [L_state, slots, ...]


def _pages(pool):
    return pool.pages if isinstance(pool, StatePool) else pool


def kv_pool_values(pool):
    """The value array of a KV page pool (the int8 member of a quantized
    {"q","s"} pair, or the pool itself when bf16)."""
    pool = _pages(pool)
    return pool["q"] if isinstance(pool, dict) else pool


def _write_pool(pool, layer, page, off, kv):
    """Scatter K/V rows into cells [layer, page, off] of the stacked pool,
    in place: `layer` is a static index (decode's unrolled loop) or a
    run-time scalar (the layer scan of prefill and extend), `page` and
    `off` arrays of the rows' shape. Quantized pools take the int8 values
    plus the per-vector scales at the same indices — quantize-on-write."""
    if isinstance(pool, StatePool):
        return pool._replace(
            pages=_write_pool(pool.pages, layer, page, off, kv))
    if isinstance(pool, dict):
        q, s = quantize_kv(kv)
        return {"q": pool["q"].at[layer, page, off].set(q),
                "s": pool["s"].at[layer, page, off].set(s)}
    return pool.at[layer, page, off].set(kv.astype(pool.dtype))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_stacked_names(cfg: LlamaConfig) -> list[str]:
    names = ["wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln_attn", "ln_mlp"]
    if cfg.attention_bias:
        names += ["bq", "bk", "bv"]
    return names


def _with_scales(params: Params, names: list[str]) -> list[str]:
    """Extend a stacked-name list with the companions the pytree carries:
    `<name>_scale` (int8 quant) and `<name>_lora_a`/`<name>_lora_b` (LoRA
    adapter pools), so every per-layer slice sees them. On a plain pytree
    this is the identity — same names, same jit cache keys, bit-identical
    programs."""
    out = list(names)
    for n in names:
        for suffix in (_SCALE, _LORA_A, _LORA_B):
            if n + suffix in params:
                out.append(n + suffix)
    return out


def _proj(lp: Params, name: str, x: jnp.ndarray,
          lora_idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """`x @ W` with on-the-fly int8 dequant when W is quantized: the int8
    -> bf16 convert fuses into the einsum's operand read (HBM moves int8
    bytes), accumulation is fp32 (`preferred_element_type`), and the
    per-output-channel scale applies to the OUTPUT — exact, because the
    scale is constant along the contraction axis. Unquantized weights take
    the original matmul untouched.

    With `lora_idx` ([B] int32 adapter pool rows) and this projection's
    adapter pools in the layer slice, each row's rank-R LoRA delta is added
    to the OUTPUT (ops/lora.py bgmv) — the int8 dequant path above is
    untouched, and row 0 (the all-zero identity adapter) adds exactly 0.0,
    keeping adapter-free rows bit-identical."""
    w = lp[name]
    scale = lp.get(name + _SCALE)
    if scale is None:
        if w.dtype == jnp.int8:
            raise TypeError(
                f"param {name!r} is int8 but its {name}{_SCALE} companion "
                "is missing from the layer slice"
            )
        y = x @ w
    else:
        y32 = jnp.einsum("...i,io->...o", x, w.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        y = (y32 * scale).astype(x.dtype)
    if lora_idx is not None and name + _LORA_A in lp:
        from llmlb_tpu.ops.lora import lora_delta

        delta = lora_delta(x, lp[name + _LORA_A], lp[name + _LORA_B],
                           lora_idx)
        y = y + delta.astype(y.dtype)
    return y


def _proj_heads(lp: Params, name: str, x: jnp.ndarray,
                lora_idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """`_proj` for a projection whose caller splits the result into heads
    (`wq`, `wk`, `wv`, a latent's `wq_b`): the same values, behind a barrier
    that keeps the product a plain `[S, E] x [E, H*D]`.

    Without it the chip's compiler folds the reshape to `[S, H, D]` into the
    product, the product becomes a convolution over a weight `[H, D, E]`
    whose minor dimension is the contracted one, and the layer's slice of
    the `[L, E, H*D]` stack is read, transposed and written for it in front
    of every product, every decode step: twice the weight's bytes moved, and
    a third pass to read them (Mistral-7B's widths: 2.0 ms of a 13.2 ms
    step for what takes 1.1; PERF.md section 6, PR 47). It happens at heads
    of 128, 192 and 64 alike. Every other projection has no split behind it
    and is one fusion of norm, slice in place and product; behind the
    barrier these are too. `tests/test_decode_program_structure.py` holds
    the compiled programs to it."""
    return lax.optimization_barrier(_proj(lp, name, x, lora_idx))


def _qkv(cfg: LlamaConfig, lp: Params, x: jnp.ndarray, lora_idx=None):
    b, t, _ = x.shape
    d = cfg.head_dim_
    q = _proj_heads(lp, "wq", x, lora_idx)
    k = _proj_heads(lp, "wk", x, lora_idx)
    v = _proj_heads(lp, "wv", x, lora_idx)
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    return (
        q.reshape(b, t, cfg.num_heads, d),
        k.reshape(b, t, cfg.num_kv_heads, d),
        v.reshape(b, t, cfg.num_kv_heads, d),
    )


def _mlp(lp: Params, x: jnp.ndarray, lora_idx=None) -> jnp.ndarray:
    return _proj(
        lp, "wd",
        jax.nn.silu(_proj(lp, "wg", x, lora_idx))
        * _proj(lp, "wu", x, lora_idx),
        lora_idx,
    )


def _attn_block(cfg: LlamaConfig, lp: Params, x: jnp.ndarray, positions,
                inv_freq, attn_fn, lora_idx=None):
    """Shared pre-norm attention sub-block (every serving path uses this one
    skeleton: norm → qkv → rope → attn_fn → wo residual). `attn_fn(q, k, v)`
    supplies the attention flavor (dense prefill / cache decode / ring) and may
    capture caches via closure. Returns (x_out, roped_k, roped_v)."""
    b, t, _ = x.shape
    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    q, k, v = _qkv(cfg, lp, h, lora_idx)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    attn = attn_fn(q, k, v)
    return x + _proj(lp, "wo", attn.reshape(b, t, -1), lora_idx), k, v


def _embed(cfg: LlamaConfig, params: Params, input_ids) -> jnp.ndarray:
    x = params["embed"][input_ids]
    scale = cfg.embedding_multiplier
    return x if scale == 1.0 else x * scale


def _unembed(cfg: LlamaConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["ln_final"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    logits = jnp.einsum(
        "be,ev->bv", x, head, preferred_element_type=jnp.float32
    )
    scale = cfg.logits_scaling
    return logits if scale == 1.0 else logits / scale


def _default_mlp_fn(lp: Params, h: jnp.ndarray, token_valid,
                    lora_idx=None) -> jnp.ndarray:
    return _mlp(lp, h, lora_idx)


class Attention(NamedTuple):
    """A family's attention, as the shared bodies below take it (they take
    the feed-forward as `mlp_fn` the same way). The bodies never look inside
    `q`, nor at what the two cached values are: `block` hands `attn_fn(q, k,
    v)` whatever its own three ops expect, and returns the two values a
    token leaves in the page pool (keys and values here; the latent and the
    rotated shared key in models/deepseek_v3.py).

    block(cfg, lp, x, positions, inv_freq, attn_fn, lora_idx)
        -> (x + attention, k_cached, v_cached)
    prefill(q, k, v, prompt_lens)                       fresh prompt, no pool
    extend(q, pool_k, pool_v, layer, tables, positions, chunk_lens)
    decode(q, pool_k, pool_v, layer, tables, kv_lens, window=, work=)
        both: the stacked pool and the layer to attend over, never a slice
    decode_work(pool_k, pool_v, tables, kv_lens, window) the decode kernel's grid
    """

    block: Callable
    prefill: Callable
    extend: Callable
    decode: Callable
    decode_work: Callable


class LayerGroup(NamedTuple):
    """`count` consecutive layers of one kind: their stacked parameters
    (`names`, under `prefix + name` in the pytree where two groups have
    parameters of one name), what mixes their tokens and their
    feed-forward. A model is a list of groups in layer order (Llama and
    Mixtral: one; a model with leading dense layers before its expert
    layers: two; a stack whose kinds alternate layer by layer: a group a
    layer, all groups of a kind reading one stack at their `start`).
    Prefill and extend scan each group's parameters with the page pool as
    the carry (_scan_groups), decode unrolls them all.

    A layer is `x + mix(norm(x))`, then `x + mlp_fn(norm(x))` — under
    `post_norm` `x + norm(mlp_fn(x))`, the block that norms what a sub-layer
    gives and not what it takes (models/olmo_hybrid.py, whose mixes do the
    same themselves; `out_norm` names a second norm for a block that norms
    both) — and either half may be absent: `attends` says that the mix is the model's
    `Attention` over the page pool; `mixer(lp, x, cache_k, cache_v, layer,
    rows: StateRows) -> (x + mix, cache_k, cache_v)` is another mix, with a
    state of its own in the pool (StatePool); `mlp_fn` None is a layer
    without a feed-forward.

    THE DEFERRED BRANCH. A residual branch may cross layers: `branch(lp, h,
    token_valid, lora_idx) -> (value, aux)` is computed from the SAME normed
    input `h` as the group's feed-forward and is NOT added there. The bodies
    carry it — beside `x` and the pools in the scan's carry of prefill and
    extend, in a variable of decode's unrolled loop — and the next layer
    whose group says `joins` adds it to `x` after its own feed-forward, so
    no mix or feed-forward in between has seen it (a shortcut-connected
    mixture of experts: models/longcat_flash.py). The group's aux is then
    the branch's. A model none of whose groups has a branch carries nothing
    and compiles to the program it had. `scope`, where set, names the
    group's layers in a device trace (`jax.named_scope`); the branch runs
    under `deferred_branch` inside it."""

    names: tuple
    mlp_fn: Callable | None
    count: int
    prefix: str = ""  # of the group's keys in the pytree; a layer sees none
    # Of `names`, those a layer is handed WHOLE ([count, ...]) beside its
    # own index in the group, `lp["layer"]`, instead of its slice: operands
    # of a kernel, which takes whole buffers — a slice of the stack would be
    # copied on every call, as a slice of the page pool was (PR 25).
    whole: tuple = ()
    start: int = 0  # the group's first layer in its stacks [start + count, ...]
    # The group's first layer in the POOL it writes (the page pool's layer
    # axis, or its mixer's state); None: its place in the whole stack.
    pool_layer: int | None = None
    attends: bool = True
    mixer: Callable | None = None
    branch: Callable | None = None  # computed here, added by a later layer
    joins: bool = False  # adds the branch an earlier layer left
    scope: str = ""
    post_norm: bool = False  # `ln_mlp` norms the feed-forward's OUTPUT
    # A sub-layer normed on BOTH sides (a sandwich: models/afmoe.py): the
    # name of the group's parameter that norms what the feed-forward GIVES,
    # `ln_mlp` still norming what it takes.
    out_norm: str = ""


class StateRows(NamedTuple):
    """What a group's `mixer` is told about the rows of a call. Prefill:
    `slots` and `lens` (a fresh sequence: whatever the slot held is void).
    Extend: those and `start_pos` (0 is a fresh sequence too). Decode: one
    token a row (`lens` None), at position `start_pos`, `live` the rows to
    advance (None: all)."""

    slots: jnp.ndarray | None  # [B] the rows' slots; None: row i is slot i
    lens: jnp.ndarray | None = None  # [B] valid tokens of the chunk
    start_pos: jnp.ndarray | None = None  # [B] tokens before the chunk
    live: jnp.ndarray | None = None  # [B] bool


GQA_ATTENTION = Attention(_attn_block, gqa_attention_prefill,
                          paged_attention_extend, paged_attention_decode,
                          paged_decode_work)


def _groups_for(cfg, stacked_names, mlp_fn, groups):
    if groups is not None:
        return groups
    return [LayerGroup(tuple(stacked_names or _layer_stacked_names(cfg)),
                       mlp_fn, cfg.num_layers)]


def _group_params(params: Params, group: LayerGroup) -> tuple[Params, Params]:
    """The stacks the group reads, with their companions (_with_scales),
    under the names a layer reads them by: (those a layer gets its slice
    of, those it gets whole beside its own index in them, `lp["layer"]`).
    The group's layers are [start, start + count) of each."""
    keys = _with_scales(params, [group.prefix + n for n in group.names])
    named = {k[len(group.prefix):]: params[k] for k in keys}
    whole = {n: w for n, w in named.items()
             if any(n == base or n.startswith(base + "_")
                    for base in group.whole)}
    return {n: w for n, w in named.items() if n not in whole}, whole


def _feed_forward(cfg, group: LayerGroup, lp: Params, x, deferred,
                  token_valid, lora_idx):
    """x + the group's feed-forward of norm(x) (`post_norm`: x + the norm
    of its feed-forward of x; `out_norm`: x + that norm of its feed-forward
    of norm(x)), the deferred branch as the layer leaves it
    (LayerGroup: computed here, or joined here, or passed on), and what the
    layer reported."""
    if group.mlp_fn is None:
        return x, deferred, None
    h = x if group.post_norm else rms_norm(x, lp["ln_mlp"], cfg.rms_eps)
    out, aux = _mlp_out(group.mlp_fn(lp, h, token_valid, lora_idx))
    if group.post_norm:
        out = rms_norm(out, lp["ln_mlp"], cfg.rms_eps)
    elif group.out_norm:
        out = rms_norm(out, lp[group.out_norm], cfg.rms_eps)
    x = x + out
    if group.branch is not None:
        with jax.named_scope("deferred_branch"):
            deferred, aux = group.branch(lp, h, token_valid, lora_idx)
    elif group.joins:
        x = x + deferred.astype(x.dtype)
    return x, deferred, aux


def _layer_scope(group: LayerGroup):
    return (jax.named_scope(group.scope) if group.scope
            else contextlib.nullcontext())


def _mlp_out(res):
    """A feed-forward gives its output, or (output, aux): what a routed
    layer reports about its routing, stacked per group by the bodies."""
    return res if isinstance(res, tuple) else (res, None)


def _scan_groups(params, groups, x, cache_k, cache_v, layer_of):
    """Prefill's and extend's walk over the stack: each group a `lax.scan`
    over its own stacked parameters (one layer body traced and compiled a
    group), with `(x, cache_k, cache_v, deferred)` as the CARRY (`deferred`:
    the branch a layer left for a later one, LayerGroup; nothing where no
    group has a branch). The pools are never
    a scan's `xs` or `ys` and never sliced by layer: `ys` is a fresh stacked
    buffer, so a pool that went through it was copied whole and each layer
    of it sliced out and written back, on every call, whatever the prompt's
    length (25 ms of a 45 ms prefill step: PERF.md section 6, PR 32). A
    layer writes its cells at `pool.at[layer, page, off]` and an extend
    kernel reads the pool at (layer, page), so the only pool-sized value in
    the program is the donated pool itself, as in decode. A second group
    goes on with the same carry. `layer_of(group)` gives the layer `(carry,
    lp, layer) -> (carry, aux)`, `layer` the layer's index in the pool it
    writes (LayerGroup.pool_layer). Returns (x, cache_k, cache_v, aux per
    group)."""
    deferred = (jnp.zeros_like(x) if any(g.branch for g in groups) else None)
    carry, aux, at = (x, cache_k, cache_v, deferred), [], 0
    for group in groups:
        stacked, whole = _group_params(params, group)
        first, count = group.start, group.count
        stacked = {n: w if (first, count) == (0, w.shape[0])
                   else w[first:first + count] for n, w in stacked.items()}
        own = jnp.arange(count, dtype=jnp.int32)
        if whole:
            stacked["layer"] = first + own if first else own
        body = layer_of(group)

        def layer(carry, layer_in, body=body, whole=whole, group=group):
            lp, idx = layer_in
            with _layer_scope(group):
                return body(carry, {**lp, **whole}, idx)

        pool_first = at if group.pool_layer is None else group.pool_layer
        carry, group_aux = lax.scan(layer, carry, (stacked, pool_first + own))
        aux.append(group_aux)
        at += count
    return (*carry[:3], aux)


def _prefill_impl(params, cfg, input_ids, prompt_lens, block_tables,
                  cache_k, cache_v, *, stacked_names=None,
                  mlp_fn=_default_mlp_fn, lora_idx=None, groups=None,
                  attention=None, slot_ids=None):
    """Shared prefill body for every model family.

    K/V scatter through `block_tables` into the page pool; `mlp_fn(lp, h,
    token_valid, lora_idx)` is the per-family feed-forward (dense SwiGLU
    here, routed experts for mixtral — token_valid marks non-padding tokens
    so MoE routing can ignore padding). `lora_idx` ([B] int32, optional)
    selects each row's adapter pool slot (docs/lora.md). `groups` describes
    a stack of more than one kind of layer and `attention` another
    attention than GQA (LayerGroup, Attention); `slot_ids` ([B]) are the
    rows' slots, for a group whose mixer keeps a state per slot. Returns
    (logits, cache_k, cache_v, aux) with `aux` one entry per group: what
    its feed-forward reported, stacked over the group's layers, or None."""
    b, t = input_ids.shape
    ps = kv_pool_values(cache_k).shape[2]
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    token_valid = positions < prompt_lens[:, None]  # [B, T]
    # position p of row b lands in cell (block_tables[b, p // PS], p % PS)
    page = jnp.take_along_axis(block_tables, positions // ps, axis=1)
    off = positions % ps

    x = _embed(cfg, params, input_ids)  # [B, T, E]
    attention = attention or GQA_ATTENTION
    rows = StateRows(slot_ids, prompt_lens)

    def layer_of(group):
        def layer(carry, lp, layer_idx):
            carry_x, ck, cv, deferred = carry
            if group.mixer is not None:
                carry_x, ck, cv = group.mixer(lp, carry_x, ck, cv, layer_idx,
                                              rows)
            elif group.attends:
                carry_x, k, v = attention.block(
                    cfg, lp, carry_x, positions, inv_freq,
                    lambda q, k, v: attention.prefill(q, k, v, prompt_lens),
                    lora_idx,
                )
                ck = _write_pool(ck, layer_idx, page, off, k)
                cv = _write_pool(cv, layer_idx, page, off, v)
            carry_x, deferred, aux = _feed_forward(
                cfg, group, lp, carry_x, deferred, token_valid, lora_idx)
            return (carry_x, ck, cv, deferred), aux

        return layer

    x, cache_k, cache_v, aux = _scan_groups(
        params, _groups_for(cfg, stacked_names, mlp_fn, groups), x,
        cache_k, cache_v, layer_of)

    last = jnp.maximum(prompt_lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # [B, E]
    logits = _unembed(cfg, params, x_last)
    return logits, cache_k, cache_v, aux


@partial(jax.jit, static_argnames=("cfg", "mesh"),
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,  # [B, T] int32, right-padded
    prompt_lens: jnp.ndarray,  # [B] int32
    block_tables: jnp.ndarray,  # [B, PPN] int32 — target pages per prompt
    cache_k: jnp.ndarray,  # [L, P, PS, K, D] — the engine's live page pool
    cache_v: jnp.ndarray,
    mesh: Mesh | None = None,  # unused; shared family signature
    lora_idx: jnp.ndarray | None = None,  # [B] int32 adapter pool rows
):
    """Prefill B prompts and scatter their KV through the block tables into
    the global page pool — the continuous-batching insert path (new
    requests land in free pages while other rows keep decoding).
    Returns (last_logits [B, V] fp32, cache_k, cache_v).

    HANDOFF CONTRACT (docs/disaggregation.md): this entry point (and the
    extend/CP variants) is handoff-shaped — row i of `last_logits` is the
    FINAL-position logits of prompt i, and every KV row lands at its
    absolute token position. Split mode stages exactly this logits row
    for a later decode-pool adoption (the first token samples from it),
    and the cross-process replay depends on position-exact KV so the
    adopted continuation is token-identical. A family that fused
    prefill+sample, or wrote KV at relative positions, would break both."""
    return _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx,
    )[:3]


def _prefill_extend_paged_impl(params, cfg, input_ids, chunk_lens, start_pos,
                               block_tables, cache_k, cache_v, *,
                               stacked_names=None, mlp_fn=_default_mlp_fn,
                               all_logits=False, window=None, lora_idx=None,
                               groups=None, attention=None, slot_ids=None,
                               logits_from=None, logits_len=None):
    """Shared chunked-prefill body: process a [B, T] chunk of prompt tokens
    whose rows already hold `start_pos` tokens of KV. The chunk's KV scatters
    through the block table into the page pool, in place (_scan_groups), and
    queries attend over the full row (earlier chunks + causal within this
    chunk) via ops.attention.paged_attention_extend at (layer, page). Backs
    long prompts that exceed the
    one-shot prefill buckets, and — with `all_logits=True` — the speculative
    verify step, which needs logits at EVERY chunk position, not just the
    last. Padding tokens (i >= chunk_lens) write garbage past the chunk —
    into this row's own later pages or the trash page (unallocated table
    entries), never another row's cells; those cells sit past the valid
    range (masked by every later attention) and are overwritten in place
    when the sequence grows into them. `window` (static, the engine's
    context BUCKET: the first `window` cells; a model's sliding window is a
    lower bound a row, ops/attention.paged_band_decode's `kv_from`) bounds
    the attention sweep to whole pages covering it, same contract as decode.
    `groups`, `attention`, `slot_ids` and the fourth value returned: as
    _prefill_impl. `logits_from` ([B] int32, with `all_logits`) narrows the
    logits to `logits_len` (static) positions a row from the row's own
    offset into the chunk, [B, logits_len, V]: a block family's pass sends
    two blocks and wants one's logits (programs._build_block_many). The
    default, None, traces what it always did."""
    _, t = input_ids.shape
    ps = kv_pool_values(cache_k).shape[2]
    ppn = block_tables.shape[1]
    capacity = ppn * ps
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    offs = jnp.arange(t, dtype=jnp.int32)[None, :]
    positions = start_pos[:, None] + offs  # [B, T] global positions
    write_pos = jnp.minimum(positions, capacity - 1)
    page = jnp.take_along_axis(block_tables, write_pos // ps, axis=1)
    off = write_pos % ps
    token_valid = offs < chunk_lens[:, None]  # [B, T]
    # attention sweeps only the pages covering `window` (writes keep the full
    # table: write_pos clamps into capacity, not the window)
    read_tables = block_tables
    if window is not None and -(-window // ps) < ppn:
        read_tables = lax.slice_in_dim(
            block_tables, 0, max(1, -(-window // ps)), axis=1
        )

    x = _embed(cfg, params, input_ids)  # [B, T, E]
    attention = attention or GQA_ATTENTION
    rows = StateRows(slot_ids, chunk_lens, start_pos)

    def layer_of(group):
        def layer(carry, lp, layer_idx):
            carry_x, ck, cv, deferred = carry

            def attn_fn(q, k, v):
                nonlocal ck, cv  # pool write precedes attention over the pool
                ck = _write_pool(ck, layer_idx, page, off, k)
                cv = _write_pool(cv, layer_idx, page, off, v)
                return attention.extend(
                    q, _pages(ck), _pages(cv), layer_idx, read_tables,
                    positions, chunk_lens
                )

            if group.mixer is not None:
                carry_x, ck, cv = group.mixer(lp, carry_x, ck, cv, layer_idx,
                                              rows)
            elif group.attends:
                carry_x, _, _ = attention.block(
                    cfg, lp, carry_x, positions, inv_freq, attn_fn, lora_idx)
            carry_x, deferred, aux = _feed_forward(
                cfg, group, lp, carry_x, deferred, token_valid, lora_idx)
            return (carry_x, ck, cv, deferred), aux

        return layer

    x, cache_k, cache_v, aux = _scan_groups(
        params, _groups_for(cfg, stacked_names, mlp_fn, groups), x,
        cache_k, cache_v, layer_of)

    if all_logits:
        b = x.shape[0]
        if logits_from is not None:
            t = logits_len
            at = logits_from[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
            x = jnp.take_along_axis(x, at[:, :, None], axis=1)  # [B, t, E]
        logits = _unembed(cfg, params, x.reshape(b * t, -1)).reshape(b, t, -1)
        return logits, cache_k, cache_v, aux
    last = jnp.maximum(chunk_lens - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # [B, E]
    logits = _unembed(cfg, params, x_last)
    return logits, cache_k, cache_v, aux


@partial(jax.jit, static_argnames=("cfg", "mesh"),
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,  # [B, T] int32, right-padded chunk
    chunk_lens: jnp.ndarray,  # [B] int32 — valid tokens in this chunk
    start_pos: jnp.ndarray,  # [B] int32 — tokens already in the row's pages
    block_tables: jnp.ndarray,  # [B, PPN] int32
    cache_k: jnp.ndarray,  # [L, P, PS, K, D]
    cache_v: jnp.ndarray,
    mesh: Mesh | None = None,  # unused; shared family signature
    lora_idx: jnp.ndarray | None = None,  # [B] int32 adapter pool rows
):
    """Chunked prefill: append a chunk of prompt tokens to rows that already
    hold `start_pos` tokens, attending over everything so far through the
    block tables. Lets the engine serve prompts far beyond the one-shot
    prefill buckets while decode steps interleave between chunks. Returns
    (chunk-last logits [B, V] fp32, caches)."""
    return _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx,
    )[:3]


@partial(jax.jit, static_argnames=("cfg", "mesh", "window"),
         donate_argnames=("cache_k", "cache_v"))
def verify_step_paged(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,  # [B, K+1] int32 — last committed token + drafts
    chunk_lens: jnp.ndarray,  # [B] int32 — 1 + draft count per row
    start_pos: jnp.ndarray,  # [B] int32 — committed tokens in the row's pages
    block_tables: jnp.ndarray,  # [B, PPN] int32
    cache_k: jnp.ndarray,  # [L, P, PS, K, D]
    cache_v: jnp.ndarray,
    mesh: Mesh | None = None,  # unused; shared family signature
    window: int | None = None,  # static context-window bucket
    lora_idx: jnp.ndarray | None = None,  # [B] int32 adapter pool rows
):
    """Speculative verification: one extend-style dispatch scores the last
    committed token plus up to K draft tokens, returning logits at EVERY
    chunk position ([B, K+1, V] fp32) so the scheduler can sample each
    position and accept the longest matching draft prefix. KV for all chunk
    positions is written; rejected-suffix cells become garbage past the
    rolled-back length (standard contract)."""
    return _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, all_logits=True, window=window, lora_idx=lora_idx,
    )[:3]


def _unrolled_layers(params, groups):
    """The stack as decode walks it, UNROLLED: for each group, the group and
    an iterator of (the layer's parameters — its slices by a STATIC index,
    the group's whole stacks and the layer's own index in them beside —, the
    layer's index in the pool it writes). Lazy, so that a layer's slices
    are traced where its body is."""
    at = 0
    for group in groups:
        stacked, whole = _group_params(params, group)
        pool_first = at if group.pool_layer is None else group.pool_layer

        def layers(group=group, stacked=stacked, whole=whole,
                   pool_first=pool_first):
            for in_group in range(group.count):
                own = group.start + in_group  # static indices, unrolled
                lp = {n: w[own] for n, w in stacked.items()}
                lp.update(whole, layer=own)
                yield lp, pool_first + in_group

        yield group, layers()
        at += group.count


def _decode_paged_impl(params, cfg, input_ids, seq_lens, cache_k, cache_v,
                       block_tables, *, stacked_names=None,
                       mlp_fn=_default_mlp_fn, window=None, lora_idx=None,
                       live=None, groups=None, attention=None,
                       slot_ids=None):
    """Shared one-token decode body for every model family.

    The layer loop is UNROLLED (static layer indices; decode programs are
    tiny, so L× code growth is cheap) where prefill and extend scan with
    the pools as the carry (_scan_groups); no body hands a scan the pools
    as inputs and outputs, which copied them. Each layer's one-token KV
    lands at page block_tables[b, pos//PS], offset pos%PS.

    `live` ([B] bool; None = every row) says which rows are decoding. The
    device's `seq_lens` is no guide to that: a freed or never-used row keeps
    counting (every step adds one) until it clamps at capacity, and a
    prefilling row is parked at capacity - 1 on purpose. Such rows still
    WRITE — into their own last cell or the trash page (their block-table
    rows are zeroed on free), never a page another row owns — but attend
    over nothing: their length goes to attention as 0, which the Pallas
    kernel writes as zeros without reading a page. Their logits are finite
    and the caller discards them.

    Attention gets the whole stacked pool [L, P, PS, K, D] and the layer
    index, never `pool[layer_idx]`: the Pallas kernel addresses the pool at
    (layer, page) and reads it in place, so the only pool-sized value in the
    program is the donated pool itself, updated by each layer's [B, K, D]
    scatter. No per-layer slice is materialized
    (tests/test_decode_program_structure.py holds that). The kernel's grid,
    the work-list of live (row, page) pairs, is built here once a step for
    all the layers; under the engine's burst scan it follows `seq_lens`
    across page boundaries. `groups`, `attention` and the fourth value
    returned: as _prefill_impl (a group's aux is a list over its layers);
    `slot_ids` None says that row i is slot i, as the engine's burst has
    it. A group's mixer advances the state of the `live` rows alone."""
    b = input_ids.shape[0]
    ps = kv_pool_values(cache_k).shape[2]
    capacity = block_tables.shape[1] * ps
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    write_pos = jnp.minimum(seq_lens, capacity - 1)
    positions = write_pos[:, None]  # [B, 1]
    batch_idx = jnp.arange(b)
    page = block_tables[batch_idx, write_pos // ps]  # [B]
    off = write_pos % ps
    kv_lens = write_pos + 1  # the row's cells once this step's is written
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
    attention = attention or GQA_ATTENTION
    work = attention.decode_work(_pages(cache_k), _pages(cache_v),
                                 block_tables, kv_lens, window)
    rows = StateRows(slot_ids, start_pos=write_pos, live=live)

    x = _embed(cfg, params, input_ids)[:, None, :]  # [B, 1, E]
    aux, deferred = [], None  # the branch a layer left (LayerGroup)
    for group, layers in _unrolled_layers(
            params, _groups_for(cfg, stacked_names, mlp_fn, groups)):
        group_aux = []
        for lp, layer_idx in layers:

            def attn_fn(q, k, v, layer_idx=layer_idx):
                nonlocal cache_k, cache_v  # write precedes attention
                cache_k = _write_pool(cache_k, layer_idx, page, off, k[:, 0])
                cache_v = _write_pool(cache_v, layer_idx, page, off, v[:, 0])
                return attention.decode(
                    q, _pages(cache_k), _pages(cache_v), layer_idx,
                    block_tables, kv_lens, window=window, work=work,
                )

            with _layer_scope(group):
                if group.mixer is not None:
                    x, cache_k, cache_v = group.mixer(
                        lp, x, cache_k, cache_v, layer_idx, rows)
                elif group.attends:
                    x, _, _ = attention.block(cfg, lp, x, positions,
                                              inv_freq, attn_fn, lora_idx)
                x, deferred, layer_aux = _feed_forward(
                    cfg, group, lp, x, deferred, None, lora_idx)
            group_aux.append(layer_aux)
        aux.append(group_aux)

    logits = _unembed(cfg, params, x[:, 0])
    return logits, cache_k, cache_v, aux


@partial(jax.jit, static_argnames=("cfg", "mesh", "window"),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,  # [B] int32 — previous sampled token per row
    seq_lens: jnp.ndarray,  # [B] int32 — tokens already in the row's pages
    cache_k: jnp.ndarray,  # [L, P, PS, K, D]
    cache_v: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, PPN] int32
    mesh: Mesh | None = None,  # unused; shared family signature
    # static context-window BUCKET (≥ max seq+1), not a sliding window
    # (that is ops/attention.paged_band_decode's `kv_from`)
    window: int | None = None,
    lora_idx: jnp.ndarray | None = None,  # [B] int32 adapter pool rows
    live: jnp.ndarray | None = None,  # [B] bool — rows decoding; None = all
):
    """One decode step across all rows. Returns (logits [B, V] fp32,
    caches). Rows that are not `live` attend over nothing and their logits
    are to be discarded (_decode_paged_impl)."""
    return _decode_paged_impl(params, cfg, input_ids, seq_lens, cache_k,
                              cache_v, block_tables, window=window,
                              lora_idx=lora_idx, live=live)[:3]


def _mixed_paged_impl(params, cfg, input_ids, seq_lens, cache_k, cache_v,
                      block_tables, prompt_ids, prompt_len, prompt_row, *,
                      stacked_names=None, mlp_fn=_default_mlp_fn,
                      window=None, live=None, groups=None, attention=None):
    """Shared MIXED step: the decode rows' one token each AND one arrival's
    whole prompt in ONE pass over the weights (the "chunked prefill" step of
    Sarathi-Serve and vLLM at its simplest: a prompt that fits the step
    whole). A one-prompt prefill of a hundred tokens is bound by the same
    bytes as a decode step, every weight read once, so run alone it costs a
    step; here its tokens ride the step the rows take anyway.

    `prompt_ids` [1, T] right-padded, `prompt_len` [1], and `prompt_row`
    (int32 scalar) the arrival's row of `block_tables` (row i is slot i, as
    the engine's burst has it). The norms, q/k/v, `wo`, the feed-forward and
    the head are ONE product over the B + T tokens; only the attention core
    splits by token — the rows through `attention.decode` over the pool with
    the step's work-list, as _decode_paged_impl; the prompt through
    `attention.prefill` over its own fresh keys and values, which land in
    its pages by the same scatter as the rows' cells, as _prefill_impl
    (padding past `prompt_len` writes into the row's own later cells or the
    trash page, and a later step overwrites it). The arrival's OWN decode
    row has no token to decode: it is parked as a prefilling row is (it
    writes the slot's last cell or the trash page and attends over
    nothing, whatever `seq_lens` holds for it), and its logits are the
    PROMPT's last position's — to the sampler the arrival is a row whose
    step took T tokens in place of one. Unrolled like the decode body, and
    for its reasons: static layer indices, the pool never a scan's `xs` or
    `ys` and never sliced by layer. A group with a `mixer` keeps a state per
    slot and would need its step for the rows and its scan for the prompt:
    that is the family's to bring. Returns (logits [B, V], cache_k,
    cache_v, aux) as _decode_paged_impl."""
    b, t = input_ids.shape[0], prompt_ids.shape[1]
    ps = kv_pool_values(cache_k).shape[2]
    ppn = block_tables.shape[1]
    capacity = ppn * ps
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    batch_idx = jnp.arange(b)
    arrives = batch_idx == prompt_row
    write_pos = jnp.where(arrives, capacity - 1,
                          jnp.minimum(seq_lens, capacity - 1))
    kv_lens = jnp.where(arrives, 0, write_pos + 1)
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
    prompt_pos = jnp.arange(t, dtype=jnp.int32)
    prompt_cell = jnp.minimum(prompt_pos, capacity - 1)
    # the B rows' cells, then the prompt's: one scatter a layer and pool
    page = jnp.concatenate([block_tables[batch_idx, write_pos // ps],
                            block_tables[prompt_row, prompt_cell // ps]])
    off = jnp.concatenate([write_pos % ps, prompt_cell % ps])
    positions = jnp.concatenate([write_pos, prompt_pos])[:, None]  # [B+T, 1]
    token_valid = jnp.concatenate(
        [jnp.ones((b,), jnp.bool_), prompt_pos < prompt_len[0]])[:, None]
    attention = attention or GQA_ATTENTION
    work = attention.decode_work(_pages(cache_k), _pages(cache_v),
                                 block_tables, kv_lens, window)

    x = _embed(cfg, params, jnp.concatenate([input_ids, prompt_ids[0]]))
    x = x[:, None, :]  # [B + T, 1, E]: a token a row, as decode has them
    aux, deferred = [], None
    for group, layers in _unrolled_layers(
            params, _groups_for(cfg, stacked_names, mlp_fn, groups)):
        if group.mixer is not None:
            raise NotImplementedError(
                "a group with a state per slot has no mixed step")
        group_aux = []
        for lp, layer_idx in layers:

            def attn_fn(q, k, v, layer_idx=layer_idx):
                nonlocal cache_k, cache_v  # write precedes attention
                cache_k = _write_pool(cache_k, layer_idx, page, off, k[:, 0])
                cache_v = _write_pool(cache_v, layer_idx, page, off, v[:, 0])
                rows = attention.decode(
                    q[:b], _pages(cache_k), _pages(cache_v), layer_idx,
                    block_tables, kv_lens, window=window, work=work,
                )
                prompt = attention.prefill(
                    q[b:].swapaxes(0, 1), k[b:].swapaxes(0, 1),
                    v[b:].swapaxes(0, 1), prompt_len)
                return jnp.concatenate(
                    [rows, prompt.swapaxes(0, 1).astype(rows.dtype)])

            with _layer_scope(group):
                if group.attends:
                    x, _, _ = attention.block(cfg, lp, x, positions,
                                              inv_freq, attn_fn, None)
                x, deferred, layer_aux = _feed_forward(
                    cfg, group, lp, x, deferred, token_valid, None)
            group_aux.append(layer_aux)
        aux.append(group_aux)

    last = b + jnp.maximum(prompt_len[0] - 1, 0)
    x_rows = jnp.where(arrives[:, None], x[last, 0][None, :], x[:b, 0])
    return _unembed(cfg, params, x_rows), cache_k, cache_v, aux


@partial(jax.jit, static_argnames=("cfg", "mesh", "window"),
         donate_argnames=("cache_k", "cache_v"))
def mixed_step_paged(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,  # [B] int32 — previous sampled token per row
    seq_lens: jnp.ndarray,  # [B] int32 — tokens already in the row's pages
    cache_k: jnp.ndarray,  # [L, P, PS, K, D]
    cache_v: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, PPN] int32
    prompt_ids: jnp.ndarray,  # [1, T] int32, right-padded — the arrival's
    prompt_len: jnp.ndarray,  # [1] int32
    prompt_row: jnp.ndarray,  # int32 scalar — the arrival's row of the tables
    mesh: Mesh | None = None,  # unused; shared family signature
    window: int | None = None,  # static context-window BUCKET, as decode's
    live: jnp.ndarray | None = None,  # [B] bool — rows decoding; None = all
):
    """One decode step across all rows with ONE arrival's prompt prefilled
    in the same pass (_mixed_paged_impl; `Family.mixed_step`). Returns
    (logits [B, V] fp32, caches): row `prompt_row` holds the logits of the
    prompt's last position, every other row its decode step's."""
    return _mixed_paged_impl(params, cfg, input_ids, seq_lens, cache_k,
                             cache_v, block_tables, prompt_ids, prompt_len,
                             prompt_row, window=window, live=live)[:3]


@partial(jax.jit, static_argnames=("cfg",))
def encode(
    params: Params,
    cfg: LlamaConfig,
    input_ids: jnp.ndarray,  # [B, T] int32, right-padded
    prompt_lens: jnp.ndarray,  # [B] int32
) -> jnp.ndarray:
    """Text-embedding forward: full transformer pass (no KV writes), masked
    mean-pool over valid tokens, L2-normalize. Returns [B, E] fp32.

    Serves /v1/embeddings on the tpu:// engine — the reference only proxies
    embeddings to external runtimes (api/openai.rs /v1/embeddings handler);
    here the same decoder weights double as the embedding model, the common
    practice for serving stacks without a dedicated embedder.
    """
    b, t = input_ids.shape
    inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))

    x = params["embed"][input_ids]
    stacked = {n: params[n]
               for n in _with_scales(params, _layer_stacked_names(cfg))}

    def layer(carry_x, lp):
        carry_x, _, _ = _attn_block(
            cfg, lp, carry_x, positions, inv_freq,
            lambda q, k, v: gqa_attention_prefill(q, k, v, prompt_lens),
        )
        h = rms_norm(carry_x, lp["ln_mlp"], cfg.rms_eps)
        carry_x = carry_x + _mlp(lp, h)
        return carry_x, None

    x, _ = lax.scan(layer, x, stacked)
    x = rms_norm(x, params["ln_final"], cfg.rms_eps).astype(jnp.float32)

    valid = (jnp.arange(t, dtype=jnp.int32)[None, :] < prompt_lens[:, None])
    pooled = (x * valid[..., None]).sum(1) / jnp.maximum(
        prompt_lens[:, None].astype(jnp.float32), 1.0
    )
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
    )


def make_context_parallel_prefill(cfg: LlamaConfig, mesh: Mesh):
    """Long-context prefill with the sequence axis sharded over the mesh `sp`
    axis (ring attention — ops/ring_attention.py).

    Per-token ops (embed, norms, QKV/MLP matmuls, rope) shard trivially over
    the token axis under GSPMD; attention is the only op coupling tokens, and
    it runs as a shard_map ring so the full T×T score matrix never exists on
    one chip. Composes with tp over heads when tp divides num_kv_heads (the
    GQA group structure must split along kv-head boundaries); otherwise head
    compute replicates inside the ring — still correct, just not tp-scaled.

    Returns a jitted `fn(params, input_ids [B,T], prompt_lens [B]) ->
    (last_logits [B,V] fp32, k_all [L,B,T,K,D], v_all)`. The caller scatters
    k/v into its page pool (engine insert path) or keeps them
    seq-sharded for context-parallel decode. New TPU-first design — the
    reference has no long-context subsystem (SURVEY.md §5).
    """
    from llmlb_tpu.ops.ring_attention import ring_prefill_attention

    shard_rules_for(cfg, mesh.shape["tp"])  # tp-divisibility validation
    kv_shardable = cfg.num_kv_heads % mesh.shape["tp"] == 0
    head_axis = "tp" if kv_shardable else None
    seq_spec = NamedSharding(mesh, P("dp", "sp", None))

    @jax.jit
    def fn(params: Params, input_ids: jnp.ndarray, prompt_lens: jnp.ndarray):
        b, t = input_ids.shape
        inv_freq = rope_frequencies(cfg.head_dim_, cfg.rope_theta, cfg.rope_scaling)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))

        x = params["embed"][input_ids]  # [B, T, E]
        x = lax.with_sharding_constraint(x, seq_spec)
        stacked = {n: params[n]
                   for n in _with_scales(params, _layer_stacked_names(cfg))}

        def layer(carry_x, lp):
            carry_x, k, v = _attn_block(
                cfg, lp, carry_x, positions, inv_freq,
                lambda q, k, v: ring_prefill_attention(
                    q, k, v, prompt_lens, mesh,
                    head_axis=head_axis, kv_head_axis=head_axis,
                ),
            )
            carry_x = lax.with_sharding_constraint(carry_x, seq_spec)
            h = rms_norm(carry_x, lp["ln_mlp"], cfg.rms_eps)
            carry_x = carry_x + _mlp(lp, h)
            carry_x = lax.with_sharding_constraint(carry_x, seq_spec)
            return carry_x, (k, v)

        x, (k_all, v_all) = lax.scan(layer, x, stacked)

        last = jnp.maximum(prompt_lens - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = _unembed(cfg, params, x_last)
        return logits, k_all.astype(cfg.dtype), v_all.astype(cfg.dtype)

    return fn


FAMILY = Family(
    name="llama", config_class=LlamaConfig,
    model_types=("llama", "mistral", "qwen2"), mechanism_keys=(),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    context_parallel_prefill=True, mixed_step=True)
