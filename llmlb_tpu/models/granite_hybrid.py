"""Granite-4.0-H-class dense hybrid decoder (`model_type` `granitemoehybrid`
with no routed experts: Granite-4.0-H-Micro): a stack whose layers mix their
tokens by a state-space (Mamba-2) mixer or by attention, in the order the
config's `layer_types` spells (`mamba`, `attention`), EVERY layer followed by
a dense SwiGLU feed-forward, and the family's four multipliers.
docs/granite-hybrid.md has the equations.

Same serving contract and the same three shared bodies as models/llama.py;
what differs is handed to them:

- `x0 = embed[ids] * embedding_multiplier`; a layer is `x + r mix(norm(x))`,
  then `x + r mlp(norm(x))` with `r = residual_multiplier` on what each
  sub-layer GIVES; `logits = norm(x) embed^T / logits_scaling` (the head is
  the embedding table: `tie_word_embeddings`). The first and the last live
  in the shared bodies (LlamaConfig.embedding_multiplier, logits_scaling);
  `r` in this family's mixers and feed-forward. No factor is folded into a
  stored weight: a checkpoint's matrices are its own.
- The walk follows `layer_types` in RUNS of like layers, a llama.LayerGroup a
  run (Granite-4.0-H-Micro: 5 M, A, 9 M, A, 9 M, A, 9 M, A, 4 M), its
  parameters the run's OWN stacks (`r0_*` [5, ...], `r1_*` [1, ...], ..;
  the feed-forward's matrices ride with their layer), its place in the
  pool its kind's next rows.
- `mamba`: models/nemotron_h.py's `ssm_mixer`, the same function at other
  numbers: ONE group of B and C for all the heads (`mamba_n_groups` 1, so
  the gated norm is over all of `d_inner`), the state per SLOT beside the
  page pool (llama.StatePool): `cache_k.state` [n_M, slots, H, P, N]
  float32, `cache_v.state` [n_M, slots, 3, conv channels].
- `attention`: grouped-query attention WITHOUT rotary embedding
  (`position_embedding_type` "nope") whose softmax takes `q k
  attention_multiplier` and not `q k / sqrt(d)`: q is scaled by
  `attention_multiplier sqrt(d)` in front of the shared attention ops (at
  the published 1/64 over heads of 64 that is 1/8, a power of two: exact in
  bf16), over the page pool of the attention layers alone, its heads of
  64 stored two to a row (`cache_k.pages` [n_A, P, PS, K / 2, 128],
  `pool_pack`: a pool 64 lanes wide is stored 128 wide, half of it
  padding, docs/kv-cache.md).

Not served, each refused by name: the family's mixture siblings
(`num_local_experts` > 0), rotary or learned positions, a bias on the
state-space projections; speculative decoding (`verify_step_paged` is
absent: a rejected draft would need the state rolled back), an int8 page
pool, KV on the wire, int8 weights and LoRA pools; the engine refuses the
prefix cache, the offload tier and the split role for a family with state
per slot (scheduler.py).

The paged serving functions return one value after (logits, cache_k,
cache_v), as models/olmo_hybrid.py's do: the step's counters.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
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
    _decode_paged_impl,
    _mlp,
    _prefill_extend_paged_impl,
    _prefill_impl,
    _proj,
    _qkv,
    shard_rules_for,
)
from llmlb_tpu.models.nemotron_h import (
    ATTN,
    SSM,
    mixer_shapes,
    seeded_vector,
    ssm_mixer,
)
from llmlb_tpu.ops.attention import (
    lane_pack,
    pack_kv,
    pack_queries,
    unpack_heads,
)
from llmlb_tpu.ops.norms import rms_norm
from llmlb_tpu.parallel.sharding import logical_to_sharding

Params = dict[str, Any]
F32 = jnp.float32

MAMBA, ATTENTION = "mamba", "attention"
# the keys of a config.json that say something of the state-space layers
# and that no other family reads: the seven that shape them and the
# convolution's flag (`mamba_proj_bias` is nemotron_h's key too, read and
# refused by both classes themselves)
MAMBA_KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_n_groups",
              "mamba_d_state", "mamba_d_conv", "mamba_expand",
              "mamba_chunk_size", "mamba_conv_bias")
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(LlamaConfig):
    layer_types: tuple[str, ...] = (MAMBA, ATTENTION)
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 256
    # the seeded initialisation's range of dt (the family's defaults; the
    # published config carries no such key)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def pool_pack(self) -> int:
        """KV heads side by side in a row of the page pool
        (ops/attention.lane_pack): 2 at the published 8 heads of 64, whose
        pool [n_A, P, PS, 8, 64] the chip would store 128 lanes wide, half
        of them padding."""
        return lane_pack(self.num_kv_heads, self.head_dim_)

    @classmethod
    def from_hf_config(cls, hf: dict, dtype=jnp.bfloat16
                       ) -> "GraniteHybridConfig":
        """Build from a published `config.json`. What this family does not
        compute is refused by name."""
        kinds = tuple(hf["layer_types"])
        inner = hf["mamba_n_heads"] * hf["mamba_d_head"]
        limit = hf.get("time_step_limit") or (0.0, None)
        unsupported = {
            "layer_types": (bool(set(kinds) - {MAMBA, ATTENTION})
                            or len(kinds) != hf["num_hidden_layers"]),
            "num_local_experts": (hf.get("num_local_experts") or 0) > 0,
            "num_experts_per_tok": (hf.get("num_experts_per_tok") or 0) > 0,
            "position_embedding_type":
                hf.get("position_embedding_type", "nope") != "nope",
            "mamba_expand": inner != hf["mamba_expand"] * hf["hidden_size"],
            # the published gated norm runs over all of d_inner, the shared
            # mixer's over a group's channels: the same thing at one group
            "mamba_n_groups": hf["mamba_n_groups"] != 1,
            "mamba_proj_bias": bool(hf.get("mamba_proj_bias")),
            "mamba_conv_bias": not hf.get("mamba_conv_bias", True),
            "attention_bias": bool(hf.get("attention_bias")),
            "hidden_act": hf.get("hidden_act", "silu") != "silu",
            "normalization_function":
                hf.get("normalization_function", "rmsnorm") != "rmsnorm",
            "time_step_limit": (float(limit[0] or 0.0) != 0.0
                                or limit[1] not in (None, math.inf)),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"granitemoehybrid config key(s) {bad} = "
                f"{[hf.get(k) for k in bad]} are not supported by "
                "models/granite_hybrid.py; refusing to serve wrong logits")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["shared_intermediate_size"],
            num_layers=len(kinds),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf.get("rope_theta", 10000.0)),  # read by no layer
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            dtype=dtype,
            embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
            logits_scaling=float(hf.get("logits_scaling", 1.0)),
            layer_types=kinds,
            ssm_heads=hf["mamba_n_heads"],
            ssm_head_dim=hf["mamba_d_head"],
            ssm_groups=hf["mamba_n_groups"],
            ssm_state=hf["mamba_d_state"],
            conv_kernel=hf["mamba_d_conv"],
            chunk_size=hf.get("mamba_chunk_size", 256),
            attention_multiplier=float(hf.get(
                "attention_multiplier",
                (hf.get("head_dim") or hf["hidden_size"]
                 // hf["num_attention_heads"]) ** -0.5)),
            residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        )


# ---------------------------------------------------------------------------
# Params: one stack a RUN of like layers, the feed-forward with its layer
# ---------------------------------------------------------------------------

_MLP = ("ln_mlp", "wg", "wu", "wd")
_NAMES = {MAMBA: SSM + _MLP, ATTENTION: ATTN + _MLP}


def runs(layer_types) -> list[tuple[str, str, int]]:
    """(prefix of its keys in the pytree, kind, layers) of every run of like
    layers, in order: `r0_` .. (Granite-4.0-H-Micro: nine). A run is a
    stack of its own because prefill and extend scan a group's WHOLE
    stacks: rows [first, first + count) of a longer stack would be sliced
    out — copied — in front of every call (4.1 GB of temporaries a prefill
    at the published sizes, scripts/program_temporaries.py --chunk)."""
    return [(f"r{i}_", kind, len(list(run)))
            for i, (kind, run) in enumerate(itertools.groupby(layer_types))]


def _layer_shapes(cfg: GraniteHybridConfig) -> dict[str, tuple[tuple, int]]:
    """name -> (shape of one layer's leaf, fan-in; 0 = its own rule)."""
    e, f = cfg.hidden_size, cfg.intermediate_size
    return {**mixer_shapes(cfg), "ln_mlp": ((e,), 0),
            "wg": ((e, f), e), "wu": ((e, f), e), "wd": ((f, e), f)}


def _leaves(cfg: GraniteHybridConfig) -> list[stacks.Leaf]:
    """Every stacked leaf `layer_types` calls for: a stack a run."""
    return stacks.stack_leaves(_layer_shapes(cfg), [
        (prefix, _NAMES[kind], count)
        for prefix, kind, count in runs(cfg.layer_types)])


def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> Params:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark) by the family's published initialisation, as
    nemotron_h.init_params: matrices normal x fan_in^-0.5; `A_log` the log
    of a uniform draw in [1, 16]; `dt_bias` the inverse softplus of a
    log-uniform draw in [time_step_min, time_step_max] floored at
    time_step_floor; `D` and the norms ones; the convolution uniform within
    +-kernel^-0.5. The head is the embedding table when the config ties
    them."""
    return stacks.init_params(cfg, key, _leaves(cfg), seeded_vector)


def param_logical_axes(cfg: GraniteHybridConfig) -> dict[str, tuple]:
    """Attention and the feed-forward shard as llama's; the state-space
    projections replicate (nemotron_h.param_logical_axes says why)."""
    layer = {**stacks.GQA_AXES, **stacks.MLP_AXES}
    return stacks.param_logical_axes(cfg, _leaves(cfg), layer)


def param_shardings(cfg: GraniteHybridConfig, mesh: Mesh, rules=None):
    return stacks.param_shardings(cfg, mesh, rules, param_logical_axes(cfg))


# ---------------------------------------------------------------------------
# The pool: pages of the attention layers, state of the state-space layers
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: GraniteHybridConfig, num_pages: int, page_size: int,
                  dtype=None, quantized: bool = False, num_slots: int = 1):
    """The (cache_k, cache_v) pair of the serving contract, each a
    llama.StatePool, as nemotron_h.init_kv_pages: K (V) pages of the
    attention layers [n_A, P, PS, K / f, f D] — `f` = `pool_pack` KV heads
    side by side in a row, so that no lane of a tile is padding — and per
    slot the recurrent state [n_M, slots, H, P, N] float32 (the rows of xBC
    the convolution looks back on [n_M, slots, kernel - 1, channels])."""
    FAMILY.refuse(int8_kv=quantized)
    dtype = dtype or cfg.dtype
    f = cfg.pool_pack
    pages = (cfg.layers_of(ATTENTION), num_pages, page_size,
             cfg.num_kv_heads // f, f * cfg.head_dim_)
    n_m = cfg.layers_of(MAMBA)
    return (
        StatePool(jnp.zeros(pages, dtype), jnp.zeros(
            (n_m, num_slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            F32)),
        StatePool(jnp.zeros(pages, dtype), jnp.zeros(
            (n_m, num_slots, cfg.conv_kernel - 1, cfg.conv_dim), dtype)),
    )


def kv_pages_shardings(cfg: GraniteHybridConfig, mesh: Mesh, rules=None,
                       quantized: bool = False):
    """Pages as llama's, their packed rows (`pool_pack`) split over tp where
    they divide; the state replicates (a slot's rows are one sequence's,
    and its heads are not split: param_logical_axes)."""
    FAMILY.refuse(int8_kv=quantized)
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    rows = cfg.num_kv_heads // cfg.pool_pack
    pages = logical_to_sharding(
        mesh, rules, "layers", None, "seq",
        "kv_heads" if rows % mesh.shape["tp"] == 0 else None, "head_dim")
    state = logical_to_sharding(mesh, rules, "layers", None, None, None, None)
    conv = logical_to_sharding(mesh, rules, "layers", None, None, None)
    return (StatePool(pages, state), StatePool(pages, conv))


def kv_pool_layers(cfg: GraniteHybridConfig) -> int:
    """Layers of the page pool: the attention layers alone."""
    return cfg.layers_of(ATTENTION)


def kv_token_layer_bytes(cfg: GraniteHybridConfig,
                         quantized: bool = False) -> int:
    """HBM bytes one token leaves in one layer of the PAGE pool (K and V of
    every kv head); the state-space layers leave nothing per token."""
    FAMILY.refuse(int8_kv=quantized)
    return (2 * cfg.num_kv_heads * cfg.head_dim_
            * jnp.dtype(cfg.dtype).itemsize)


def state_slot_bytes(cfg: GraniteHybridConfig) -> int:
    """HBM bytes one slot holds beside its pages: the recurrent state and
    the convolution's rows of every state-space layer."""
    per_layer = (cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
                 + (cfg.conv_kernel - 1) * cfg.conv_dim
                 * jnp.dtype(cfg.dtype).itemsize)
    return cfg.layers_of(MAMBA) * per_layer


def kv_wire_cell(cfg: GraniteHybridConfig) -> None:
    """Nothing ships: the recurrent state has no KVSH wire form, and pages
    without it are a tenth of a sequence. A handoff, resume or park replays
    its tokens instead."""
    return None


# ---------------------------------------------------------------------------
# The mixes and the feed-forward
# ---------------------------------------------------------------------------

def _attn_block(cfg: GraniteHybridConfig, lp: Params, x, positions, inv_freq,
                attn_fn, lora_idx=None):
    """llama._attn_block without the rotary embedding, the scores scaled by
    `attention_multiplier` (q carries what that is over the shared ops'
    d^-0.5) and the output by `residual_multiplier`. `attn_fn` takes, and
    (x_out, k, v) gives, the keys and values as the pool's packed rows hold
    them: the bodies write what either hands them."""
    del positions, inv_freq
    b, t, _ = x.shape
    h = rms_norm(x, lp["ln_attn"], cfg.rms_eps)
    q, k, v = _qkv(cfg, lp, h, lora_idx)
    q = q * (cfg.attention_multiplier * cfg.head_dim_**0.5)
    k, v = pack_kv(k, cfg.pool_pack), pack_kv(v, cfg.pool_pack)
    out = _proj(lp, "wo", attn_fn(q, k, v).reshape(b, t, -1), lora_idx)
    return x + out * cfg.residual_multiplier, k, v


def _attention(cfg: GraniteHybridConfig):
    """llama.GQA_ATTENTION over a pool whose rows hold `pool_pack` KV heads:
    a fresh prompt attends over its keys and values head by head again; the
    paged ops are given the queries in their own KV head's lanes and give
    back those lanes."""
    k, f = cfg.num_kv_heads, cfg.pool_pack

    def prefill(q, keys, values, prompt_lens):
        heads = (*keys.shape[:2], k, cfg.head_dim_)
        return GQA_ATTENTION.prefill(q, keys.reshape(heads),
                                     values.reshape(heads), prompt_lens)

    def over_packed_rows(paged):
        def attend(q, *args, **kw):
            return unpack_heads(paged(pack_queries(q, k, f), *args, **kw),
                                k, f)

        return attend if f > 1 else paged

    return GQA_ATTENTION._replace(
        block=_attn_block, prefill=prefill,
        extend=over_packed_rows(GQA_ATTENTION.extend),
        decode=over_packed_rows(GQA_ATTENTION.decode))


def _mlp_fn(cfg: GraniteHybridConfig):
    """llama's `mlp_fn`: the dense SwiGLU, times `residual_multiplier`."""

    def fn(lp, h, token_valid, lora_idx=None):
        return _mlp(lp, h, lora_idx) * cfg.residual_multiplier

    return fn


def _groups(cfg: GraniteHybridConfig) -> list[LayerGroup]:
    """A group a RUN of like layers, in `layer_types`' order: the run's own
    stacks whole, its place in its pool its kind's next rows."""
    mixes = {
        MAMBA: dict(attends=False, scope="ssm_layers",
                    mixer=ssm_mixer(cfg, cfg.residual_multiplier)),
        ATTENTION: dict(scope="attention_layer"),
    }
    mlp_fn = _mlp_fn(cfg)
    seen = {MAMBA: 0, ATTENTION: 0}
    groups = []
    for prefix, kind, count in runs(cfg.layer_types):
        groups.append(LayerGroup(_NAMES[kind], mlp_fn, count, prefix,
                                 pool_layer=seen[kind], **mixes[kind]))
        seen[kind] += count
    return groups


def step_counters(cfg: GraniteHybridConfig) -> dict[str, tuple]:
    """The counters a decode step returns, by name and shape (all int32):
    the rows whose state the step advanced (each in every state-space
    layer), and the cells its attentions read (a live row's whole length in
    every attention layer)."""
    return {"state_rows": (), "global_kv_tokens": ()}


def _extra(cfg: GraniteHybridConfig, advanced, kv_lens):
    """What follows (logits, cache_k, cache_v): the step's counters.
    `advanced`: rows whose state moved; `kv_lens` [B]: the cells each row's
    context holds once the call is done, 0 for a row not live."""
    return ({"state_rows": jnp.asarray(advanced, jnp.int32),
             "global_kv_tokens": cfg.layers_of(ATTENTION) * jnp.sum(
                 kv_lens, dtype=jnp.int32)},)


def _chunk_extra(cfg, input_ids, lens, kv_lens):
    """A prefill's or an extend's counters: every row's state moved, `lens`
    tokens a row through the chunked scan in chunks of `chunk_size`."""
    b, t = input_ids.shape
    (counters,) = _extra(cfg, b, kv_lens)
    counters["scan_tokens"] = jnp.sum(lens, dtype=jnp.int32)
    counters["scan_chunks"] = jnp.asarray(b * -(-t // cfg.chunk_size),
                                          jnp.int32)
    return (counters,)


_STATIC = ("cfg", "mesh")


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_into_pages(params, cfg: GraniteHybridConfig, input_ids,
                       prompt_lens, block_tables, cache_k, cache_v,
                       mesh: Mesh | None = None, lora_idx=None,
                       slot_ids=None):
    """Continuous-batching insert path. Same contract as
    llama.prefill_into_pages; `slot_ids` ([B], default row i is slot i) are
    the slots whose state the rows write, from zeros."""
    logits, cache_k, cache_v, _ = _prefill_impl(
        params, cfg, input_ids, prompt_lens, block_tables, cache_k, cache_v,
        lora_idx=lora_idx, groups=_groups(cfg), attention=_attention(cfg),
        slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_chunk_extra(
        cfg, input_ids, prompt_lens, prompt_lens))


@partial(jax.jit, static_argnames=_STATIC,
         donate_argnames=("cache_k", "cache_v"))
def prefill_extend_pages(params, cfg: GraniteHybridConfig, input_ids,
                         chunk_lens, start_pos, block_tables, cache_k,
                         cache_v, mesh: Mesh | None = None, lora_idx=None,
                         slot_ids=None):
    """Chunked-prefill append path. Same contract as
    llama.prefill_extend_pages; the rows' state is read from their slots,
    scanned on from `start_pos` and written back."""
    logits, cache_k, cache_v, _ = _prefill_extend_paged_impl(
        params, cfg, input_ids, chunk_lens, start_pos, block_tables,
        cache_k, cache_v, lora_idx=lora_idx, groups=_groups(cfg),
        attention=_attention(cfg), slot_ids=slot_ids)
    return (logits, cache_k, cache_v, *_chunk_extra(
        cfg, input_ids, chunk_lens, start_pos + chunk_lens))


@partial(jax.jit, static_argnames=_STATIC + ("window",),
         donate_argnames=("cache_k", "cache_v"))
def decode_step_paged(params, cfg: GraniteHybridConfig, input_ids, seq_lens,
                      cache_k, cache_v, block_tables,
                      mesh: Mesh | None = None, window: int | None = None,
                      lora_idx=None, live=None, slot_ids=None):
    """One decode step across all rows. Same contract as
    llama.decode_step_paged; a row that is not `live` keeps its state."""
    logits, cache_k, cache_v, _ = _decode_paged_impl(
        params, cfg, input_ids, seq_lens, cache_k, cache_v, block_tables,
        window=window, lora_idx=lora_idx, live=live, groups=_groups(cfg),
        attention=_attention(cfg), slot_ids=slot_ids)
    kv_lens = seq_lens + 1
    advanced = input_ids.shape[0]
    if live is not None:
        kv_lens = jnp.where(live, kv_lens, 0)
        advanced = jnp.sum(live, dtype=jnp.int32)
    return (logits, cache_k, cache_v, *_extra(cfg, advanced, kv_lens))


# It verifies no draft: a rejected token would leave the state advanced, and
# there is no snapshot to roll back to. `slot_ids`: the rows' slots (default
# row i in slot i); `num_slots`: the slot count of the pool's state.
# `layer_types` is olmo_hybrid's and afmoe's key too, `num_local_experts`
# mixtral's (read here to refuse the family's mixtures by name);
# `num_experts_per_tok` is read likewise and not listed: every mixture's
# config carries it (afmoe's FAMILY says why).
FAMILY = Family(
    name="granite_hybrid", config_class=GraniteHybridConfig,
    model_types=("granitemoehybrid",),
    mechanism_keys=("layer_types", *MAMBA_KEYS, *MULTIPLIERS,
                    "position_embedding_type", "shared_intermediate_size",
                    "num_local_experts"),
    kv_token_layer_bytes=kv_token_layer_bytes, kv_wire_cell=kv_wire_cell,
    kv_pool_layers=kv_pool_layers, state_slot_bytes=state_slot_bytes,
    pool="page pool beside a recurrent state",
    verifies_drafts=False,
    int8_weights=False, int8_kv=False, lora=False,
    counters={
        "state_rows": StepCounter("sum", "ssm_state_rows_total"),
        "global_kv_tokens": StepCounter("sum", "global_kv_tokens_total")},
    step_counters=step_counters, paged_keywords=("slot_ids",),
    keywords_of={"init_kv_pages": ("num_slots",)})
