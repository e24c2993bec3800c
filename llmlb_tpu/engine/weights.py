"""Checkpoint ingestion: HF-format safetensors → sharded device arrays.

TPU-native equivalent of the reference's `poc/nemotron-safetensors-cpp` probe
(SURVEY.md §2.3 item 2): instead of just mmapping and reporting tensors, we map
HF names onto the model pytree, transpose to our [in, out] matmul layout, stack
layers for `lax.scan`, and `jax.device_put` each leaf with its NamedSharding so
every host touches only its shard. A C++ mmap reader (native/) accelerates the
host-side read path; `safetensors.numpy` is the portable fallback.

Loading is STREAMING per tensor: `load_checkpoint` builds one pytree leaf at a
time (stack → cast → optional int8 quantization → device_put → drop the host
copy), so peak host RAM is one stacked tensor plus the device arrays instead
of a full second model-size host copy. With `quantize_weights=True` the big
projection matrices quantize per output channel BEFORE transfer
(llmlb_tpu/quant), so the H2D traffic and the device footprint are the int8
bytes too.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Mapping

import jax
import numpy as np

from llmlb_tpu.models.llama import LlamaConfig, Params, param_shardings
from llmlb_tpu.quant import WEIGHT_QUANT_NAMES, quantize_channelwise

TensorGetter = Callable[[str], np.ndarray]
LeafBuilder = Callable[[TensorGetter], np.ndarray]


def _param_builders(cfg: LlamaConfig) -> dict[str, LeafBuilder]:
    """Per-leaf builder functions (name → fn(get) -> host ndarray) in pytree
    order. Builders are lazy so the streaming loader materializes exactly one
    stacked tensor at a time."""

    def stack(fmt: str, transpose: bool) -> LeafBuilder:
        def build(get: TensorGetter) -> np.ndarray:
            leaves = []
            for i in range(cfg.num_layers):
                w = get(fmt.format(i=i))
                leaves.append(w.T if transpose else w)
            return np.stack(leaves)

        return build

    def single(name: str, transpose: bool = False) -> LeafBuilder:
        def build(get: TensorGetter) -> np.ndarray:
            w = get(name)
            return w.T if transpose else w

        return build

    from llmlb_tpu.models.deepseek_v3 import DeepseekV3Config
    from llmlb_tpu.models.dots3_note import Dots3NoteConfig

    if isinstance(cfg, Dots3NoteConfig):
        raise NotImplementedError(
            "a dots3_note checkpoint is not loaded: no modelling file is at "
            "hand to map its tensor names from (the indexer's, the gates', "
            "the window layers' projections), and a guess would serve wrong "
            "logits; a dots3_note engine runs seeded weights "
            "(docs/sparse-attention.md)")
    if isinstance(cfg, DeepseekV3Config):
        return _deepseek_v3_param_builders(cfg, single)
    if getattr(cfg, "num_experts", 0) > 1:
        return _moe_param_builders(cfg, stack, single)

    builders: dict[str, LeafBuilder] = {
        "embed": single("model.embed_tokens.weight"),
        "wq": stack("model.layers.{i}.self_attn.q_proj.weight", True),
        "wk": stack("model.layers.{i}.self_attn.k_proj.weight", True),
        "wv": stack("model.layers.{i}.self_attn.v_proj.weight", True),
        "wo": stack("model.layers.{i}.self_attn.o_proj.weight", True),
        "wg": stack("model.layers.{i}.mlp.gate_proj.weight", True),
        "wu": stack("model.layers.{i}.mlp.up_proj.weight", True),
        "wd": stack("model.layers.{i}.mlp.down_proj.weight", True),
        "ln_attn": stack("model.layers.{i}.input_layernorm.weight", False),
        "ln_mlp": stack("model.layers.{i}.post_attention_layernorm.weight",
                        False),
        "ln_final": single("model.norm.weight"),
    }
    if cfg.attention_bias:
        builders["bq"] = stack("model.layers.{i}.self_attn.q_proj.bias", False)
        builders["bk"] = stack("model.layers.{i}.self_attn.k_proj.bias", False)
        builders["bv"] = stack("model.layers.{i}.self_attn.v_proj.bias", False)
    if not cfg.tie_word_embeddings:
        builders["lm_head"] = single("lm_head.weight", True)
    return builders


def _moe_param_builders(cfg, stack, single) -> dict[str, LeafBuilder]:
    """Mixtral layout: block_sparse_moe.gate + experts.{e}.w1/w3/w2 per layer
    (w1 = gate/silu branch, w3 = up, w2 = down in HF's naming)."""

    def stack_experts(wname: str, transpose: bool) -> LeafBuilder:
        def build(get: TensorGetter) -> np.ndarray:
            layers = []
            for i in range(cfg.num_layers):
                experts = []
                for e in range(cfg.num_experts):
                    w = get(
                        f"model.layers.{i}.block_sparse_moe.experts.{e}"
                        f".{wname}.weight"
                    )
                    experts.append(w.T if transpose else w)
                layers.append(np.stack(experts))
            return np.stack(layers)  # [L, E_experts, ...]

        return build

    builders: dict[str, LeafBuilder] = {
        "embed": single("model.embed_tokens.weight"),
        "wq": stack("model.layers.{i}.self_attn.q_proj.weight", True),
        "wk": stack("model.layers.{i}.self_attn.k_proj.weight", True),
        "wv": stack("model.layers.{i}.self_attn.v_proj.weight", True),
        "wo": stack("model.layers.{i}.self_attn.o_proj.weight", True),
        "router": stack("model.layers.{i}.block_sparse_moe.gate.weight", True),
        "we_gate": stack_experts("w1", True),
        "we_up": stack_experts("w3", True),
        "we_down": stack_experts("w2", True),
        "ln_attn": stack("model.layers.{i}.input_layernorm.weight", False),
        "ln_mlp": stack("model.layers.{i}.post_attention_layernorm.weight",
                        False),
        "ln_final": single("model.norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        builders["lm_head"] = single("lm_head.weight", True)
    return builders


def _deepseek_v3_param_builders(cfg, single) -> dict[str, LeafBuilder]:
    """`modeling_deepseek_v3` names onto the two-group pytree of
    models/deepseek_v3.py: layers [0, first_k_dense) are the `dense_` group,
    the rest the expert group. `kv_b_proj` [H*(Dn+Dv), C] is split per head
    into `wk_b` [H, C, Dn] and `wv_b` [H, C, Dv] here, once."""
    from llmlb_tpu.models.deepseek_v3 import DENSE

    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    at = "model.layers.{i}.self_attn."

    def stack(layers: range, fmt: str, shape=lambda w: w.T) -> LeafBuilder:
        def build(get: TensorGetter) -> np.ndarray:
            return np.stack([shape(get(fmt.format(i=i))) for i in layers])

        return build

    def kv_b(lo: int, hi: int):
        def shape(w):  # [H*(Dn+Dv), C] -> [H, C, hi-lo]
            per_head = w.reshape(cfg.num_heads, dn + dv, -1)
            return per_head[:, lo:hi, :].transpose(0, 2, 1)

        return shape

    def experts(layers: range, proj: str) -> LeafBuilder:
        def build(get: TensorGetter) -> np.ndarray:
            return np.stack([np.stack([
                get(f"model.layers.{i}.mlp.experts.{e}.{proj}.weight").T
                for e in range(cfg.num_experts)]) for i in layers])

        return build

    def same(w):
        return w

    builders: dict[str, LeafBuilder] = {
        "embed": single("model.embed_tokens.weight"),
        "ln_final": single("model.norm.weight"),
    }
    dense = range(cfg.first_k_dense)
    routed = range(cfg.first_k_dense, cfg.num_layers)
    for prefix, layers in ((DENSE, dense), ("", routed)):
        if not layers:
            continue
        builders.update({
            prefix + "wq": stack(layers, at + "q_proj.weight"),
            prefix + "wkv_a": stack(layers, at + "kv_a_proj_with_mqa.weight"),
            prefix + "ln_kv": stack(layers, at + "kv_a_layernorm.weight", same),
            prefix + "wk_b": stack(layers, at + "kv_b_proj.weight", kv_b(0, dn)),
            prefix + "wv_b": stack(layers, at + "kv_b_proj.weight",
                                   kv_b(dn, dn + dv)),
            prefix + "wo": stack(layers, at + "o_proj.weight"),
            prefix + "ln_attn": stack(
                layers, "model.layers.{i}.input_layernorm.weight", same),
            prefix + "ln_mlp": stack(
                layers, "model.layers.{i}.post_attention_layernorm.weight",
                same),
        })
    mlp = "model.layers.{i}.mlp."
    if dense:
        builders.update({
            DENSE + "wg": stack(dense, mlp + "gate_proj.weight"),
            DENSE + "wu": stack(dense, mlp + "up_proj.weight"),
            DENSE + "wd": stack(dense, mlp + "down_proj.weight"),
        })
    if routed:
        builders.update({
            "router": stack(routed, mlp + "gate.weight"),
            "router_bias": stack(
                routed, mlp + "gate.e_score_correction_bias", same),
            "we_gate": experts(routed, "gate_proj"),
            "we_up": experts(routed, "up_proj"),
            "we_down": experts(routed, "down_proj"),
            "ws_gate": stack(routed, mlp + "shared_experts.gate_proj.weight"),
            "ws_up": stack(routed, mlp + "shared_experts.up_proj.weight"),
            "ws_down": stack(routed, mlp + "shared_experts.down_proj.weight"),
        })
    if not cfg.tie_word_embeddings:
        builders["lm_head"] = single("lm_head.weight", True)
    return builders


# Leaves served in float32 whatever the serving dtype (the published
# checkpoints keep them so): the router's choice bias.
_FLOAT32_LEAVES = ("router_bias",)


def convert_hf_tensors(cfg: LlamaConfig, get: TensorGetter) -> Params:
    """Map HF llama/qwen2/mistral/mixtral tensor names to our stacked pytree
    (all leaves materialized at once — tests and tooling; the serving load
    path streams per tensor via load_checkpoint instead)."""
    return {name: build(get) for name, build in _param_builders(cfg).items()}


def _open_shard(path: str):
    """Prefer the C++ mmap reader (native/safetensors_reader.cpp); fall back
    to the safetensors package. Both expose keys()/get_tensor()."""
    try:
        from llmlb_tpu.native import NativeSafetensors

        return NativeSafetensors(path)
    except Exception:
        from safetensors import safe_open

        return safe_open(path, framework="numpy")


def _close_shard(shard) -> None:
    """Release a reader from _open_shard (NativeSafetensors or safe_open)."""
    if hasattr(shard, "close"):
        shard.close()
    elif hasattr(shard, "__exit__"):
        shard.__exit__(None, None, None)


def _safetensors_getter(model_dir: str) -> TensorGetter:
    """Build a name→tensor getter over all *.safetensors shards in a directory."""
    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    name_to_file: dict[str, str] = {}
    if os.path.exists(index_path):
        with open(index_path) as f:
            name_to_file = json.load(f)["weight_map"]
    else:
        for fname in sorted(os.listdir(model_dir)):
            if fname.endswith(".safetensors"):
                shard = _open_shard(os.path.join(model_dir, fname))
                try:
                    for name in shard.keys():
                        name_to_file[name] = fname
                finally:
                    _close_shard(shard)  # native readers mmap the whole file
    handles: dict[str, object] = {}

    def get(name: str) -> np.ndarray:
        fname = name_to_file[name]
        if fname not in handles:
            handles[fname] = _open_shard(os.path.join(model_dir, fname))
        return handles[fname].get_tensor(name)

    return get


def load_config(model_dir: str, dtype=None) -> LlamaConfig:
    """The configuration object of a model directory's `config.json`
    (models.config_from_hf: the class by `model_type`, a stated mechanism
    the class would ignore refused by name)."""
    from llmlb_tpu.models import config_from_hf

    with open(os.path.join(model_dir, "config.json")) as f:
        return config_from_hf(json.load(f), dtype)


def load_checkpoint(model_dir: str, cfg: LlamaConfig, mesh=None,
                    quantize_weights: bool = False) -> Params:
    """Load a HF checkpoint directory into (optionally sharded) device arrays.

    Streams one pytree leaf at a time: build the stacked host tensor, cast to
    the serving dtype, quantize it (per-output-channel int8 + f32 scales,
    when requested and the leaf is a projection matrix), `device_put`, then
    drop the host copy before touching the next leaf. Peak host RAM is one
    stacked tensor — not a second full model copy."""
    from llmlb_tpu.models import family_for

    get = _safetensors_getter(model_dir)
    shardings = (family_for(cfg).param_shardings(cfg, mesh)
                 if mesh is not None else None)

    def put(name: str, host: np.ndarray):
        if shardings is None:
            return jax.numpy.asarray(host)
        return jax.device_put(host, shardings[name])

    dtype = np.dtype(cfg.dtype)
    params: Params = {}
    for name, build in _param_builders(cfg).items():
        host = build(get)
        if quantize_weights and name in WEIGHT_QUANT_NAMES:
            q, scale = quantize_channelwise(np.asarray(host))
            params[name] = put(name, q)
            params[f"{name}_scale"] = put(f"{name}_scale", scale)
        elif name in _FLOAT32_LEAVES:
            params[name] = put(name, np.asarray(host, dtype=np.float32))
        else:
            params[name] = put(name, np.asarray(host, dtype=dtype))
        del host  # streaming contract: one host leaf live at a time
    return params
