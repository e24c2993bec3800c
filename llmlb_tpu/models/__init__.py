"""Model families served by the tpu:// engine.

`family_for(cfg)` resolves the function module (init_params / param_shardings /
init_kv_pages / kv_pages_shardings / prefill_into_pages / prefill_extend_pages
/ verify_step_paged / decode_step_paged — one shared serving contract over the
paged KV pool) for a config, so the engine scheduler is family-agnostic: dense
Llama-class (llama.py) and sparse-MoE Mixtral-class (mixtral.py) plug into the
same continuous-batching loop; DeepSeek-V3-class (deepseek_v3.py: latent
attention, sigmoid-routed experts behind leading dense layers) brings its own
attention and layer stack to the same bodies, and SDAR-MoE-class
(sdar_moe.py: QK-normed GQA under a block-causal mask, generation by
diffusion over blocks) declares a block length the scheduler decodes by;
Nemotron-H-class (nemotron_h.py: a stack of state-space, attention and
expert layers, one mixer a layer) keeps a recurrent state per slot beside
the page pool; LongCat-Flash-class (longcat_flash.py: double layers whose
mixture is a shortcut across two latent attentions, zero-compute experts)
leaves a residual branch in one layer for a later one to add.

`config_from_hf(hf, dtype)` picks the configuration class of a published
`config.json` by its `model_type` and refuses a config that carries a key the
chosen class would ignore at the cost of wrong output.
"""

from llmlb_tpu.models.llama import (
    LlamaConfig,
    init_params,
    param_shardings,
    kv_pages_shardings,
    init_kv_pages,
    prefill_into_pages,
    prefill_extend_pages,
    verify_step_paged,
    decode_step_paged,
)


# model_type -> the module whose configuration class reads it. A type that
# is not here is read as a Llama-shaped dense decoder, as it always was —
# under the guard below.
MODEL_TYPES = {
    "llama": "llama", "mistral": "llama", "qwen2": "llama",
    "mixtral": "mixtral",
    "deepseek_v3": "deepseek_v3",
    "sdar_moe": "sdar_moe",
    "nemotron_h": "nemotron_h",
    "longcat_flash": "longcat_flash",
}
_CONFIG_CLASSES = {"llama": "LlamaConfig", "mixtral": "MixtralConfig",
                   "deepseek_v3": "DeepseekV3Config",
                   "sdar_moe": "SdarMoeConfig",
                   "nemotron_h": "NemotronHConfig",
                   "longcat_flash": "LongcatFlashConfig"}

# Keys that change the function a model computes, and the classes that read
# them. A config carrying one for a class that does not read it would be
# served as another model without a word.
_ABSENT = (None, False, 0, 1, [], {})
_MECHANISM_KEYS = {
    "kv_lora_rank": ("deepseek_v3", "longcat_flash"),
    "q_lora_rank": ("longcat_flash",),
    "zero_expert_num": ("longcat_flash",),
    "n_routed_experts": ("deepseek_v3", "nemotron_h", "longcat_flash"),
    "n_shared_experts": ("deepseek_v3", "nemotron_h"),
    "first_k_dense_replace": ("deepseek_v3",),
    "num_local_experts": ("mixtral",),
    "num_experts": ("mixtral", "sdar_moe"),
    "moe_intermediate_size": ("deepseek_v3", "sdar_moe", "nemotron_h"),
    "hybrid_override_pattern": ("nemotron_h",),
    "mamba_num_heads": ("nemotron_h",),
    "ssm_state_size": ("nemotron_h",),
    "expert_parallel": ("nemotron_h", "longcat_flash"),
    "sliding_window": (),
    "attn_logit_softcapping": (),
    "final_logit_softcapping": (),
    "partial_rotary_factor": (),
}


def config_from_hf(hf: dict, dtype=None):
    """The configuration object of a published `config.json`: the class by
    `model_type` (MODEL_TYPES; a Mixtral-shaped config of another type by its
    `num_local_experts`), refused where the class would ignore a mechanism
    the config states."""
    import importlib

    model_type = hf.get("model_type", "llama")
    family = MODEL_TYPES.get(model_type, "llama")
    if family == "llama" and max(hf.get("num_local_experts") or 0,
                                 hf.get("num_experts") or 0) > 1:
        family = "mixtral"
    for key, readers in _MECHANISM_KEYS.items():
        value = hf.get(key)
        if family in readers or value in _ABSENT:
            continue
        if (key == "moe_intermediate_size"
                and value == hf.get("intermediate_size")):
            continue  # the width the class reads is the experts' own
        if key == "sliding_window" and hf.get("use_sliding_window") is False:
            continue  # stated and switched off (Qwen2)
        raise ValueError(
            f"config.json ({model_type!r}) carries {key}={value!r}, which "
            f"models/{family}.py does not compute: it would be served as "
            "another model. Add the mechanism or the model_type "
            "(llmlb_tpu/models/__init__.py MODEL_TYPES)")
    module = importlib.import_module(f"llmlb_tpu.models.{family}")
    cls = _CONFIG_CLASSES[family]
    kwargs = {} if dtype is None else {"dtype": dtype}
    return getattr(module, cls).from_hf_config(hf, **kwargs)


def family_for(cfg):
    """Resolve the serving-function module for a model config."""
    from llmlb_tpu.models import (
        deepseek_v3,
        llama,
        longcat_flash,
        mixtral,
        nemotron_h,
        sdar_moe,
    )

    if isinstance(cfg, longcat_flash.LongcatFlashConfig):
        return longcat_flash  # a DeepseekV3Config too: asked first
    if isinstance(cfg, deepseek_v3.DeepseekV3Config):
        return deepseek_v3
    if isinstance(cfg, nemotron_h.NemotronHConfig):
        return nemotron_h
    if isinstance(cfg, sdar_moe.SdarMoeConfig):
        return sdar_moe
    if isinstance(cfg, mixtral.MixtralConfig):
        return mixtral
    if isinstance(cfg, LlamaConfig):
        return llama
    raise TypeError(f"no model family for config type {type(cfg).__name__}")


__all__ = [
    "LlamaConfig",
    "config_from_hf",
    "family_for",
    "init_params",
    "param_shardings",
    "kv_pages_shardings",
    "init_kv_pages",
    "prefill_into_pages",
    "prefill_extend_pages",
    "verify_step_paged",
    "decode_step_paged",
]
