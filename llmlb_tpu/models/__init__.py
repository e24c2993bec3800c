"""Model families served by the tpu:// engine, over one serving contract.

`family_for(cfg)` resolves a config's function module (init_params /
param_shardings / init_kv_pages / kv_pages_shardings / prefill_into_pages /
prefill_extend_pages / verify_step_paged / decode_step_paged over the paged
KV pool), so the engine is family-agnostic. What a family IS — its
configuration class and `model_type`s, its pool, how it decodes, what it
refuses, its counters — is its module's `FAMILY` record (models/family.py);
FAMILIES registers the modules and everything else here is derived.

`config_from_hf(hf, dtype)` picks the configuration class of a published
`config.json` by its `model_type` and refuses a config that carries a key the
chosen class would ignore at the cost of wrong output.
"""

from llmlb_tpu.models import (afmoe, deepseek_v3, dots3_note, granite_hybrid,
                              kimi_linear, lfm2_moe, llama, longcat_flash, mimo_v2,
                              mixtral, nemotron_h, olmo_hybrid, sdar_moe)

# Adding a family is its module and its line here. The order decides nothing
# but the order /api/health and /metrics list the families' counters in.
FAMILIES = (llama, mixtral, deepseek_v3, sdar_moe, longcat_flash, nemotron_h,
            mimo_v2, olmo_hybrid, afmoe, granite_hybrid, lfm2_moe,
            kimi_linear, dots3_note)

# Every counter some family computes: an engine of any family exports them
# all, zero where its own computes none.
STEP_COUNTERS = {name: counter for m in FAMILIES
                 for name, counter in m.FAMILY.counters.items()}
_BY_CONFIG_CLASS = {m.FAMILY.config_class: m for m in FAMILIES}
_BY_MODEL_TYPE = {t: m for m in FAMILIES for t in m.FAMILY.model_types}

# Keys that change the function a model computes: what some family reads
# (FAMILY.mechanism_keys), then the mechanisms nobody computes. A config
# carrying one for a class that does not read it would be served as another
# model without a word.
_ABSENT = (None, False, 0, 1, [], {})
_NOBODY_COMPUTES = ("attn_logit_softcapping", "final_logit_softcapping")
_STATED_KEYS = tuple(dict.fromkeys(
    [k for m in FAMILIES for k in m.FAMILY.mechanism_keys])) + _NOBODY_COMPUTES


def config_from_hf(hf: dict, dtype=None):
    """The configuration object of a published `config.json`: the class by
    `model_type` (a type no family names is read as a Llama-shaped dense
    decoder, as it always was; a Mixtral-shaped config of such a type by
    its `num_local_experts`), refused where the class would ignore a
    mechanism the config states."""
    model_type = hf.get("model_type", "llama")
    module = _BY_MODEL_TYPE.get(model_type, llama)
    if module is llama and max(hf.get("num_local_experts") or 0,
                               hf.get("num_experts") or 0) > 1:
        module = mixtral
    family = module.FAMILY
    # every `linear_*` key says something of a linear-attention layer,
    # whether or not a family reads that one yet
    stated = _STATED_KEYS + tuple(k for k in hf if k.startswith("linear_")
                                  and k not in _STATED_KEYS)
    for key in stated:
        value = hf.get(key)
        if key in family.mechanism_keys or (value in _ABSENT
                                            and value is not True):
            continue  # (True == 1, and a flag stated true is stated)
        if key == "layer_types" and set(value) == {"full_attention"}:
            continue  # stated, and every layer the kind the class computes
        if (key == "moe_intermediate_size"
                and value == hf.get("intermediate_size")):
            continue  # the width the class reads is the experts' own
        if key == "sliding_window" and hf.get("use_sliding_window") is False:
            continue  # stated and switched off (Qwen2)
        raise ValueError(
            f"config.json ({model_type!r}) carries {key}={value!r}, which "
            f"models/{family.name}.py does not compute: it would be served "
            "as another model. Add the mechanism or the model_type (the "
            "family's FAMILY record: llmlb_tpu/models/family.py)")
    kwargs = {} if dtype is None else {"dtype": dtype}
    return family.config_class.from_hf_config(hf, **kwargs)


def family_for(cfg):
    """Resolve the serving-function module for a model config: the family
    of the most derived registered class the config is an instance of."""
    for cls in type(cfg).__mro__:
        if cls in _BY_CONFIG_CLASS:
            return _BY_CONFIG_CLASS[cls]
    raise TypeError(f"no model family for config type {type(cfg).__name__}")


__all__ = ["FAMILIES", "STEP_COUNTERS", "config_from_hf", "family_for"]
