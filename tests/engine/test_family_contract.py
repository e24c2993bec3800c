"""The serving contract a model family provides, held name by name.

`models.family_for(cfg)` hands the engine a module of free functions, which
`engine/programs.py` calls positionally, and the module's `FAMILY` record
(`models/family.py`) says what the family is. This holds every family to
llama's signatures up front and every exception to them to the record, so
the next family is added against a test and not against a traceback.
"""

import dataclasses
import inspect
import re

import pytest

from llmlb_tpu import models
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import (
    FAMILIES,
    config_from_hf,
    deepseek_v3,
    family_for,
    llama,
    longcat_flash,
)
from llmlb_tpu.models.family import Family

# one debug configuration per registered module
PRESETS = {"llama": "debug-tiny", "mixtral": "debug-moe-tiny",
           "deepseek_v3": "debug-mla-tiny", "sdar_moe": "debug-sdar-tiny",
           "nemotron_h": "debug-nemotron-h-tiny",
           "longcat_flash": "debug-longcat-tiny",
           "mimo_v2": "debug-mimo-tiny",
           "olmo_hybrid": "debug-olmo-hybrid-tiny",
           "afmoe": "debug-trinity-tiny",
           "granite_hybrid": "debug-granite-hybrid-tiny",
           "lfm2_moe": "debug-lfm2-moe-tiny",
           "kimi_linear": "debug-kimi-linear-tiny",
           "dots3_note": "debug-dots3-note-tiny"}
MODULES = {m.FAMILY.name: m for m in FAMILIES}

PAGED = ("prefill_into_pages", "prefill_extend_pages", "verify_step_paged",
         "decode_step_paged")
CONTRACT = (
    "init_params",
    "param_shardings",
    "init_kv_pages",
    "kv_pages_shardings",
    *PAGED,
    "make_context_parallel_prefill",
    "mixed_step_paged",
)
# the record's field that says a family exports the name; every other name
# of the contract is exported by all
EXPORTED_IF = {"verify_step_paged": "verifies_drafts",
               "make_context_parallel_prefill": "context_parallel_prefill",
               "mixed_step_paged": "mixed_step"}


def _params(fn) -> list[tuple[str, inspect._ParameterKind]]:
    """Names and kinds, in order: what a positional or a keyword call binds
    to. Annotations and defaults may differ (a family names its own config
    class); `inspect.signature` sees through `jax.jit`."""
    return [(p.name, p.kind)
            for p in inspect.signature(fn).parameters.values()]


def test_every_family_module_is_covered():
    assert sorted(MODULES) == sorted(PRESETS)
    for name, module in MODULES.items():
        assert module.__name__ == f"llmlb_tpu.models.{name}"
        assert family_for(get_preset(PRESETS[name])) is module


@pytest.mark.parametrize("name", CONTRACT)
@pytest.mark.parametrize("family", sorted(PRESETS))
def test_family_provides_the_paged_contract(family, name):
    module, record = MODULES[family], MODULES[family].FAMILY
    exported = getattr(record, EXPORTED_IF.get(name, ""), True)
    assert hasattr(module, name) == exported, (
        f"{family} exports {name} and its record says it does not, or the "
        "other way round")
    if not exported:
        return
    want = _params(getattr(llama, name))
    got = _params(getattr(module, name))
    extra = list((record.paged_keywords if name in PAGED else ())
                 + record.keywords_of.get(name, ()))
    assert got[:len(want)] == want and [n for n, _ in got[len(want):]] == extra, (
        f"{family}.{name} takes other parameters than llama.{name} and "
        "the keywords its record states")


@pytest.mark.parametrize("family", sorted(PRESETS))
def test_family_says_what_a_token_leaves_in_the_pool(family):
    """The scheduler's page bytes, gauges and KVSH header ask the record."""
    module, record = MODULES[family], MODULES[family].FAMILY
    cfg = get_preset(PRESETS[family])
    ck, cv = map(llama._pages, module.init_kv_pages(cfg, 3, 8))
    per_token = (ck[0, 0, 0].size + cv[0, 0, 0].size) * ck.dtype.itemsize
    assert record.kv_token_layer_bytes(cfg) == per_token
    assert record.kv_pool_layers(cfg) == ck.shape[0]
    cell = record.kv_wire_cell(cfg)
    assert cell is None or cell == ck.shape[-2:] == cv.shape[-2:]


@pytest.mark.parametrize("family", sorted(PRESETS))
def test_an_added_parameter_has_a_default_that_serves_one_row(family):
    """What a family adds behind llama's parameters is optional: the
    benchmark's check (benchmark/correctness.py) calls every family with
    llama's arguments alone."""
    module, record = MODULES[family], MODULES[family].FAMILY
    for fn in PAGED:
        if hasattr(module, fn):
            params = inspect.signature(getattr(module, fn)).parameters
            assert all(params[n].default in (False, None)
                       for n in record.paged_keywords)
    for fn, names in record.keywords_of.items():
        params = inspect.signature(getattr(module, fn)).parameters
        assert all(params[n].default in (None, 1) for n in names)


@pytest.mark.parametrize("family", sorted(PRESETS))
def test_the_record_points_at_the_modules_own_functions(family):
    """The functions a record names stay module-level functions under their
    names (the benchmark's tests call some of them off the module), and a
    counter a configuration returns is one the record declares."""
    module, record = MODULES[family], MODULES[family].FAMILY
    cfg = get_preset(PRESETS[family])
    for field in ("kv_token_layer_bytes", "kv_wire_cell", "kv_pool_layers",
                  "state_slot_bytes", "block_length", "check_generation",
                  "step_counters"):
        fn = getattr(record, field)
        if fn is not None and hasattr(module, field):
            assert fn is getattr(module, field)
    assert set(record.step_counters(cfg)) <= set(record.counters)
    assert all(c.reduce in ("sum", "max") for c in record.counters.values())
    assert (record.block_length(cfg) > 1) == (
        record.check_generation is not None)


@pytest.mark.parametrize("kwargs,error", [
    (dict(name="x", config_class=llama.LlamaConfig, model_types=(),
          mechanism_keys=(), kv_token_layer_bytes=len, kv_wire_cell=len,
          verifies_draft=False), "verifies_draft"),  # misspelt
    (dict(name="x", config_class=llama.LlamaConfig, model_types=(),
          mechanism_keys=(), kv_wire_cell=len), "kv_token_layer_bytes"),
])
def test_a_record_field_misspelt_or_missing_is_a_type_error(kwargs, error):
    with pytest.raises(TypeError, match=error):
        Family(**kwargs)
    with pytest.raises(dataclasses.FrozenInstanceError):
        llama.FAMILY.lora = False


def test_family_for_takes_the_most_derived_registered_class(monkeypatch):
    """`LongcatFlashConfig` is a `DeepseekV3Config` too: each resolves to
    its own module whatever the registry's order, and so does a config that
    was `dataclasses.replace`d or subclassed by a test."""
    long_cfg, deep_cfg = (get_preset("debug-longcat-tiny"),
                          get_preset("debug-mla-tiny"))
    assert issubclass(longcat_flash.LongcatFlashConfig,
                      deepseek_v3.DeepseekV3Config)
    for order in (FAMILIES, FAMILIES[::-1]):
        monkeypatch.setattr(models, "_BY_CONFIG_CLASS", {
            m.FAMILY.config_class: m for m in order})
        assert family_for(long_cfg) is longcat_flash
        assert family_for(deep_cfg) is deepseek_v3
        assert family_for(dataclasses.replace(long_cfg, num_layers=1)) \
            is longcat_flash
        sub = type("Sub", (type(deep_cfg),), {})
        assert family_for(sub(**dataclasses.asdict(deep_cfg))) is deepseek_v3
    with pytest.raises(TypeError, match="no model family"):
        family_for(object())


# What `models/__init__.py` held by hand until the records said it: kept
# here as the expectation the derived tables are held to.
OLD_MODEL_TYPES = {
    "llama": "llama", "mistral": "llama", "qwen2": "llama",
    "mixtral": "mixtral", "deepseek_v3": "deepseek_v3",
    "sdar_moe": "sdar_moe", "nemotron_h": "nemotron_h",
    "longcat_flash": "longcat_flash", "mimo_v2": "mimo_v2",
    "olmo_hybrid": "olmo_hybrid", "afmoe": "afmoe",
    "granitemoehybrid": "granite_hybrid", "lfm2_moe": "lfm2_moe",
    "kimi_linear": "kimi_linear", "dots3_note": "dots3_note",
}
OLD_MECHANISM_KEYS = {
    "kv_lora_rank": ("deepseek_v3", "longcat_flash", "kimi_linear",
                     "dots3_note"),
    "q_lora_rank": ("longcat_flash", "dots3_note"),
    "zero_expert_num": ("longcat_flash",),
    "n_routed_experts": ("deepseek_v3", "nemotron_h", "longcat_flash",
                         "mimo_v2", "dots3_note"),
    "n_shared_experts": ("deepseek_v3", "nemotron_h", "dots3_note"),
    "first_k_dense_replace": ("deepseek_v3", "kimi_linear", "dots3_note"),
    "num_local_experts": ("mixtral", "granite_hybrid"),
    "num_experts": ("mixtral", "sdar_moe", "afmoe", "lfm2_moe",
                    "kimi_linear"),
    "moe_intermediate_size": ("deepseek_v3", "sdar_moe", "nemotron_h",
                              "mimo_v2", "afmoe", "lfm2_moe", "kimi_linear",
                              "dots3_note"),
    "hybrid_override_pattern": ("nemotron_h",),
    "mamba_num_heads": ("nemotron_h",),
    "ssm_state_size": ("nemotron_h",),
    "expert_parallel": ("nemotron_h", "longcat_flash", "mimo_v2", "afmoe",
                        "kimi_linear", "dots3_note"),
    # a window and a partial rotary embedding: computed by one family since
    # PR 45, refused for every other as they were for all
    "sliding_window": ("mimo_v2", "afmoe"),
    "partial_rotary_factor": ("mimo_v2",),
    "hybrid_layer_pattern": ("mimo_v2",),
    "moe_layer_freq": ("mimo_v2",),
    "swa_num_key_value_heads": ("mimo_v2", "dots3_note"),
    # kinds of layer and the linear-attention layers' sizes: computed by one
    # family since PR 48; before it no class read them and none refused them
    "layer_types": ("olmo_hybrid", "afmoe", "granite_hybrid", "lfm2_moe",
                    "dots3_note"),
    "linear_num_key_heads": ("olmo_hybrid",),
    "linear_num_value_heads": ("olmo_hybrid",),
    "linear_key_head_dim": ("olmo_hybrid",),
    "linear_value_head_dim": ("olmo_hybrid",),
    "linear_conv_kernel_dim": ("olmo_hybrid",),
    "linear_allow_neg_eigval": ("olmo_hybrid",),
    # a window of pages, a norm on both sides, a scaled embedding and a
    # sigmoid-routed mixture with a shared expert: one family since PR 52
    # (`num_experts_per_tok`, which every mixture's config carries, is read
    # by it and listed by none: a listed key is refused of all the others)
    "num_dense_layers": ("afmoe", "lfm2_moe"),
    "num_shared_experts": ("afmoe", "kimi_linear"),
    "route_norm": ("afmoe",),
    "route_scale": ("afmoe",),
    "score_func": ("afmoe",),
    "mup_enabled": ("afmoe",),
    "global_attn_every_n_layers": ("afmoe",),
    # state-space layers at one group beside a feed-forward in every layer,
    # Granite's four multipliers and its positions: one family since PR 55
    # (it reads `num_local_experts` to refuse its mixture siblings by name)
    **dict.fromkeys((
        "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
        "mamba_conv_bias", "embedding_multiplier",
        "attention_multiplier", "residual_multiplier", "logits_scaling",
        "position_embedding_type", "shared_intermediate_size"),
        ("granite_hybrid",)),
    # gated short convolutions beside attention, and a sigmoid-and-bias
    # routed mixture under its own keys: one family since PR 59
    # (`norm_topk_prob` and `routed_scaling_factor`, which four older
    # mixtures' configs carry, are read by it and listed by none)
    **dict.fromkeys(("conv_L_cache", "conv_bias", "use_expert_bias"),
                    ("lfm2_moe",)),
    # a delta rule whose decay is a number a key channel beside latent
    # attention that rotates nothing, and a sigmoid-routed mixture under its
    # own keys: one family since PR 62 (`routed_scaling_factor` and
    # `topk_group`, which older mixtures' configs carry, are read by it and
    # listed by none)
    **dict.fromkeys(("linear_attn_config", "mla_use_nope",
                     "num_experts_per_token", "moe_router_activation_func",
                     "moe_renormalize", "use_grouped_topk",
                     "num_expert_group"), ("kimi_linear",)),
    # a learned indexer whose top-k a full layer attends over, window layers
    # with a latent of their own, a gate a head and both latents rescaled:
    # one family since PR 64 (`sliding_window_size` and
    # `swa_num_attention_heads`, which mimo_v2's configs carry, are read by
    # it and listed by none)
    **dict.fromkeys(("index_topk", "index_n_heads", "index_head_dim",
                     "attention_gate_type", "swa_attention_gate_type",
                     "apply_mla_qkv_lora_rescale", "swa_kv_lora_rank",
                     "swa_q_lora_rank"), ("dots3_note",)),
    "attn_logit_softcapping": (),
    "final_logit_softcapping": (),
}


def test_the_records_state_the_old_tables():
    assert {t: m.FAMILY.name for m in FAMILIES
            for t in m.FAMILY.model_types} == OLD_MODEL_TYPES
    assert set(models._STATED_KEYS) == set(OLD_MECHANISM_KEYS)
    for key, readers in OLD_MECHANISM_KEYS.items():
        assert {m.FAMILY.name for m in FAMILIES
                if key in m.FAMILY.mechanism_keys} == set(readers), key


class _Chosen(Exception):
    pass


@pytest.mark.parametrize("model_type", sorted(OLD_MODEL_TYPES))
@pytest.mark.parametrize("key", sorted(OLD_MECHANISM_KEYS))
def test_a_mechanism_key_is_accepted_or_refused_as_before(
        monkeypatch, model_type, key):
    """`config_from_hf` over every (key, model_type) pair of the old tables:
    a key the type's class reads reaches the class, any other is refused by
    name before it."""
    family = OLD_MODEL_TYPES[model_type]
    module = MODULES[family]

    def chosen(hf, **kwargs):
        raise _Chosen

    monkeypatch.setattr(module.FAMILY.config_class, "from_hf_config",
                        staticmethod(chosen))
    # 7: present by every rule; num_experts > 1 would re-type a dense config
    # (a `layer_types` is a list: one entry of a kind nobody attends by)
    stated = ["sliding_attention"] if key == "layer_types" else 7
    hf = {"model_type": model_type, "intermediate_size": 128, key: stated}
    if family in OLD_MECHANISM_KEYS[key] or (
            family == "llama" and key in ("num_local_experts", "num_experts")):
        if family == "llama":  # re-typed as a mixture, which reads the key
            monkeypatch.setattr(MODULES["mixtral"].FAMILY.config_class,
                                "from_hf_config", staticmethod(chosen))
        with pytest.raises(_Chosen):
            config_from_hf(hf)
    else:
        with pytest.raises(ValueError, match=re.escape(
                f"carries {key}={stated!r}, which models/{family}.py does "
                "not compute")):
            config_from_hf(hf)


LINEAR_STATED = [
    {"layer_types": ["full_attention", "linear_attention"]},
    {"layer_types": ["sliding_attention", "full_attention"]},
    {"linear_num_key_heads": 30},
    {"linear_conv_kernel_dim": 4},
    {"linear_allow_neg_eigval": True},
    {"linear_use_gate": True},  # a `linear_*` key no family reads yet
]


@pytest.mark.parametrize("stated", LINEAR_STATED, ids=lambda d: next(iter(d)))
# (afmoe, granite_hybrid, lfm2_moe and dots3_note read `layer_types` too and
# refuse a kind they do not compute in their own class, by name:
# tests/engine/test_band_family.py, test_granite_family.py,
# test_conv_moe_family.py, test_sparse_family.py)
@pytest.mark.parametrize("model_type", sorted(
    set(OLD_MODEL_TYPES) - {"olmo_hybrid", "afmoe", "granitemoehybrid",
                            "lfm2_moe", "dots3_note"})
    + ["a_type_nobody_registered"])
def test_a_linear_attention_config_is_served_as_no_other_model(
        model_type, stated):
    """Before PR 48 an unregistered `model_type` with `layer_types` and
    `linear_*` keys was read as a Llama-shaped dense decoder and would have
    been SERVED as one. Every other family and an unregistered type refuse
    a kind of layer other than `full_attention` and any `linear_*` key, by
    name, before a configuration class sees the file."""
    (key, value), = stated.items()
    family = OLD_MODEL_TYPES.get(model_type, "llama")
    hf = {"model_type": model_type, "intermediate_size": 128, **stated}
    with pytest.raises(ValueError, match=re.escape(
            f"carries {key}={value!r}, which models/{family}.py does not "
            "compute")):
        config_from_hf(hf)


@pytest.mark.parametrize("stated", LINEAR_STATED[2:],
                         ids=lambda d: next(iter(d)))
@pytest.mark.parametrize("model_type", ["afmoe", "granitemoehybrid",
                                        "lfm2_moe", "dots3_note"])
def test_a_linear_key_is_refused_of_the_window_band_family_too(model_type,
                                                               stated):
    (key, value), = stated.items()
    family = OLD_MODEL_TYPES[model_type]
    with pytest.raises(ValueError, match=re.escape(
            f"carries {key}={value!r}, which models/{family}.py does not "
            "compute")):
        config_from_hf({"model_type": model_type, "intermediate_size": 128,
                        **stated})


@pytest.mark.parametrize("model_type", ["llama", "qwen2",
                                        "a_type_nobody_registered"])
def test_a_list_of_full_attention_alone_stays_accepted(monkeypatch,
                                                       model_type):
    def chosen(hf, **kwargs):
        raise _Chosen

    monkeypatch.setattr(llama.FAMILY.config_class, "from_hf_config",
                        staticmethod(chosen))
    with pytest.raises(_Chosen):
        config_from_hf({"model_type": model_type, "intermediate_size": 128,
                        "layer_types": ["full_attention"] * 4,
                        "linear_num_key_heads": None})
