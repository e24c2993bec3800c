"""HF-checkpoint ingestion for `model_type` `deepseek_v3`: a tiny checkpoint
under the published tensor names (`kv_a_proj_with_mqa`, `kv_b_proj`,
`mlp.gate.e_score_correction_bias`, `mlp.shared_experts`, …) loads into the
two-group pytree of models/deepseek_v3.py — `kv_b_proj` split per head once,
the dense layer's leaves under their prefix, the router's bias in float32 —
and `config_from_hf` picks classes by `model_type` and refuses a config whose
stated mechanism the chosen class would ignore."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from safetensors.numpy import save_file

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.weights import load_checkpoint, load_config
from llmlb_tpu.models import (
    FAMILIES,
    config_from_hf,
    deepseek_v3,
    llama,
    mixtral,
)

CFG = get_preset("debug-mla-tiny")
HF_CONFIG = {
    "model_type": "deepseek_v3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "first_k_dense_replace": 1,
    "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "rope_interleave": True, "rope_theta": 10000.0, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "max_position_embeddings": 512, "tie_word_embeddings": False,
}


def _save_checkpoint(tmp_path, cfg, params):
    def t(x):  # safetensors serializes raw buffers: materialize transposes
        return np.ascontiguousarray(np.asarray(x).T)

    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["ln_final"]),
        "lm_head.weight": t(params["lm_head"]),
    }
    for i in range(cfg.num_layers):
        dense = i < cfg.first_k_dense
        prefix, j = (deepseek_v3.DENSE, i) if dense else ("", i - cfg.first_k_dense)
        leaf = lambda name: np.asarray(params[prefix + name][j])  # noqa: E731
        base = f"model.layers.{i}."
        # kv_b_proj [H*(Dn+Dv), C]: per head, the key rows then the value rows
        kv_b = np.concatenate([leaf("wk_b").transpose(0, 2, 1),
                               leaf("wv_b").transpose(0, 2, 1)], axis=1)
        tensors.update({
            base + "self_attn.q_proj.weight": t(leaf("wq")),
            base + "self_attn.kv_a_proj_with_mqa.weight": t(leaf("wkv_a")),
            base + "self_attn.kv_a_layernorm.weight": leaf("ln_kv"),
            base + "self_attn.kv_b_proj.weight": np.ascontiguousarray(
                kv_b.reshape(-1, cfg.kv_lora_rank)),
            base + "self_attn.o_proj.weight": t(leaf("wo")),
            base + "input_layernorm.weight": leaf("ln_attn"),
            base + "post_attention_layernorm.weight": leaf("ln_mlp"),
        })
        if dense:
            for hf, ours in (("gate_proj", "wg"), ("up_proj", "wu"),
                             ("down_proj", "wd")):
                tensors[base + f"mlp.{hf}.weight"] = t(leaf(ours))
            continue
        tensors[base + "mlp.gate.weight"] = t(leaf("router"))
        tensors[base + "mlp.gate.e_score_correction_bias"] = leaf("router_bias")
        for hf, ours in (("gate_proj", "gate"), ("up_proj", "up"),
                         ("down_proj", "down")):
            tensors[base + f"mlp.shared_experts.{hf}.weight"] = t(leaf("ws_" + ours))
            for e in range(cfg.num_experts):
                tensors[base + f"mlp.experts.{e}.{hf}.weight"] = t(
                    leaf("we_" + ours)[e])
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(HF_CONFIG))


def test_latent_moe_checkpoint_roundtrip(tmp_path):
    params = deepseek_v3.init_params(CFG, jax.random.PRNGKey(0))
    _save_checkpoint(tmp_path, CFG, params)
    cfg = load_config(str(tmp_path), dtype=CFG.dtype)
    assert cfg == CFG
    loaded = load_checkpoint(str(tmp_path), cfg)
    assert set(loaded) == set(params)
    for name in params:
        assert loaded[name].dtype == params[name].dtype, name
        np.testing.assert_array_equal(np.asarray(loaded[name], np.float32),
                                      np.asarray(params[name], np.float32),
                                      err_msg=name)
    assert loaded["router_bias"].dtype == jnp.float32
    assert loaded["wk_b"].shape == (2, 4, 32, 16)  # [Lm, H, C, Dn]
    assert loaded[deepseek_v3.DENSE + "wg"].shape == (1, 64, 128)


def test_bf16_serving_keeps_the_router_bias_in_float32(tmp_path):
    params = deepseek_v3.init_params(CFG, jax.random.PRNGKey(1))
    _save_checkpoint(tmp_path, CFG, params)
    loaded = load_checkpoint(str(tmp_path), load_config(str(tmp_path)))
    assert loaded["router_bias"].dtype == jnp.float32
    assert loaded["wq"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(loaded["router_bias"]),
                                  np.asarray(params["router_bias"]))


@pytest.mark.parametrize("model_type,cls", [
    ("llama", llama.LlamaConfig), ("mistral", llama.LlamaConfig),
    ("qwen2", llama.LlamaConfig), ("mixtral", mixtral.MixtralConfig),
    ("deepseek_v3", deepseek_v3.DeepseekV3Config),
    ("some_new_dense_model", llama.LlamaConfig)])
def test_model_type_picks_the_class(model_type, cls):
    dense = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "sliding_window": None}
    hf = {"mixtral": {**dense, "num_local_experts": 4, "num_experts_per_tok": 2},
          "deepseek_v3": HF_CONFIG}.get(model_type, dense)
    assert type(config_from_hf({**hf, "model_type": model_type})) is cls
    assert (any(model_type in m.FAMILY.model_types for m in FAMILIES)
            or cls is llama.LlamaConfig)


@pytest.mark.parametrize("model_type,key,value", [
    ("llama", "kv_lora_rank", 512),
    ("mistral", "sliding_window", 4096),
    ("llama", "n_routed_experts", 128),
    ("qwen2", "n_shared_experts", 2),
    ("mixtral", "kv_lora_rank", 512),
    ("mixtral", "sliding_window", 4096),
    ("some_new_model", "first_k_dense_replace", 3),
    ("gemma2", "attn_logit_softcapping", 50.0),
    ("deepseek_v3", "sliding_window", 4096),
    ("deepseek_v3", "q_lora_rank", 1536),
])
def test_a_mechanism_the_class_would_ignore_is_refused_by_name(
        model_type, key, value):
    base = HF_CONFIG if model_type == "deepseek_v3" else {
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        **({"num_local_experts": 4, "num_experts_per_tok": 2}
           if model_type == "mixtral" else {})}
    with pytest.raises((ValueError, NotImplementedError), match=key):
        config_from_hf({**base, "model_type": model_type, key: value})


def test_a_mechanism_stated_and_switched_off_is_served():
    hf = {"model_type": "qwen2", "vocab_size": 512, "hidden_size": 64,
          "intermediate_size": 128, "num_hidden_layers": 2,
          "num_attention_heads": 4, "sliding_window": 32768,
          "use_sliding_window": False, "q_lora_rank": None,
          "num_experts": 0}
    assert type(config_from_hf(hf)) is llama.LlamaConfig


def test_the_family_refuses_what_it_does_not_compute():
    for key, value in (("n_group", 8), ("topk_group", 4),
                       ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn", "factor": 40})):
        with pytest.raises(NotImplementedError, match=key):
            config_from_hf({**HF_CONFIG, key: value})
