"""Model correctness: prefill/decode consistency + parity with HF transformers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.models import llama
from llmlb_tpu.models.llama import (
    LlamaConfig,
    decode_step_paged,
    init_params,
    prefill_into_pages,
)
from tests.support import identity_kv_pages

TINY = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    rope_theta=10000.0,
    rms_eps=1e-5,
    dtype=jnp.float32,
)


def test_prefill_then_decode_matches_full_prefill():
    """Decoding token-by-token must reproduce full-prompt prefill logits."""
    cfg = TINY
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    b, t_full, capacity = 2, 8, 16

    ids = jax.random.randint(jax.random.PRNGKey(1), (b, t_full), 0, cfg.vocab_size)
    lens_full = jnp.array([t_full, t_full], jnp.int32)

    ck, cv, tables = identity_kv_pages(llama, cfg, b, capacity)
    full_logits, _, _ = prefill_into_pages(params, cfg, ids, lens_full,
                                           tables, ck, cv)

    # prefill only the first 5 tokens, then decode the remaining 3
    t0 = 5
    ck, cv, tables = identity_kv_pages(llama, cfg, b, capacity)
    padded = jnp.zeros((b, t0), jnp.int32).at[:, :t0].set(ids[:, :t0])
    logits, ck, cv = prefill_into_pages(
        params, cfg, padded, jnp.array([t0, t0], jnp.int32), tables, ck, cv
    )
    for step in range(t0, t_full):
        logits, ck, cv = decode_step_paged(
            params, cfg, ids[:, step], jnp.full((b,), step, jnp.int32),
            ck, cv, tables,
        )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits), rtol=2e-4, atol=2e-4
    )


def test_ragged_prompt_lens_ignore_padding():
    """Padding tokens after prompt_len must not change the last-token logits."""
    cfg = TINY
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, t, capacity = 2, 8, 16
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, t), 0, cfg.vocab_size)
    lens = jnp.array([5, 8], jnp.int32)

    ck, cv, tables = identity_kv_pages(llama, cfg, b, capacity)
    logits_a, _, _ = prefill_into_pages(params, cfg, ids, lens, tables,
                                        ck, cv)

    garbage = ids.at[0, 5:].set(7)  # mutate only padding of sequence 0
    ck, cv, tables = identity_kv_pages(llama, cfg, b, capacity)
    logits_b, _, _ = prefill_into_pages(params, cfg, garbage, lens, tables,
                                        ck, cv)
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("attention_bias,tie", [(False, False), (True, True)])
def test_matches_hf_transformers(attention_bias, tie):
    """Logit parity with HF torch Llama/Qwen2 on a random tiny checkpoint."""
    torch = pytest.importorskip("torch")
    import transformers

    torch.manual_seed(0)

    from llmlb_tpu.engine.weights import convert_hf_tensors

    if attention_bias:
        hf_cfg = transformers.Qwen2Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
            tie_word_embeddings=tie,
        )
        hf_model = transformers.Qwen2ForCausalLM(hf_cfg)
    else:
        hf_cfg = transformers.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
            tie_word_embeddings=tie, attention_bias=False,
        )
        hf_model = transformers.LlamaForCausalLM(hf_cfg)
    hf_model.eval()

    cfg = LlamaConfig.from_hf_config(hf_cfg.to_dict(), dtype=jnp.float32)
    assert cfg.attention_bias == attention_bias

    state = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    params = convert_hf_tensors(cfg, lambda name: state[name])
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)

    b, t = 2, 7
    ids_np = np.random.default_rng(0).integers(0, 256, (b, t))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids_np)).logits[:, -1, :].numpy()

    ck, cv, tables = identity_kv_pages(llama, cfg, b, 16)
    logits, _, _ = prefill_into_pages(
        params, cfg, jnp.asarray(ids_np, jnp.int32),
        jnp.full((b,), t, jnp.int32), tables, ck, cv,
    )
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-3, atol=2e-3)
