"""Named model presets for the tpu:// engine.

Presets let the engine start without a checkpoint directory (random weights) for
benches/tests, and pin the architectural config for well-known checkpoints so
serving starts before config.json is even read. Shapes follow the public model
cards; none of this data comes from the reference repo (which stores only
name→engine alias mappings, /root/reference/llmlb/src/models/mapping.rs).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from llmlb_tpu.models.afmoe import AfmoeConfig
from llmlb_tpu.models.deepseek_v3 import DeepseekV3Config
from llmlb_tpu.models.dots3_note import Dots3NoteConfig
from llmlb_tpu.models.granite_hybrid import GraniteHybridConfig
from llmlb_tpu.models.kimi_linear import KimiLinearConfig
from llmlb_tpu.models.lfm2_moe import Lfm2MoeConfig
from llmlb_tpu.models.llama import LlamaConfig
from llmlb_tpu.models.longcat_flash import LongcatFlashConfig
from llmlb_tpu.models.mimo_v2 import MimoV2Config
from llmlb_tpu.models.mixtral import MixtralConfig
from llmlb_tpu.models.nemotron_h import NemotronHConfig
from llmlb_tpu.models.olmo_hybrid import OlmoHybridConfig
from llmlb_tpu.models.sdar_moe import SdarMoeConfig
from llmlb_tpu.ops.rope import RopeScaling

PRESETS: dict[str, LlamaConfig] = {
    # sparse-MoE flagship (BASELINE.json config #5: multi-slice v5e target);
    # served via models/mixtral.py with experts on the mesh ep axis
    "mixtral-8x7b": MixtralConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=1000000.0,
        rms_eps=1e-5, max_position_embeddings=32768,
        num_experts=8, experts_per_token=2,
    ),
    # CI-sized MoE config for unit tests and the multichip dry-run
    "debug-moe-tiny": MixtralConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, dtype=jnp.float32,
        max_position_embeddings=128, num_experts=4, experts_per_token=2,
    ),
    # CI-sized latent-attention mixture (models/deepseek_v3.py): one leading
    # dense layer, two expert layers, a shared expert
    "debug-mla-tiny": DeepseekV3Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=4, num_kv_heads=4, head_dim=8,
        rope_theta=10000.0, rms_eps=1e-6, dtype=jnp.float32,
        max_position_embeddings=512, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
        experts_per_token=2, moe_intermediate_size=32, num_shared_experts=1,
        first_k_dense=1, routed_scaling_factor=2.448,
    ),
    # CI-sized mixture that generates by diffusion over blocks
    # (models/sdar_moe.py, docs/block-diffusion.md): blocks of 4
    "debug-sdar-tiny": SdarMoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=3, num_heads=8, num_kv_heads=2, head_dim=16,
        rope_theta=1000000.0, rms_eps=1e-6, dtype=jnp.float32,
        max_position_embeddings=512, num_experts=16, experts_per_token=4,
        moe_intermediate_size=32, mask_token_id=500,
    ),
    # CI-sized hybrid of state-space, attention and expert layers
    # (models/nemotron_h.py, docs/hybrid-state.md): every kind of layer, a
    # scan chunk of 16, and the second half (4 of 8) of the experts held
    "debug-nemotron-h-tiny": NemotronHConfig(
        vocab_size=512, hidden_size=64, intermediate_size=32,
        num_layers=7, num_heads=4, num_kv_heads=2, head_dim=16,
        rms_eps=1e-5, dtype=jnp.float32, max_position_embeddings=512,
        pattern="MEM*EME", ssm_heads=8, ssm_head_dim=8, ssm_groups=2,
        ssm_state=16, conv_kernel=4, chunk_size=16, router_experts=8,
        num_experts=4, first_expert=4, experts_per_token=2,
        moe_intermediate_size=32, shared_intermediate_size=48,
        routed_scaling_factor=2.5,
    ),
    # CI-sized shortcut-connected mixture (models/longcat_flash.py,
    # docs/longcat-flash.md): two double layers, a low-rank query with both
    # latents scaled, a router over 8 experts and 4 zero-compute ones that
    # holds the second half (4 of 8) of the experts
    "debug-longcat-tiny": LongcatFlashConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=2, num_heads=4, num_kv_heads=4, head_dim=8,
        rope_theta=10000.0, rms_eps=1e-5, dtype=jnp.float32,
        max_position_embeddings=512, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=16,
        q_lora_scale=2.0, kv_lora_scale=2.0 ** 0.5, router_experts=8,
        num_experts=4, first_expert=4, zero_experts=4, experts_per_token=3,
        moe_intermediate_size=32, routed_scaling_factor=6.0,
    ),
    # CI-sized window-and-global decoder (models/mimo_v2.py,
    # docs/window-attention.md): the published period, a leading global +
    # dense layer and five window layers to one global behind it; a ring of
    # 16 cells a slot, keys of 24 beside values of 16 (8 of 24 rotate), a
    # sink a head, the second half (4 of 8) of the experts held
    "debug-mimo-tiny": MimoV2Config(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=7, num_heads=8, num_kv_heads=2, head_dim=24,
        rope_theta=10000000.0, rms_eps=1e-5, dtype=jnp.float32,
        max_position_embeddings=1024, pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_pattern=(0, 1, 1, 1, 1, 1, 1), v_head_dim=16, window_kv_heads=4,
        window_rope_theta=10000.0, sliding_window=16,
        partial_rotary_factor=0.334, value_scale=0.707, router_experts=8,
        num_experts=4, first_expert=4, experts_per_token=2,
        moe_intermediate_size=32,
    ),
    # CI-sized linear-attention hybrid (models/olmo_hybrid.py,
    # docs/linear-attention.md): the published period of three delta-rule
    # layers to a full-attention one and a linear layer behind it, values
    # twice as wide as keys, chunks of 16, no grouping; three heads, so
    # that the page pool holds a dead fourth
    "debug-olmo-hybrid-tiny": OlmoHybridConfig(
        vocab_size=512, hidden_size=48, intermediate_size=96,
        num_layers=5, num_heads=3, num_kv_heads=3, head_dim=16,
        rms_eps=1e-6, dtype=jnp.float32, max_position_embeddings=512,
        layer_types=("linear_attention",) * 3 + ("full_attention",
                                                 "linear_attention"),
        lin_heads=3, lin_key_dim=8, lin_value_dim=16, conv_kernel=4,
        allow_neg_eigval=True, chunk_size=16,
    ),
    # CI-sized window-band decoder (models/afmoe.py, docs/afmoe.md): two
    # leading dense layers and ONE published period behind them (window,
    # window, window, global, window, window): a window of 16 cells held as
    # a band of 3 pages of 8 a slot, so prompts, extends and decode all wrap
    # it; gated, sandwich-normed attention, rotary in the window layers
    # only, the second half (4 of 8) of the experts held, one shared expert
    "debug-trinity-tiny": AfmoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=6, num_heads=8, num_kv_heads=2, head_dim=16,
        rope_theta=10000.0, rms_eps=1e-5, dtype=jnp.float32,
        max_position_embeddings=1024,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)
        + ("sliding_attention",) * 2,
        sliding_window=16, band_page_size=8, num_dense_layers=2,
        router_experts=8, num_experts=4, first_expert=4,
        experts_per_token=2, moe_intermediate_size=32,
        num_shared_experts=1, route_norm=True, route_scale=2.826,
        mup_enabled=True,
    ),
    # CI-sized dense hybrid (models/granite_hybrid.py,
    # docs/granite-hybrid.md): runs of 2, 3 and 1 state-space layers at ONE
    # group between two attention layers, a feed-forward in every layer, the
    # four multipliers, the head tied to the embedding table
    "debug-granite-hybrid-tiny": GraniteHybridConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=8, num_heads=4, num_kv_heads=2, rms_eps=1e-5,
        dtype=jnp.float32, max_position_embeddings=512,
        tie_word_embeddings=True, embedding_multiplier=12.0,
        logits_scaling=8.0,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba",
                     "mamba", "attention", "mamba"),
        ssm_heads=8, ssm_head_dim=16, ssm_groups=1, ssm_state=16,
        conv_kernel=4, chunk_size=16, attention_multiplier=0.0625,
        residual_multiplier=0.22,
    ),
    # CI-sized gated-short-convolution mixture (models/lfm2_moe.py,
    # docs/lfm2-moe.md): conv, conv, attention, conv twice over, two rows
    # carried a slot and conv layer, QK-normed rotary attention at two KV
    # heads of 16 packed to a row, two dense feed-forwards, then mixtures of
    # 8 experts, 2 a token, the head tied to the embedding table
    "debug-lfm2-moe-tiny": Lfm2MoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=8, num_heads=4, num_kv_heads=2, rope_theta=1000000.0,
        rms_eps=1e-5, dtype=jnp.float32, max_position_embeddings=512,
        tie_word_embeddings=True,
        layer_types=("conv", "conv", "full_attention", "conv") * 2,
        conv_taps=3, num_dense_layers=2, num_experts=8, experts_per_token=2,
        moe_intermediate_size=48, norm_topk_prob=True,
        routed_scaling_factor=1.0,
    ),
    # CI-sized Kimi Delta Attention mixture (models/kimi_linear.py,
    # docs/kimi-linear.md): K | KK A | KK A, a delta-rule state whose decay
    # is a number a key channel beside a LATENT page pool that rotates
    # nothing, one dense feed-forward, then mixtures of which this chip holds
    # experts 4-7 of 8 (chip 1 of 2), 2 a token, with a shared expert
    "debug-kimi-linear-tiny": KimiLinearConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=7, num_heads=4, num_kv_heads=4, head_dim=8,
        rms_eps=1e-5, dtype=jnp.float32, max_position_embeddings=512,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=4, router_experts=8, first_expert=4,
        experts_per_token=2, moe_intermediate_size=32, num_shared_experts=1,
        first_k_dense=1, routed_scaling_factor=2.446, norm_topk_prob=True,
        mixers=("kda", "kda", "kda", "mla", "kda", "kda", "mla"),
        kda_heads=4, kda_head_dim=16, conv_kernel=4, chunk_size=16,
    ),
    # docs/sparse-attention.md at a CI size: two full layers whose learned
    # indexer picks 16 cells a query, then three sliding layers over the last
    # 5 positions with a latent of their own in a ring a slot, a gate a head
    # on both, one dense feed-forward, then mixtures of which this chip holds
    # experts 4-7 of 8 (chip 1 of 2), 2 a token, with a shared expert
    "debug-dots3-note-tiny": Dots3NoteConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        num_layers=5, num_heads=4, num_kv_heads=4, head_dim=8,
        rope_theta=8e7, rms_eps=1e-5, dtype=jnp.float32,
        max_position_embeddings=512,
        kv_lora_rank=32, q_lora_rank=24, q_lora_scale=math.sqrt(64 / 24),
        kv_lora_scale=math.sqrt(64 / 32), qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=4, router_experts=8, first_expert=4,
        experts_per_token=2, moe_intermediate_size=32, num_shared_experts=1,
        first_k_dense=1, routed_scaling_factor=1.0, norm_topk_prob=True,
        index_topk=16, index_heads=4, index_head_dim=16,
        layer_types=("full", "full", "sliding", "sliding", "sliding"),
        sliding_window=5, swa_heads=2, swa_q_lora_rank=24,
        swa_kv_lora_rank=48, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, swa_rope_theta=5e4,
    ),
    # flagship serving target (BASELINE.json config #2)
    "llama-3-8b": LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
        rms_eps=1e-5, max_position_embeddings=8192,
    ),
    "llama-3.1-8b": LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
        rope_scaling=RopeScaling(factor=8.0, low_freq_factor=1.0,
                                 high_freq_factor=4.0, original_max_position=8192),
        rms_eps=1e-5, max_position_embeddings=131072,
    ),
    # 1B-class: fits one v5e chip with headroom; the single-chip bench model
    "tinyllama-1.1b": LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=32, num_kv_heads=4, rope_theta=10000.0,
        rms_eps=1e-5, max_position_embeddings=2048,
    ),
    "qwen2.5-0.5b": LlamaConfig(
        vocab_size=151936, hidden_size=896, intermediate_size=4864,
        num_layers=24, num_heads=14, num_kv_heads=2, rope_theta=1000000.0,
        rms_eps=1e-6, attention_bias=True, tie_word_embeddings=True,
        max_position_embeddings=32768,
    ),
    # CI-sized config for unit tests and the multichip dry-run
    "debug-tiny": LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=8, num_kv_heads=4, dtype=jnp.float32,
        max_position_embeddings=512,
    ),
}


def get_preset(name: str) -> LlamaConfig:
    try:
        return PRESETS[name]
    except KeyError:
        raise KeyError(
            f"unknown model preset {name!r}; available: {sorted(PRESETS)}"
        ) from None
