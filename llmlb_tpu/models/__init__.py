"""Model families served by the tpu:// engine.

`family_for(cfg)` resolves the function module (init_params / param_shardings /
init_kv_pages / kv_pages_shardings / prefill_into_pages / prefill_extend_pages
/ verify_step_paged / decode_step_paged — one shared serving contract over the
paged KV pool) for a config, so the engine scheduler is family-agnostic: dense
Llama-class (llama.py) and sparse-MoE Mixtral-class (mixtral.py) plug into the
same continuous-batching loop.
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


def family_for(cfg):
    """Resolve the serving-function module for a model config."""
    from llmlb_tpu.models import llama, mixtral

    if isinstance(cfg, mixtral.MixtralConfig):
        return mixtral
    if isinstance(cfg, LlamaConfig):
        return llama
    raise TypeError(f"no model family for config type {type(cfg).__name__}")


__all__ = [
    "LlamaConfig",
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
