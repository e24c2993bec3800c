"""TPU chip/HBM telemetry for the engine's /api/health endpoint.

Replaces the GPU VRAM/utilization fields the reference's health checker reads
from xLLM endpoints (/root/reference/llmlb/src/health/endpoint_checker.rs:515,
types/health.rs) with libtpu-backed figures surfaced through JAX device APIs.
The gateway's scheduler consumes these for placement decisions.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip peak figures for utilization math (bf16 and int8 dense
    FLOPs, HBM bandwidth). Public spec-sheet numbers; MFU/HBM-utilization
    gauges divide measured work by these. `peak_flops_int8` is the OPS
    figure quantized serving is judged against (v5e/v5p/v6e double their
    bf16 rate on int8 operands; v4 has no int8 fast path — same figure)."""

    generation: str
    peak_flops: float  # bf16 FLOP/s per chip
    peak_hbm_bw: float  # bytes/s per chip
    peak_flops_int8: float = 0.0  # int8 OP/s per chip (0 -> same as bf16)

    @property
    def int8_flops(self) -> float:
        return self.peak_flops_int8 or self.peak_flops


# Keyed by a normalized device_kind substring (lowercase, spaces stripped).
# jax reports e.g. "TPU v4", "TPU v5 lite", "TPU v5p", "TPU v6 lite".
# Order matters: more specific keys first ("v5p" before "v5").
CHIP_SPECS: tuple[tuple[str, ChipSpec], ...] = (
    ("v6lite", ChipSpec("v6e", 918e12, 1.64e12, 1836e12)),
    ("v6e", ChipSpec("v6e", 918e12, 1.64e12, 1836e12)),
    ("v5p", ChipSpec("v5p", 459e12, 2.765e12, 918e12)),
    ("v5lite", ChipSpec("v5e", 197e12, 0.82e12, 394e12)),
    ("v5e", ChipSpec("v5e", 197e12, 0.82e12, 394e12)),
    ("v4", ChipSpec("v4", 275e12, 1.23e12)),
)


def chip_spec_for(device_kind: str) -> ChipSpec | None:
    """Resolve a jax device_kind string to its peak specs (None for CPU /
    unknown chips — utilization gauges are then unavailable, never wrong)."""
    key = str(device_kind).lower().replace(" ", "")
    for frag, spec in CHIP_SPECS:
        if frag in key:
            return spec
    return None


def model_flops_per_token(cfg, n_params: int) -> float:
    """Decode FLOPs per generated token: ~2 FLOPs per parameter touched
    (one multiply + one add per weight). MoE models only touch the routed
    experts' FFN weights, so count active params, not total."""
    experts = getattr(cfg, "num_experts", 0) or 0
    if experts > 1:
        per_tok = getattr(cfg, "experts_per_token", 1) or 1
        # FFN weights are the expert-replicated part; attention/embed are
        # shared. Approximate: scale the FFN fraction by routed/total.
        # a family may give its experts a width and a layer count of their
        # own (leading dense layers): models/deepseek_v3.py
        ffn = (3 * cfg.hidden_size
               * getattr(cfg, "moe_intermediate_size", cfg.intermediate_size)
               * getattr(cfg, "num_moe_layers", cfg.num_layers) * experts)
        active = n_params - ffn + ffn * per_tok / experts
        return 2.0 * active
    return 2.0 * n_params


def model_bytes_per_token(cfg, n_params: int, mean_context: float,
                          batch: int = 1, *,
                          weight_bytes: float | None = None,
                          kv_cell_bytes: float | None = None,
                          kv_token_layer_bytes: float | None = None) -> float:
    """HBM bytes read per decoded token: every weight once per STEP (decode
    is memory-bound; weights dominate and are amortized across the `batch`
    sequences decoded together) plus the KV rows of the sequence's own
    context (never amortized — each sequence reads its own).

    Quantization overrides (llmlb_tpu/quant): `weight_bytes` is the actual
    total parameter footprint (int8 values + f32 scales when weights are
    quantized — the engine passes its measured device-array bytes), and
    `kv_cell_bytes` the bytes per cached (token, head) cell (D·1 + 4-byte
    scale under int8 KV vs D·itemsize bf16). Defaults reproduce the
    unquantized bf16 math exactly. `kv_token_layer_bytes` is the family's
    own figure for one token in one layer of the pool (the engine passes
    it: a latent pool has no per-head cell) and overrides the head math."""
    import jax.numpy as jnp

    itemsize = jnp.dtype(cfg.dtype).itemsize
    if weight_bytes is None:
        weight_bytes = n_params * itemsize
    if kv_cell_bytes is None:
        kv_cell_bytes = cfg.head_dim_ * itemsize
    if kv_token_layer_bytes is None:
        kv_token_layer_bytes = cfg.num_kv_heads * kv_cell_bytes * 2
    kv_bytes = cfg.num_layers * mean_context * kv_token_layer_bytes
    return weight_bytes / max(1, batch) + kv_bytes


def device_telemetry() -> dict[str, Any]:
    devices = jax.local_devices()
    chips = []
    hbm_used_total = 0
    hbm_limit_total = 0
    for d in devices:
        stats: dict[str, Any] = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        used = int(stats.get("bytes_in_use", 0))
        limit = int(stats.get("bytes_limit", 0))
        hbm_used_total += used
        hbm_limit_total += limit
        chips.append(
            {
                "id": d.id,
                "platform": d.platform,
                "device_kind": getattr(d, "device_kind", "unknown"),
                "hbm_used_bytes": used,
                "hbm_total_bytes": limit,
            }
        )
    return {
        "accelerator": devices[0].platform if devices else "none",
        "chip_count": len(devices),
        "hbm_used_bytes": hbm_used_total,
        "hbm_total_bytes": hbm_limit_total,
        "chips": chips,
    }
