"""Batched grouped LoRA matmul (bgmv): per-row adapter deltas in one dispatch.

Multi-LoRA serving (docs/lora.md) keeps every resident adapter's A/B factors
stacked in device pools `a [N, IN, R]` / `b [N, R, OUT]` per projection, and
each batch row carries an adapter index. The delta for row i is

    delta_i = (x_i @ a[idx_i]) @ b[idx_i]        # rank-R bottleneck

added to the BASE projection's output — so a mixed-adapter batch (including
adapter-free rows, which point at the all-zero identity row 0) decodes in ONE
dispatch instead of one sub-batch per adapter. This is the punica/vLLM "bgmv"
shape (PAPERS.md: S-LoRA lineage), built here in two flavors:

- `lora_delta_xla`: gather-by-index + two einsums. Runs anywhere (CPU tests,
  partitioned meshes — a pallas_call is opaque to GSPMD sharding propagation,
  same caveat as ops/attention.py).
- `lora_delta_pallas`: a Pallas TPU kernel. The adapter indices arrive via
  scalar prefetch (PrefetchScalarGridSpec), and the per-row A/B blocks are
  DMA'd straight from their pool rows by the block index_map — the gathered
  [B, IN, R] copy the XLA path materializes never exists. Grid is
  (B, T blocks); blocks take the full trailing dims, satisfying the Mosaic
  tiling rule the attention kernels rely on (block dims equal to array dims
  are always legal), so any (IN, R, OUT) works — ranks are far below one
  lane tile.

Numerics: fp32 accumulation through both thin matmuls
(`preferred_element_type`), delta returned in fp32; the caller adds it to the
base output and casts. Adapter-free rows read the all-zero row 0, so their
delta is exactly 0.0 and `base + 0.0` is bit-identical to the no-LoRA path.

`LLMLB_TPU_LORA=pallas|xla|auto` forces a path (auto: Pallas on a
single-device TPU, the ops/attention.py convention).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp


def _pallas_enabled() -> bool:
    mode = os.environ.get("LLMLB_TPU_LORA", "auto")
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def lora_delta_xla(
    x: jnp.ndarray,  # [B, T, IN]
    a: jnp.ndarray,  # [N, IN, R]
    b: jnp.ndarray,  # [N, R, OUT]
    idx: jnp.ndarray,  # [B] int32 — adapter pool row per batch row (0 = none)
) -> jnp.ndarray:
    """Per-row LoRA delta via take-along gather + two thin einsums.

    Returns [B, T, OUT] fp32. The gather materializes each row's factors
    ([B, IN, R] / [B, R, OUT]) — fine for XLA which fuses it into the
    contraction reads; the Pallas kernel avoids it outright.
    """
    a_sel = jnp.take(a, idx, axis=0)  # [B, IN, R]
    b_sel = jnp.take(b, idx, axis=0)  # [B, R, OUT]
    u = jnp.einsum("bti,bir->btr", x, a_sel,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btr,bro->bto", u, b_sel,
                      preferred_element_type=jnp.float32)


# Rows of x per grid step. A whole [512, 5632] f32 output block (a prefill
# chunk through TinyLlama's gate/up projection) is 11 MiB, 22 MiB double
# buffered — past v5e's 16 MiB scoped VMEM; 128 rows keep it under 6 MiB.
_BLOCK_T = 128


def _bgmv_kernel(idx_ref, x_ref, a_ref, b_ref, o_ref):
    """One block of one batch row: shrink (x @ A) then expand (u @ B), fp32.
    A/B blocks were already DMA'd from pool row idx_ref[bi] by the
    index_maps — the kernel body never touches the index itself."""
    u = jax.lax.dot_general(
        x_ref[0], a_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [T, R]
    o_ref[0] = jax.lax.dot_general(
        u, b_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def lora_delta_pallas(
    x: jnp.ndarray,  # [B, T, IN]
    a: jnp.ndarray,  # [N, IN, R]
    b: jnp.ndarray,  # [N, R, OUT]
    idx: jnp.ndarray,  # [B] int32
    interpret: bool = False,
) -> jnp.ndarray:
    """bgmv Pallas kernel: gather A/B by adapter index through the block
    index_map (scalar-prefetched indices steer the DMA), two thin matmuls
    per row. Returns [B, T, OUT] fp32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, t, in_dim = x.shape
    _, _, r = a.shape
    out_dim = b.shape[2]

    blk_t = min(t, _BLOCK_T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, pl.cdiv(t, blk_t)),
        in_specs=[
            pl.BlockSpec((1, blk_t, in_dim), lambda bi, ti, idx: (bi, ti, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, in_dim, r), lambda bi, ti, idx: (idx[bi], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, r, out_dim), lambda bi, ti, idx: (idx[bi], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, blk_t, out_dim),
                               lambda bi, ti, idx: (bi, ti, 0),
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        _bgmv_kernel,
        out_shape=jax.ShapeDtypeStruct((bsz, t, out_dim), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(idx.astype(jnp.int32), x, a, b)


def lora_delta(
    x: jnp.ndarray,  # [B, T, IN]
    a: jnp.ndarray,  # [N, IN, R]
    b: jnp.ndarray,  # [N, R, OUT]
    idx: jnp.ndarray,  # [B] int32
) -> jnp.ndarray:
    """Dispatcher: Pallas bgmv on an unpartitioned TPU, XLA gather path
    elsewhere (LLMLB_TPU_LORA forces either). Returns [B, T, OUT] fp32."""
    if _pallas_enabled():
        return lora_delta_pallas(x, a, b, idx)
    return lora_delta_xla(x, a, b, idx)
