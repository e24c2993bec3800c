"""Ring attention: sequence-parallel causal prefill over an `sp` mesh axis.

Long-context prefill is the one place where a single chip's HBM cannot hold the
working set (activations + KV for 128k+ tokens). The TPU-native answer is
sequence parallelism: shard the token axis over `sp` devices and rotate KV
blocks around the ring with `lax.ppermute` while each device keeps its query
chunk resident. Attention statistics are merged with the online-softmax
recurrence (running max / running sum), so the result is bit-comparable to
dense softmax attention up to float associativity.

Communication pattern (per layer): sp-1 ppermute hops of the local KV block
([B, T/sp, K, D] each) — nearest-neighbour ICI traffic that overlaps with the
per-block QK^T/PV matmuls on the MXU. This is the standard ring-attention
schedule (Liu et al., see PAPERS.md); causality means on average half the
blocks are fully masked for a given query chunk. We still traverse the full
ring (static schedule — XLA requires it) but skip the FLOPs for fully-masked
blocks via `lax.cond`-free masking, which XLA folds into the einsum when the
block contributes nothing.

The reference gateway has no sequence parallelism of any kind (SURVEY.md §2.4,
§5 "long-context: absent") — this subsystem is new TPU-first design required by
the north star (BASELINE.json long-context configs).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30  # finite: fully-masked rows must still produce softmax-able sums


def _ring_attention_local(
    q: jnp.ndarray,  # [B, C, H, D] local query chunk (C = T / sp)
    k: jnp.ndarray,  # [B, C, K, D] local key chunk
    v: jnp.ndarray,  # [B, C, K, D] local value chunk
    prompt_lens: jnp.ndarray,  # [B] int32, replicated — global valid lengths
    *,
    axis_name: str,
    axis_size: int,
) -> jnp.ndarray:
    """Per-device ring attention body (runs inside shard_map)."""
    b, c, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = d**-0.5

    rank = lax.axis_index(axis_name)
    q_pos = rank * c + jnp.arange(c, dtype=jnp.int32)  # [C] global query positions
    qg = q.reshape(b, c, kh, g, d)

    # Online-softmax state, all fp32: running max m, running sum l, accum o.
    m = jnp.full((b, kh, g, c), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, kh, g, c), jnp.float32)
    o = jnp.zeros((b, c, kh, g, d), jnp.float32)

    # Ring schedule: at step s each device holds the KV block originally owned
    # by rank (rank - s) mod sp. The loop is a static Python unroll — sp is a
    # small static mesh dim, and a static perm lets XLA pipeline ppermute with
    # the matmuls of the next step.
    fwd_perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for s in range(axis_size):
        src = (rank - s) % axis_size
        k_pos = src * c + jnp.arange(c, dtype=jnp.int32)  # [C] global key positions

        scores = jnp.einsum(
            "bckgd,bskd->bkgcs", qg, k, preferred_element_type=jnp.float32
        ) * scale  # [B, K, G, C, Ck]

        causal = q_pos[:, None] >= k_pos[None, :]  # [C, Ck]
        valid = k_pos[None, :] < prompt_lens[:, None]  # [B, Ck]
        mask = causal[None, :, :] & valid[:, None, :]  # [B, C, Ck]
        scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)

        m_new = jnp.maximum(m, scores.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])  # [B, K, G, C, Ck]
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bkgcs,bskd->bckgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        o = o * jnp.moveaxis(corr, -1, 1)[..., None] + pv
        m = m_new

        if s != axis_size - 1:  # last block needs no forwarding
            k = lax.ppermute(k, axis_name, fwd_perm)
            v = lax.ppermute(v, axis_name, fwd_perm)

    # Normalize; guard fully-masked rows (padding queries) against 0/0.
    l_safe = jnp.maximum(jnp.moveaxis(l, -1, 1)[..., None], 1e-30)
    out = o / l_safe
    return out.reshape(b, c, h, d).astype(q.dtype)


def ring_prefill_attention(
    q: jnp.ndarray,  # [B, T, H, D] — T divisible by mesh sp
    k: jnp.ndarray,  # [B, T, K, D]
    v: jnp.ndarray,  # [B, T, K, D]
    prompt_lens: jnp.ndarray,  # [B] int32
    mesh: Mesh,
    *,
    seq_axis: str = "sp",
    batch_axis: str | None = "dp",
    head_axis: str | None = "tp",
    kv_head_axis: str | None = "unset",
) -> jnp.ndarray:
    """Causal GQA prefill attention, sequence-sharded over `seq_axis`.

    Drop-in equal to ops.attention.gqa_attention_prefill (same [B, T, H, D] in/
    out), but the sequence axis lives sharded across the ring — the full T×T
    score matrix never materializes on any one chip. Composes with batch
    sharding over `batch_axis` and head (tensor-parallel) sharding over
    `head_axis`: ppermute only rotates within each (dp, tp) fiber.
    """
    sp = mesh.shape[seq_axis]
    if q.shape[1] % sp:
        raise ValueError(f"seq len {q.shape[1]} not divisible by sp={sp}")
    if kv_head_axis == "unset":  # kv heads replicate when tp exceeds their count
        kv_head_axis = head_axis
    q_spec = P(batch_axis, seq_axis, head_axis, None)
    kv_spec = P(batch_axis, seq_axis, kv_head_axis, None)
    fn = jax.shard_map(
        partial(_ring_attention_local, axis_name=seq_axis, axis_size=sp),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(batch_axis)),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v, prompt_lens)
