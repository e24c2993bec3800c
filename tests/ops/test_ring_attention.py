"""Ring attention (sequence-parallel prefill) vs the dense XLA reference.

Strategy per SURVEY.md §4: multi-device behavior tested on the virtual 8-device
CPU mesh — the ring (shard_map + ppermute) path must match dense causal GQA
attention and the dense full-model prefill bit-for-bit up to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.attention import gqa_attention_prefill
from llmlb_tpu.ops.ring_attention import ring_prefill_attention
from llmlb_tpu.parallel.mesh import MeshConfig, build_mesh


def _rand_qkv(key, b, t, h, kh, d):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, kh, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, kh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_matches_dense_mha(sp, cpu_mesh_devices):
    mesh = build_mesh(MeshConfig(dp=1, sp=sp, tp=1), devices=cpu_mesh_devices[:sp])
    b, t, h, d = 2, 64, 4, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, t, h, h, d)
    lens = jnp.array([64, 37], jnp.int32)  # one full, one ragged (not chunk-aligned)

    dense = gqa_attention_prefill(q, k, v, lens)
    ring = ring_prefill_attention(q, k, v, lens, mesh)
    valid = np.arange(t)[None, :, None, None] < np.asarray(lens)[:, None, None, None]
    np.testing.assert_allclose(
        np.where(valid, np.asarray(ring), 0.0),
        np.where(valid, np.asarray(dense), 0.0),
        rtol=2e-5, atol=2e-5,
    )


def test_ring_matches_dense_gqa_with_tp(cpu_mesh_devices):
    """GQA (h=8 over kh=4) with heads tp-sharded and sequence sp-sharded."""
    mesh = build_mesh(MeshConfig(dp=1, sp=4, tp=2), devices=cpu_mesh_devices)
    b, t, h, kh, d = 2, 32, 8, 4, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, t, h, kh, d)
    lens = jnp.array([32, 9], jnp.int32)

    dense = gqa_attention_prefill(q, k, v, lens)
    ring = ring_prefill_attention(q, k, v, lens, mesh)
    valid = np.arange(t)[None, :, None, None] < np.asarray(lens)[:, None, None, None]
    np.testing.assert_allclose(
        np.where(valid, np.asarray(ring), 0.0),
        np.where(valid, np.asarray(dense), 0.0),
        rtol=2e-5, atol=2e-5,
    )


def test_ring_with_dp_batch_sharding(cpu_mesh_devices):
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2), devices=cpu_mesh_devices)
    b, t, h, kh, d = 4, 16, 4, 2, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, t, h, kh, d)
    lens = jnp.array([16, 11, 3, 16], jnp.int32)

    dense = gqa_attention_prefill(q, k, v, lens)
    ring = ring_prefill_attention(q, k, v, lens, mesh)
    valid = np.arange(t)[None, :, None, None] < np.asarray(lens)[:, None, None, None]
    np.testing.assert_allclose(
        np.where(valid, np.asarray(ring), 0.0),
        np.where(valid, np.asarray(dense), 0.0),
        rtol=2e-5, atol=2e-5,
    )


def test_context_parallel_prefill_matches_dense(cpu_mesh_devices):
    """Full-model sequence-parallel prefill == dense prefill (logits and KV)."""
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.models import llama
    from llmlb_tpu.models.llama import (
        init_params, make_context_parallel_prefill, prefill_into_pages,
    )
    from tests.support import identity_kv_pages, kv_rows

    cfg = get_preset("debug-tiny")
    params = init_params(cfg, jax.random.PRNGKey(3))
    mesh = build_mesh(MeshConfig(dp=1, sp=4, tp=2), devices=cpu_mesh_devices)

    b, t = 2, 32
    ids = jax.random.randint(jax.random.PRNGKey(4), (b, t), 0, cfg.vocab_size)
    lens = jnp.array([32, 21], jnp.int32)

    cache_k, cache_v, tables = identity_kv_pages(llama, cfg, b, t)
    dense_logits, pool_k, pool_v = prefill_into_pages(
        params, cfg, ids, lens, tables, cache_k, cache_v
    )
    dense_k, dense_v = kv_rows(pool_k, tables, t), kv_rows(pool_v, tables, t)

    cp_prefill = make_context_parallel_prefill(cfg, mesh)
    cp_logits, k_all, v_all = cp_prefill(params, ids, lens)

    np.testing.assert_allclose(
        np.asarray(cp_logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )
    # KV written during dense prefill == KV returned by the cp path ([L,B,T,K,D])
    valid = np.arange(t)[None, None, :, None, None] < np.asarray(lens)[None, :, None, None, None]
    np.testing.assert_allclose(
        np.where(valid, np.asarray(k_all), 0.0),
        np.where(valid, np.asarray(dense_k), 0.0),
        rtol=2e-5, atol=2e-5,
    )
    np.testing.assert_allclose(
        np.where(valid, np.asarray(v_all), 0.0),
        np.where(valid, np.asarray(dense_v), 0.0),
        rtol=2e-5, atol=2e-5,
    )


def test_mesh_config_sp_resolution():
    cfg = MeshConfig(dp=2, tp=-1, sp=2).resolve(8)
    assert (cfg.dp, cfg.sp, cfg.tp) == (2, 2, 2)
    cfg = MeshConfig(dp=1, tp=1, sp=-1).resolve(8)
    assert cfg.sp == 8
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=-1, sp=1).resolve(8)
