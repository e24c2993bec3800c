"""Heads narrower than the chip's 128 lanes (ops/attention.lane_pack, PR
55): a page pool of 8 KV heads of 64 held as 4 rows of 128 — two heads side
by side, no lane padding — and attended over by the paged kernels as they
are (interpret mode), given queries that are zero outside their own KV
head's lanes, against the XLA route over the same numbers head by head; and
`flash_prefill` at heads of 64 as it is."""

import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops import attention as ops
from llmlb_tpu.ops.pallas_attention import (
    decode_group,
    flash_prefill,
    paged_flash_decode,
    paged_flash_extend,
)

LAYERS, PS, H, K, D = 2, 16, 32, 8, 64


@pytest.mark.parametrize("num_kv,head_dim,want", [
    (8, 64, 2), (4, 64, 2), (1, 64, 1), (3, 64, 1), (8, 128, 1), (2, 192, 1),
    (8, 32, 4), (2, 16, 2), (16, 16, 8), (6, 32, 2)])
def test_as_many_heads_share_a_row_as_fill_the_lanes(num_kv, head_dim, want):
    assert ops.lane_pack(num_kv, head_dim) == want


def _pool(seed, b, pages):
    r = np.random.default_rng(seed)
    p = b * pages + 1
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    tables = jnp.asarray(r.permutation(np.arange(1, p)).reshape(b, pages),
                         jnp.int32)
    return r, f, f(LAYERS, p, PS, K, D), f(LAYERS, p, PS, K, D), tables


def test_packing_keeps_the_numbers_where_they_lie():
    _r, f, kp, _vp, _tables = _pool(0, 2, 2)
    packed = ops.pack_kv(kp, 2)
    assert packed.shape == (*kp.shape[:3], K // 2, 2 * D)
    assert (np.asarray(packed).ravel() == np.asarray(kp).ravel()).all()
    q = f(2, 3, H, D)
    wide = ops.pack_queries(q, K, 2)
    assert wide.shape == (2, 3, H, 2 * D)
    # heads 0-3 are KV head 0's (the left lanes), 4-7 KV head 1's (the right)
    np.testing.assert_allclose(np.asarray(wide[:, :, 0, :D]),
                               np.asarray(q[:, :, 0]) * 2**0.5, rtol=1e-6)
    assert (np.asarray(wide[:, :, 0, D:]) == 0).all()
    assert (np.asarray(wide[:, :, 5, :D]) == 0).all()
    back = ops.unpack_heads(wide, K, 2)
    np.testing.assert_allclose(np.asarray(back), np.asarray(q) * 2**0.5,
                               rtol=1e-6)


@pytest.mark.parametrize("lens", [[37, 0, 64, 1], [16, 17, 15, 48]])
def test_decode_over_packed_rows_is_attention_head_by_head(lens):
    r, f, kp, vp, tables = _pool(1, 4, 4)
    lens = jnp.asarray(lens, jnp.int32)
    q = f(4, 1, H, D)
    want = ops.gqa_attention_decode(
        q, ops.gather_kv_pages(kp, tables, layer=1),
        ops.gather_kv_pages(vp, tables, layer=1), lens)
    got = ops.unpack_heads(paged_flash_decode(
        ops.pack_queries(q, K, 2)[:, 0], ops.pack_kv(kp, 2),
        ops.pack_kv(vp, 2), 1, tables, lens, interpret=True)[:, None], K, 2)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert (np.asarray(got)[~live] == 0).all()
    del r


def test_extend_over_packed_rows_is_attention_head_by_head():
    r, f, kp, vp, tables = _pool(2, 3, 4)
    t = 8
    starts = jnp.asarray([0, 13, 40], jnp.int32)
    chunk = jnp.asarray([8, 5, 8], jnp.int32)
    q = f(3, t, H, D)
    pos = starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    want = ops.gqa_attention_extend(
        q, ops.gather_kv_pages(kp, tables, layer=0),
        ops.gather_kv_pages(vp, tables, layer=0), pos)
    got = ops.unpack_heads(paged_flash_extend(
        ops.pack_queries(q, K, 2), ops.pack_kv(kp, 2), ops.pack_kv(vp, 2), 0,
        tables, starts, chunk, interpret=True), K, 2)
    for row, n in enumerate(np.asarray(chunk)):
        np.testing.assert_allclose(np.asarray(got)[row, :n],
                                   np.asarray(want)[row, :n], rtol=2e-5,
                                   atol=2e-5)
    del r


def test_a_fresh_prompt_at_heads_of_64_through_the_prefill_kernel():
    _r, f, *_ = _pool(3, 1, 1)
    q, k, v = f(2, 48, H, D), f(2, 48, K, D), f(2, 48, K, D)
    lens = jnp.asarray([48, 19], jnp.int32)
    got = flash_prefill(q, k, v, lens, block_q=16, block_k=16, interpret=True)
    want = ops._prefill_einsum(q, k, v, lens)
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(got)[row, :n],
                                   np.asarray(want)[row, :n], rtol=2e-5,
                                   atol=2e-5)


def test_the_group_of_pages_a_decode_step_takes_at_the_packed_shape():
    """8 heads of 64 packed are 4 rows of 128: a page is 131,072 elements
    of keys and values, so a grid step takes 4 (Trinity-Mini's global
    layers' group); unpacked the rule would read the same at 64 lanes."""
    assert decode_group(128 * 4 * (128 + 128), 16) == 4
    assert decode_group(128 * 8 * (64 + 64), 16) == 4
    assert decode_group(128 * 4 * (128 + 128), 2) == 2
