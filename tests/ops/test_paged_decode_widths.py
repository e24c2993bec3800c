"""The paged decode kernels at what a window-and-global decoder asks of them
(models/mimo_v2.py, PR 45), in interpret mode against plain einsums:
`paged_flash_decode` with values narrower than keys and with a sink a head
in the softmax's denominator, over a pool of pages and over a RING a row (a
pool of one page a row, its table the rows' slots); and `paged_flat_decode`,
GQA over a pool without a head axis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.pallas_attention import paged_flash_decode, paged_flat_decode

LAYERS, PAGES, PS = 2, 9, 8


def _pools(key, kv, d, dv):
    kk, kv_ = jax.random.split(key)
    return (jax.random.normal(kk, (LAYERS, PAGES, PS, kv, d), jnp.float32),
            jax.random.normal(kv_, (LAYERS, PAGES, PS, kv, dv), jnp.float32))


def _dense(q, k, v, lens, sink=None):
    """q [B, H, D] over the first `lens` of k [B, S, K, D], v [B, S, K, Dv]:
    softmax(q.k / sqrt(D)) v with `sink` [H] in the denominator."""
    b, h, d = q.shape
    g = h // k.shape[2]
    k, v = (np.repeat(np.asarray(a, np.float64), g, axis=2) for a in (k, v))
    s = np.einsum("bhd,bshd->bhs", np.asarray(q, np.float64), k) / np.sqrt(d)
    seen = np.arange(k.shape[1])[None, None, :] < np.asarray(lens)[:, None, None]
    s = np.where(seen, s, -np.inf)
    m = s.max(-1, keepdims=True)
    if sink is not None:
        m = np.maximum(m, np.asarray(sink, np.float64)[None, :, None])
    m = np.where(np.isfinite(m), m, 0.0)
    p = np.exp(s - m)
    denom = p.sum(-1, keepdims=True)
    if sink is not None:
        denom = denom + np.exp(np.asarray(sink, np.float64)[None, :, None] - m)
    denom = np.where(denom == 0, 1.0, denom)
    return np.einsum("bhs,bshd->bhd", p / denom, v)


def _rows(pool, layer, tables):
    got = np.asarray(pool)[layer][np.asarray(tables)]  # [B, N, PS, K, D]
    return got.reshape(got.shape[0], -1, *got.shape[3:])


@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("h,kv,d,dv", [(8, 2, 24, 16), (8, 4, 16, 16),
                                       (4, 4, 24, 8)])
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_flash_decode_narrow_values_and_a_sink(h, kv, d, dv, sink,
                                                     layer):
    key = jax.random.PRNGKey(h * 100 + d + layer)
    k_pages, v_pages = _pools(key, kv, d, dv)
    q = jax.random.normal(jax.random.fold_in(key, 1), (3, h, d), jnp.float32)
    tables = jnp.asarray([[3, 1, 7], [2, 8, 4], [5, 6, 0]], jnp.int32)
    lens = jnp.asarray([19, 0, 8], jnp.int32)  # a row not live among them
    sinks = (jax.random.normal(jax.random.fold_in(key, 2), (h,), jnp.float32)
             if sink else None)
    got = paged_flash_decode(q, k_pages, v_pages, layer, tables, lens,
                             sink=sinks, interpret=True)
    assert got.shape == (3, h, dv)
    want = _dense(q, _rows(k_pages, layer, tables),
                  _rows(v_pages, layer, tables), lens, sinks)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert not np.asarray(got)[1].any()  # a row of length 0 is zeros


def test_a_sink_takes_weight_and_no_value():
    """With one live cell the output is v x 1 / (1 + exp(sink - s)): the
    sink's share of the softmax goes nowhere."""
    k_pages, v_pages = _pools(jax.random.PRNGKey(0), 1, 16, 16)
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16), jnp.float32)
    tables = jnp.asarray([[4]], jnp.int32)
    sinks = jnp.asarray([0.5, -2.0], jnp.float32)
    got = np.asarray(paged_flash_decode(
        q, k_pages, v_pages, 0, tables, jnp.asarray([1], jnp.int32),
        sink=sinks, interpret=True))[0]
    s = np.asarray(q)[0] @ np.asarray(k_pages)[0, 4, 0, 0] / 4.0
    share = 1.0 / (1.0 + np.exp(np.asarray(sinks) - s))
    np.testing.assert_allclose(
        got, share[:, None] * np.asarray(v_pages)[0, 4, 0, 0][None], atol=1e-5)


@pytest.mark.parametrize("lens", [[8, 3, 0, 5], [1, 8, 8, 2]])
def test_a_ring_a_row_is_a_pool_of_one_page_a_row(lens):
    """[L, slots, cells, K, D] under a table [B, 1] of the rows' slots,
    `pages=1`: each row attends over the first `lens` cells of its own slot
    (models/mimo_v2.py's window layers), the order of rows and slots
    free."""
    key = jax.random.PRNGKey(7)
    ring_k, ring_v = _pools(key, 2, 24, 16)  # 9 slots of 8 cells
    q = jax.random.normal(jax.random.fold_in(key, 3), (4, 4, 24), jnp.float32)
    slots = jnp.asarray([6, 2, 8, 0], jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    sinks = jax.random.normal(jax.random.fold_in(key, 4), (4,), jnp.float32)
    got = paged_flash_decode(q, ring_k, ring_v, 1, slots[:, None], lens,
                             pages=1, sink=sinks, name="paged_window_decode",
                             interpret=True)
    want = _dense(q, np.asarray(ring_k)[1][np.asarray(slots)],
                  np.asarray(ring_v)[1][np.asarray(slots)], lens, sinks)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("h,kv,d,dv", [(8, 4, 24, 16), (8, 2, 16, 16),
                                       (4, 1, 24, 8)])
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_flat_decode_matches_dense(h, kv, d, dv, layer):
    """A pool without a head axis, a cell one row of its KV heads side by
    side: the same attention as over [.., K, D] pages."""
    key = jax.random.PRNGKey(h + kv + d + layer)
    k_pages, v_pages = _pools(key, kv, d, dv)
    q = jax.random.normal(jax.random.fold_in(key, 1), (3, h, d), jnp.float32)
    tables = jnp.asarray([[3, 1, 7], [2, 8, 4], [5, 6, 0]], jnp.int32)
    lens = jnp.asarray([24, 0, 9], jnp.int32)
    flat = (LAYERS, PAGES, PS, -1)
    got = paged_flat_decode(q, k_pages.reshape(flat), v_pages.reshape(flat),
                            layer, tables, lens, num_kv=kv, interpret=True)
    assert got.shape == (3, h, dv)
    want = _dense(q, _rows(k_pages, layer, tables),
                  _rows(v_pages, layer, tables), lens)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert not np.asarray(got)[1].any()
    # `pages` bounds the sweep as paged_flash_decode's does
    short = paged_flat_decode(q, k_pages.reshape(flat), v_pages.reshape(flat),
                              layer, tables, lens, num_kv=kv, pages=2,
                              interpret=True)
    want = _dense(q, _rows(k_pages, layer, tables[:, :2]),
                  _rows(v_pages, layer, tables[:, :2]),
                  np.minimum(np.asarray(lens), 2 * PS))
    np.testing.assert_allclose(np.asarray(short), want, atol=2e-5)
