"""The paged decode kernel under a LOWER bound a row (a sliding window held
as a band of pages a slot: ops/attention.paged_band_decode, models/afmoe.py)
against a dense masked softmax over the whole sequence: the bounded
work-list names only the pages of the span, the mask holds at both ends, a
band of R pages wraps, and the XLA fall-back gives the same; and the same
with a GROUP of the span's pages a grid step (PR 54), the span wrapping
inside a group and the bound falling mid-page. CPU, the Pallas
interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.attention import band_positions, paged_band_decode
from llmlb_tpu.ops.pallas_attention import decode_work_list, paged_flash_decode
from tests.ops.pools import grouped_work

PS, W, H, KV, D = 8, 16, 4, 2, 16
R = W // PS + 1  # pages of a band
LAYERS, SLOTS = 2, 3
# below the window, at it, past it; at and around page boundaries; not live
LENS = [1, 7, 8, 9, 15, 16, 17, 23, 24, 25, 40, 41, 47, 48, 100, 0]


def _sequence(key, n):
    """Keys and values [n, KV, D] of every position of one sequence."""
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (n, KV, D), jnp.float32),
            jax.random.normal(k2, (n, KV, D), jnp.float32))


def _dense(q, k, v, lo, n):
    """q [H, D] over positions lo <= p < n of k, v [N, KV, D]."""
    if n == 0:
        return np.zeros((H, D), np.float32)
    k = np.repeat(np.asarray(k[lo:n]), H // KV, axis=1)  # [S, H, D]
    v = np.repeat(np.asarray(v[lo:n]), H // KV, axis=1)
    s = np.einsum("hd,shd->hs", np.asarray(q), k) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True), v)


def _band(seqs, lens, layer):
    """The bands of `seqs` once each row is `lens` long: [LAYERS, (SLOTS +
    1) x R, PS, KV, D], row i in slot i, position p in page (p // PS) mod R
    of its slot and cell p mod PS; the other layer holds noise."""
    shape = (LAYERS, (SLOTS + 1) * R, PS, KV, D)
    pool_k = np.array(jax.random.normal(jax.random.PRNGKey(99), shape))
    pool_v = np.array(pool_k[::-1])
    for slot, ((k, v), n) in enumerate(zip(seqs, lens)):
        for p in range(n):  # later positions overwrite: the band wraps
            page = slot * R + p // PS % R
            pool_k[layer, page, p % PS] = np.asarray(k[p])
            pool_v[layer, page, p % PS] = np.asarray(v[p])
    return jnp.asarray(pool_k), jnp.asarray(pool_v)


@pytest.mark.parametrize("route", ["pallas", "xla"])
@pytest.mark.parametrize("at", range(0, len(LENS), SLOTS))
def test_a_band_decode_is_the_dense_softmax_over_the_window(at, route,
                                                            monkeypatch):
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", route)
    lens = (LENS[at:at + SLOTS] + [0] * SLOTS)[:SLOTS]
    keys = jax.random.split(jax.random.PRNGKey(at), SLOTS + 1)
    seqs = [_sequence(k, max(n, 1)) for k, n in zip(keys, lens)]
    q = jax.random.normal(keys[-1], (SLOTS, 1, H, D), jnp.float32)
    layer = at % LAYERS
    pool_k, pool_v = _band(seqs, lens, layer)
    tables = jnp.arange(SLOTS * R, dtype=jnp.int32).reshape(SLOTS, R)
    kv_lens = jnp.asarray(lens, jnp.int32)
    kv_from = jnp.maximum(kv_lens - W, 0)
    got = paged_band_decode(q, pool_k, pool_v, layer, tables, kv_lens, kv_from)
    for row, n in enumerate(lens):
        if n == 0 and route == "xla":
            continue  # finite and discarded (paged_attention_decode)
        want = _dense(q[row, 0], *seqs[row], max(n - W, 0), n)
        np.testing.assert_allclose(np.asarray(got[row, 0]), want, atol=2e-6)


@pytest.mark.parametrize("n", [n for n in LENS if n])
def test_the_bounded_work_list_names_the_spans_pages_alone(n):
    """A row's items are the logical pages that hold `max(n - W, 0) <= p <
    n`: ceil(n / PS) of them while n <= W, never more than R, each fetched
    from column (page mod R) of the band's table; a row that is not live
    beside it takes one item and reads nothing."""
    tables = jnp.asarray([[10, 11, 12], [20, 21, 22]], jnp.int32)
    lens = jnp.asarray([n, 0], jnp.int32)
    work = decode_work_list(tables, lens, page_size=PS,
                            kv_from=jnp.maximum(lens - W, 0))
    first, last = max(n - W, 0) // PS, (n - 1) // PS
    pages = list(range(first, last + 1))
    count = int(work.count)
    assert count == len(pages) + 1 and len(pages) <= R
    if n <= W:
        assert len(pages) == -(-n // PS)
    assert (last - first + 1) * PS <= W + PS or len(pages) == R
    assert np.asarray(work.row_of)[:count].tolist() == [0] * len(pages) + [1]
    assert np.asarray(work.page_of)[:len(pages)].tolist() == pages
    assert np.asarray(work.pool_page_of)[:count].tolist() == (
        [10 + p % R for p in pages] + [10 + last % R])


def test_without_a_bound_the_work_list_is_the_one_it_was():
    tables = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    lens = jnp.asarray([9, 0, 30], jnp.int32)
    plain = decode_work_list(tables, lens, page_size=PS)
    zero = decode_work_list(tables, lens, page_size=PS,
                            kv_from=jnp.zeros((3,), jnp.int32))
    for a, b in zip(plain, zero):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_bound_over_a_table_as_wide_as_the_context_reads_the_last_pages():
    """The same bound over an ordinary block table (no wrap: PPN covers the
    context): only the pages of the span are items."""
    n, ppn = 45, 8
    k, v = _sequence(jax.random.PRNGKey(5), n)
    pool = np.zeros((1, ppn + 1, PS, KV, D), np.float32)
    pool_k, pool_v = pool.copy(), pool.copy()
    for p in range(n):
        pool_k[0, 1 + p // PS, p % PS] = np.asarray(k[p])
        pool_v[0, 1 + p // PS, p % PS] = np.asarray(v[p])
    tables = jnp.arange(1, ppn + 1, dtype=jnp.int32)[None]
    q = jax.random.normal(jax.random.PRNGKey(6), (1, H, D), jnp.float32)
    lens, lo = jnp.asarray([n], jnp.int32), jnp.asarray([n - W], jnp.int32)
    work = decode_work_list(tables, lens, page_size=PS, kv_from=lo)
    assert int(work.count) == 3  # positions 29..44: pages 3, 4, 5
    got = paged_flash_decode(q, jnp.asarray(pool_k), jnp.asarray(pool_v), 0,
                             tables, lens, work=work, kv_from=lo)
    np.testing.assert_allclose(np.asarray(got[0]),
                               _dense(q[0], k, v, n - W, n), atol=2e-6)


def test_band_positions_are_the_last_positions_a_ring_holds():
    held = np.asarray(band_positions(jnp.asarray([0, 5, 24, 30]), 24))
    assert (held[0] == -1).all()
    assert held[1].tolist() == list(range(5)) + [-1] * 19
    assert held[2].tolist() == list(range(24))
    assert held[3].tolist() == [24, 25, 26, 27, 28, 29] + list(range(6, 24))


def test_a_sink_beside_a_bound_is_refused():
    z = jnp.zeros((1, 2, PS, KV, D))
    with pytest.raises(NotImplementedError, match="sink"):
        paged_flash_decode(jnp.zeros((1, H, D)), z, z, 0,
                           jnp.zeros((1, 2), jnp.int32),
                           jnp.ones((1,), jnp.int32), sink=jnp.zeros((H,)),
                           kv_from=jnp.zeros((1,), jnp.int32))


# --- a group of the span's pages a grid step (PR 54) -------------------------

WIDE_W = 32  # a window of four pages: a band of five
WIDE_R = WIDE_W // PS + 1


@pytest.mark.parametrize("group", [1, 2, 4, 8])
@pytest.mark.parametrize("kv", [2, 4, 8, 32])
def test_a_grouped_band_decode_wraps_inside_a_group(kv, group):
    """Rows whose span starts mid-page and wraps the band INSIDE a group
    (43 cells: pages 1..5 of a band of 5, the bound at cell 11; 77: pages
    5..9, columns 0..4 from the middle of the table), a short one, a span
    of one page and a row that is not live: each is the dense softmax over
    `kv_from <= p < len`, at the cells' KV heads and every group."""
    lens = [43, 77, 5, 40, 0]
    h = kv * (1 if kv == 32 else 2)
    keys = jax.random.split(jax.random.PRNGKey(kv + group), 3)
    seq_k = jax.random.normal(keys[0], (len(lens), 80, kv, D), jnp.float32)
    seq_v = jax.random.normal(keys[1], (len(lens), 80, kv, D), jnp.float32)
    q = jax.random.normal(keys[2], (len(lens), h, D), jnp.float32)
    pool_k = np.array(jax.random.normal(
        jax.random.PRNGKey(9), (LAYERS, len(lens) * WIDE_R + 1, PS, kv, D)))
    pool_v = pool_k[::-1].copy()
    tables = 1 + np.arange(len(lens) * WIDE_R, dtype=np.int32).reshape(
        len(lens), WIDE_R)[:, ::-1]  # a row's pages in no pool order
    for row, n in enumerate(lens):
        for p in range(n):  # later positions overwrite: the band wraps
            page = tables[row, p // PS % WIDE_R]
            pool_k[1, page, p % PS] = np.asarray(seq_k[row, p])
            pool_v[1, page, p % PS] = np.asarray(seq_v[row, p])
    kv_lens = jnp.asarray(lens, jnp.int32)
    kv_from = jnp.maximum(kv_lens - WIDE_W, 0)  # 11, 45: mid-page
    got = np.asarray(paged_flash_decode(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v), 1, jnp.asarray(tables),
        kv_lens, kv_from=kv_from, interpret=True,
        work=grouped_work(group, jnp.asarray(tables), kv_lens, PS,
                          kv_from=kv_from)))
    for row, n in enumerate(lens):
        if n == 0:
            assert not got[row].any()
            continue
        k = np.repeat(np.asarray(seq_k[row, max(n - WIDE_W, 0):n]),
                      h // kv, axis=1)
        v = np.repeat(np.asarray(seq_v[row, max(n - WIDE_W, 0):n]),
                      h // kv, axis=1)
        s = np.einsum("hd,shd->hs", np.asarray(q[row]), k) / np.sqrt(D)
        w = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hs,shd->hd", w / w.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(got[row], want, atol=3e-6)


@pytest.mark.parametrize("group", [2, 4])
def test_a_grouped_bounded_work_list_wraps_a_page_at_a_time(group):
    """A span of pages 5..9 over a band of 5: the items start at the page
    the bound falls in, G pages each, and every page is column p mod R of
    the table — the wrap falls inside the first group."""
    tables = jnp.asarray([[10, 11, 12, 13, 14], [20, 21, 22, 23, 24]],
                         jnp.int32)
    lens = jnp.asarray([77, 0], jnp.int32)
    work = decode_work_list(tables, lens, page_size=PS,
                            kv_from=jnp.maximum(lens - WIDE_W, 0),
                            group=group)
    items = -(-5 // group)
    assert int(work.count) == items + 1
    assert np.asarray(work.page_of)[:items].tolist() == list(
        range(5, 10, group))
    pool = np.asarray(work.pool_page_of).reshape(-1, group)[:items + 1]
    want = [10 + p % 5 for p in range(5, 10)]  # 10, 11, 12, 13, 14
    assert pool[:items].reshape(-1)[:5].tolist() == want
    # the short last group and the row that is not live repeat a page
    assert set(pool.reshape(-1)[5:].tolist()) <= set(want)
    np.testing.assert_array_equal(pool[items], pool[items - 1])
