"""The paged decode kernels at what a window-and-global decoder asks of them
(models/mimo_v2.py, PR 45), in interpret mode against plain einsums:
`paged_flash_decode` with values narrower than keys and with a sink a head
in the softmax's denominator, over a pool of pages and over a RING a row (a
pool of one page a row, its table the rows' slots); `paged_flat_decode`,
GQA over a pool without a head axis; `paged_flash_decode` at every width of
page the cells serve taking a GROUP of a row's pages a grid step (PR 54),
with the work-list's invariants under a group; and the two kernels over
pools without a head axis, `paged_latent_decode` and `paged_flat_decode`,
taking a group too (PR 58)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.pallas_attention import (
    decode_work_list,
    paged_flash_decode,
    paged_flat_decode,
    paged_latent_decode,
)
from tests.ops.pools import grouped_work

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


@pytest.mark.parametrize("group", [None, 2], ids=["by_shape", "group2"])
@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("h,kv,d,dv", [(8, 2, 24, 16), (8, 4, 16, 16),
                                       (4, 4, 24, 8)])
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_flash_decode_narrow_values_and_a_sink(h, kv, d, dv, sink,
                                                     layer, group):
    key = jax.random.PRNGKey(h * 100 + d + layer)
    k_pages, v_pages = _pools(key, kv, d, dv)
    q = jax.random.normal(jax.random.fold_in(key, 1), (3, h, d), jnp.float32)
    tables = jnp.asarray([[3, 1, 7], [2, 8, 4], [5, 6, 0]], jnp.int32)
    lens = jnp.asarray([19, 0, 8], jnp.int32)  # a row not live among them
    sinks = (jax.random.normal(jax.random.fold_in(key, 2), (h,), jnp.float32)
             if sink else None)
    got = paged_flash_decode(q, k_pages, v_pages, layer, tables, lens,
                             sink=sinks, interpret=True,
                             work=grouped_work(group, tables, lens, PS))
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


@pytest.mark.parametrize("group", [None, 1, 2, 3, 4],
                         ids=lambda g: "by_shape" if g is None else f"group{g}")
@pytest.mark.parametrize("h,kv,d,dv", [(8, 4, 24, 16), (8, 2, 16, 16),
                                       (4, 1, 24, 8)])
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_flat_decode_matches_dense(h, kv, d, dv, layer, group):
    """A pool without a head axis, a cell one row of its KV heads side by
    side: the same attention as over [.., K, D] pages, whatever group of a
    row's pages a grid step takes."""
    key = jax.random.PRNGKey(h + kv + d + layer)
    k_pages, v_pages = _pools(key, kv, d, dv)
    q = jax.random.normal(jax.random.fold_in(key, 1), (3, h, d), jnp.float32)
    tables = jnp.asarray([[3, 1, 7], [2, 8, 4], [5, 6, 0]], jnp.int32)
    lens = jnp.asarray([24, 0, 9], jnp.int32)
    flat = (LAYERS, PAGES, PS, -1)
    got = paged_flat_decode(q, k_pages.reshape(flat), v_pages.reshape(flat),
                            layer, tables, lens, num_kv=kv, interpret=True,
                            work=grouped_work(group, tables, lens, PS))
    assert got.shape == (3, h, dv)
    want = _dense(q, _rows(k_pages, layer, tables),
                  _rows(v_pages, layer, tables), lens)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert not np.asarray(got)[1].any()
    # `pages` bounds the sweep as paged_flash_decode's does
    short = paged_flat_decode(q, k_pages.reshape(flat), v_pages.reshape(flat),
                              layer, tables, lens, num_kv=kv, pages=2,
                              interpret=True,
                              work=grouped_work(group, tables, lens, PS, 2))
    want = _dense(q, _rows(k_pages, layer, tables[:, :2]),
                  _rows(v_pages, layer, tables[:, :2]),
                  np.minimum(np.asarray(lens), 2 * PS))
    np.testing.assert_allclose(np.asarray(short), want, atol=2e-5)


# --- a group of a row's pages a grid step (PR 54) ----------------------------

GROUPS = [1, 2, 4, 8]
WIDE_PAGES, WIDE_PPN = 40, 7  # a pool and a table for rows of up to 7 pages
# name -> (lengths, `pages` bucket): cells a page PS = 8
GROUP_CASES = {
    # ragged rows, one of length 0 among them, pages no multiple of a group
    "ragged": ([19, 0, 56, 1, 33], None),
    "one_live_row": ([0, 0, 37, 0], None),
    # a bucket smaller than a group of 4 or 8, cutting two rows short
    "bucket_of_three_pages": ([30, 9, 0, 56], 3),
    "bucket_of_one_page": ([30, 9, 56], 1),
}


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("kv", [2, 4, 8, 32])
@pytest.mark.parametrize("case", sorted(GROUP_CASES))
def test_a_group_of_pages_a_grid_step_is_the_dense_softmax(case, kv, group):
    """ONE product of every query head with a group's [G*PS*K, D] rows: at
    the cells' KV heads (Nemotron's 2, Trinity's 4, Mistral's 8, OLMo's 32
    stored) and every group, a row attends over its first min(len, pages x
    PS) cells exactly, whatever of the last group is missing."""
    lens, pages = GROUP_CASES[case]
    h = kv * (1 if kv == 32 else 2)
    key = jax.random.PRNGKey(kv * 10 + group)
    kk, kv_, kq = jax.random.split(key, 3)
    k_pages = jax.random.normal(kk, (LAYERS, WIDE_PAGES, PS, kv, 16))
    v_pages = jax.random.normal(kv_, (LAYERS, WIDE_PAGES, PS, kv, 16))
    q = jax.random.normal(kq, (len(lens), h, 16), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(kv).permutation(WIDE_PAGES)[
        :len(lens) * WIDE_PPN].reshape(len(lens), WIDE_PPN), jnp.int32)
    kv_lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(paged_flash_decode(
        q, k_pages, v_pages, 1, tables, kv_lens, pages=pages, interpret=True,
        work=grouped_work(group, tables, kv_lens, PS, pages)))
    sweep = WIDE_PPN if pages is None else pages
    want = _dense(q, _rows(k_pages, 1, tables[:, :sweep]),
                  _rows(v_pages, 1, tables[:, :sweep]),
                  np.minimum(lens, sweep * PS))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not got[~live].any()


# --- … and of the pools without a head axis (PR 58) ---------------------------

HEADLESS_GROUPS = [1, 2, 3, 4]
# name -> the `pages` bucket over a table 7 pages wide
HEADLESS_SWEEPS = {"whole_table": None, "bucket_of_three_pages": 3,
                   "bucket_of_one_page": 1}
HEADLESS_PAGES = 48  # a pool for six rows of a table's width


def _headless_step(group, seed):
    """Rows of 0 cells, of one cell, of exactly a group of pages, of a group
    and one page (one cell into it), one ragged and one the table's width:
    (tables [6, 7], lengths) over a pool of HEADLESS_PAGES pages."""
    lens = [0, 1, group * PS, group * PS + 1, 19, WIDE_PPN * PS]
    tables = np.random.default_rng(seed).permutation(HEADLESS_PAGES)[
        :len(lens) * WIDE_PPN].reshape(len(lens), WIDE_PPN)
    return jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32)


def _swept(pool, tables, lens, pages):
    """One layer's cells [B, S, ...] of the pages a row is read to, and the
    lengths within them."""
    sweep = WIDE_PPN if pages is None else pages
    got = np.asarray(pool, np.float64)[np.asarray(tables)[:, :sweep]]
    return (got.reshape(got.shape[0], -1, *got.shape[3:]),
            np.minimum(np.asarray(lens), sweep * PS))


@pytest.mark.parametrize("group", HEADLESS_GROUPS)
@pytest.mark.parametrize("heads", [32, 64])
@pytest.mark.parametrize("sweep", sorted(HEADLESS_SWEEPS))
def test_a_group_of_latent_pages_a_grid_step_is_the_dense_softmax(
        sweep, heads, group):
    """Every head over the same [G*PS, C] latent tiles, scores in two
    products and the tiles the values, at kanana-2-30b-a3b's 32 heads and
    longcat-flash-omni's 64, on the pool's second layer: a row attends over
    its first min(len, pages x PS) cells exactly, whatever of its last group
    is missing, and a row of 0 cells is zeros."""
    pages, c_dim, r_dim, scale = HEADLESS_SWEEPS[sweep], 64, 16, 0.11
    tables, lens = _headless_step(group, heads + group)
    kc, kr, kq, kp = jax.random.split(jax.random.PRNGKey(heads + group), 4)
    c_pages = jax.random.normal(kc, (LAYERS, HEADLESS_PAGES, PS, c_dim))
    r_pages = jax.random.normal(kr, (LAYERS, HEADLESS_PAGES, PS, r_dim))
    q_abs = jax.random.normal(kq, (len(lens), heads, c_dim), jnp.float32)
    q_rope = jax.random.normal(kp, (len(lens), heads, r_dim), jnp.float32)
    got = np.asarray(paged_latent_decode(
        q_abs, q_rope, c_pages, r_pages, 1, tables, lens, scale=scale,
        pages=pages, interpret=True,
        work=grouped_work(group, tables, lens, PS, pages)))
    c, seen = _swept(c_pages[1], tables, lens, pages)
    r, _ = _swept(r_pages[1], tables, lens, pages)
    s = (np.einsum("bhc,bsc->bhs", np.asarray(q_abs, np.float64), c)
         + np.einsum("bhr,bsr->bhs", np.asarray(q_rope, np.float64), r)) * scale
    s = np.where(np.arange(c.shape[1])[None, None, :] < seen[:, None, None],
                 s, -np.inf)
    live = np.asarray(lens) > 0
    p = np.exp(s[live] - s[live].max(-1, keepdims=True))
    want = np.einsum("bhs,bsc->bhc", p / p.sum(-1, keepdims=True), c[live])
    np.testing.assert_allclose(got[live], want, atol=2e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("group", HEADLESS_GROUPS)
@pytest.mark.parametrize("h,kv,d,dv", [(64, 4, 192, 128), (8, 2, 24, 16)],
                         ids=["mimo-64-on-4x192-4x128", "tiny"])
@pytest.mark.parametrize("sweep", sorted(HEADLESS_SWEEPS))
def test_a_group_of_flat_pages_a_grid_step_is_the_dense_softmax(
        sweep, h, kv, d, dv, group):
    """ONE product of the widened queries with a group's [G*PS, K*D] rows,
    at mimo-v2-5's 64 heads on 4 x 192 keys and 4 x 128 values, on the
    pool's second layer: the dense attention over a row's first min(len,
    pages x PS) cells, and zeros for a row of 0 cells."""
    pages = HEADLESS_SWEEPS[sweep]
    tables, lens = _headless_step(group, h + group)
    kk, kv_, kq = jax.random.split(jax.random.PRNGKey(h + group), 3)
    k_pages = jax.random.normal(kk, (LAYERS, HEADLESS_PAGES, PS, kv, d))
    v_pages = jax.random.normal(kv_, (LAYERS, HEADLESS_PAGES, PS, kv, dv))
    q = jax.random.normal(kq, (len(lens), h, d), jnp.float32)
    flat = (LAYERS, HEADLESS_PAGES, PS, -1)
    got = np.asarray(paged_flat_decode(
        q, k_pages.reshape(flat), v_pages.reshape(flat), 1, tables, lens,
        num_kv=kv, pages=pages, interpret=True,
        work=grouped_work(group, tables, lens, PS, pages)))
    k, seen = _swept(k_pages[1], tables, lens, pages)
    v, _ = _swept(v_pages[1], tables, lens, pages)
    live = np.asarray(lens) > 0
    want = _dense(q, k, v, seen)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("kernel", ["latent", "flat"])
def test_a_headless_kernel_called_alone_builds_its_shapes_own_group(kernel):
    """Without `work` the latent and the flat kernel build their list by
    `decode_group` of what a page holds in both pools: the same answer as
    a list of one page an item gives, to rounding."""
    tables, lens = _headless_step(2, 3)
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    if kernel == "latent":
        pools = [jax.random.normal(k, (LAYERS, HEADLESS_PAGES, PS, w))
                 for k, w in zip(keys, (64, 16))]
        queries = [jax.random.normal(k, (len(lens), 8, w), jnp.float32)
                   for k, w in zip(keys[2:], (64, 16))]
        call = lambda work: paged_latent_decode(  # noqa: E731
            *queries, *pools, 1, tables, lens, scale=0.2, interpret=True,
            work=work)
    else:
        pools = [jax.random.normal(k, (LAYERS, HEADLESS_PAGES, PS, 2 * w))
                 for k, w in zip(keys, (24, 16))]
        q = jax.random.normal(keys[2], (len(lens), 8, 24), jnp.float32)
        call = lambda work: paged_flat_decode(  # noqa: E731
            q, *pools, 1, tables, lens, num_kv=2, interpret=True, work=work)
    np.testing.assert_allclose(
        np.asarray(call(None)),
        np.asarray(call(grouped_work(1, tables, lens, PS))), atol=2e-5)


def _work_list_pr52(block_tables, kv_lens, *, page_size, pages=None,
                    kv_from=None):
    """decode_work_list as PR 52 left it (one page an item), word for word:
    (count, row_of, page_of, pool_page_of)."""
    b, ppn = block_tables.shape
    sweep = ppn if pages is None else max(1, min(pages, ppn))
    lens = kv_lens.astype(jnp.int32)
    ends = -(-lens // page_size)
    first = None
    if kv_from is not None:
        first = jnp.clip(kv_from.astype(jnp.int32), 0,
                         jnp.maximum(lens - 1, 0)) // page_size
        ends = ends - first
    per_row = jnp.clip(ends, 1, sweep)
    end = jnp.cumsum(per_row)
    item = jnp.arange(b * sweep, dtype=jnp.int32)
    ended = item[:, None] >= end[None, :]
    row_of = jnp.minimum(jnp.sum(ended, axis=1, dtype=jnp.int32), b - 1)
    page_of = jnp.clip(
        item - jnp.sum(jnp.where(ended, per_row[None, :], 0), axis=1),
        0, sweep - 1)
    column = page_of
    if first is not None:
        page_of = first[row_of] + page_of
        column = page_of % ppn
    reads = jax.lax.cummax(jnp.where(lens[row_of] > 0, item, 0))
    pool_page_of = block_tables.astype(jnp.int32)[row_of, column][reads]
    return end[-1], row_of, page_of, pool_page_of


def _random_step(seed):
    rng = np.random.default_rng(seed)
    b, ppn = int(rng.integers(1, 7)), int(rng.integers(1, 10))
    tables = rng.integers(0, 64, (b, ppn)).astype(np.int32)
    lens = (rng.integers(0, ppn * PS + 1, (b,))
            * (rng.random(b) > 0.3)).astype(np.int32)
    pages = [None, max(1, ppn - 2)][seed % 2]
    bound = [None, np.maximum(lens - 2 * PS - 3, 0)][seed // 2 % 2]
    return tables, lens, pages, bound


@pytest.mark.parametrize("seed", range(8))
def test_at_a_group_of_one_the_work_list_is_the_arrays_it_was(seed):
    tables, lens, pages, bound = _random_step(seed)
    kw = dict(page_size=PS, pages=pages,
              kv_from=None if bound is None else jnp.asarray(bound))
    now = decode_work_list(jnp.asarray(tables), jnp.asarray(lens), **kw)
    was = _work_list_pr52(jnp.asarray(tables), jnp.asarray(lens), **kw)
    assert now.group == 1
    for a, b in zip(now, was):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("group", GROUPS[1:] + [3])
@pytest.mark.parametrize("seed", range(8))
def test_a_grouped_work_list_holds_every_live_page_once(seed, group):
    """`count` = the sum of ceil(pages / G) over the live rows + the rows
    that are not live; a row's items name its pages in order, G an item,
    from the page its lower bound falls in and column p mod PPN of the
    table; an entry that stands for no page repeats what its block fetched
    last, or (before it fetched any) the step's first live page: no fetch,
    and never a page no live row reads beside one that a live row does. So
    the G blocks fetch (their index changes, and their first) no more than
    the live pages and one page each."""
    tables, lens, pages, bound = _random_step(seed)
    b, ppn = tables.shape
    sweep = ppn if pages is None else pages
    work = decode_work_list(
        jnp.asarray(tables), jnp.asarray(lens), page_size=PS, pages=pages,
        kv_from=None if bound is None else jnp.asarray(bound), group=group)
    assert work.group == group
    assert work.row_of.shape == (b * -(-sweep // group),)
    count = int(work.count)
    row_of, page_of = np.asarray(work.row_of), np.asarray(work.page_of)
    pool = np.asarray(work.pool_page_of).reshape(-1, group)
    items, held = [], [None] * group  # what each of the G blocks holds
    first_live = None  # the first page of the first live row
    for row, n in enumerate(lens):
        first = 0 if bound is None else min(bound[row], max(n - 1, 0)) // PS
        row_pages = min(max(-(-n // PS) - first, 1), sweep)
        for start in range(0, row_pages, group):
            entries = []
            for g in range(group):
                real = n > 0 and start + g < row_pages
                if real:
                    held[g] = int(tables[row, (first + start + g) % ppn])
                    if first_live is None:
                        first_live = held[g]
                entries.append((real, held[g]))
            items.append((row, first + start, entries))
    assert count == len(items) == sum(
        1 if n == 0 else -(-min(
            -(-n // PS) - (0 if bound is None else min(bound[r], n - 1) // PS),
            sweep) // group) for r, n in enumerate(lens))
    for i, (row, start, entries) in enumerate(items):
        assert (row_of[i], page_of[i]) == (row, start)
        for g, (real, page) in enumerate(entries):
            if real or page is not None:
                assert pool[i, g] == page, (i, g)
            elif g and first_live is not None:  # nothing fetched yet
                assert pool[i, g] == first_live
        if i and lens[row] == 0:
            np.testing.assert_array_equal(pool[i], pool[i - 1])
    live_pages = sum(real for _, _, entries in items for real, _ in entries)
    assert _pages_fetched(pool[:count]) <= live_pages + group


def _pages_fetched(pool):
    """What the grid's pipeline fetches over items [W, G]: a block is
    fetched at the first item and wherever its index changes."""
    return pool.shape[1] + int(np.count_nonzero(np.diff(pool, axis=0)))


@pytest.mark.parametrize("group", GROUPS[1:])
def test_rows_shorter_than_the_group_fetch_their_pages_once(group):
    """Rows of one page each, a dead row before them: the blocks no row
    fills hold ONE page of the step throughout (an index that follows the
    item would fetch that item's page again a block: G times the bytes)."""
    tables = jnp.arange(6 * WIDE_PPN, dtype=jnp.int32).reshape(6, WIDE_PPN)
    lens = jnp.asarray([0, 5, PS, 1, 0, 3], jnp.int32)
    work = decode_work_list(tables, lens, page_size=PS, group=group)
    pool = np.asarray(work.pool_page_of).reshape(-1, group)[:int(work.count)]
    assert pool[:, 0].tolist() == [0, 7, 14, 21, 21, 35]
    assert (pool[:, 1:] == 7).all()
    assert _pages_fetched(pool) == 5 + group - 1
